"""Fault tolerance of the sharded bank service: the taxonomy/injector/
watchdog substrate (`repro.distributed.faultbank`), tail-snapshot
capture/restore/persist (`repro.compiler.state`), the engine's
detect → re-partition → replay recovery, `AsyncBankServer`'s bounded
retry/deadline semantics, and the multi-device chaos legs (kill grids,
cascade to the degraded engine, time/channel mesh recovery) in a
forced-8-device subprocess."""
import json
import os

import numpy as np
import pytest

from repro.compiler import (SnapshotFormatError, TailSnapshot, compile_bank)
from repro.distributed.faultbank import (FaultInjector, PendingInvalidated,
                                         ShardHealth, ShardLost,
                                         StragglerStats, TransientShardError)
from repro.filters import (FilterBankEngine, ShardedFilterBankEngine,
                           fir_bit_layers_batch, spread_lowpass_qbank)
from repro.serving import AsyncBankServer
from tests._subproc import run_py

TAPS = 31


def _qbank(n_filters: int, taps: int = TAPS) -> np.ndarray:
    return spread_lowpass_qbank(n_filters, taps)


def _stream(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(-128, 128, n)


# ---------------------------------------------------------------------------
# substrate: compat re-exports, watchdog, injector (no devices involved)
# ---------------------------------------------------------------------------


def test_fault_module_reexports_survive_the_move():
    # StragglerStats / SimulatedFailure moved to faultbank; the train
    # module and the package root must keep serving the same objects
    import repro.distributed as dist
    from repro.distributed import fault, faultbank

    assert fault.StragglerStats is faultbank.StragglerStats
    assert fault.SimulatedFailure is faultbank.SimulatedFailure
    assert dist.StragglerStats is faultbank.StragglerStats
    for name in ("FaultInjector", "ShardHealth", "ShardLost",
                 "TransientShardError", "RetriesExhausted"):
        assert getattr(dist, name) is getattr(faultbank, name)


def test_straggler_stats_flags_only_with_history():
    st = StragglerStats(factor=2.0)
    assert not any(st.record(100.0) for _ in range(4))  # < 5 samples: never
    st = StragglerStats(factor=2.0)
    for _ in range(4):
        st.record(1.0)
    assert st.record(100.0)  # 5th sample: median window armed, 100 > 2x1
    assert not st.record(1.0)
    assert st.slow_steps == 1


def test_shard_health_reset_and_summary():
    h = ShardHealth(3, timeout=0.5, straggler_factor=3.0)
    for _ in range(6):
        h.record(0, 0.01)
    assert h.record(0, 1.0)  # straggler on shard 0
    s = h.summary()
    assert s["n_shards"] == 3 and s["timeout_s"] == 0.5
    assert s["heartbeats"] == [7, 0, 0] and s["slow_steps"][0] == 1
    h.reset(2)  # recovery re-partition rebuilds the per-shard series
    assert h.n_shards == 2 and h.summary()["heartbeats"] == [0, 0]


def test_injector_is_deterministic_and_slot_scoped():
    inj = FaultInjector().kill_shard(1, at_chunk=2).kill_shard(1, at_chunk=5)
    inj.fail_push(0, at_chunk=1, times=2).corrupt_output(2, at_chunk=3)
    # chunks before the kill pass; the kill then fires for EVERY chunk
    # until the engine removes the shard (a dead machine stays dead)
    inj.on_dispatch(1, 0)
    inj.on_dispatch(1, 1)
    with pytest.raises(ShardLost):
        inj.on_dispatch(1, 2)
    with pytest.raises(ShardLost):
        inj.on_dispatch(1, 3)
    assert inj.faults_injected()["kills"] == 1  # one kill event, not two
    # removal retires only the FIRED kill; the second (1, 5) entry keeps
    # targeting slot 1 of the recovered mesh
    inj.on_shard_removed(1)
    inj.on_dispatch(1, 3)
    with pytest.raises(ShardLost):
        inj.on_dispatch(1, 5)
    assert inj.faults_injected()["kills"] == 2
    # transients burn a per-(shard, chunk) budget, then pass
    for _ in range(2):
        with pytest.raises(TransientShardError):
            inj.on_dispatch(0, 1)
    inj.on_dispatch(0, 1)
    # corruption damages the block exactly `times` times
    a = np.zeros((2, 1, 4), np.int32)
    assert inj.corrupt(2, 3, a).sum() == 8
    assert inj.corrupt(2, 3, a).sum() == 0
    assert inj.faults_injected() == {
        "kills": 2, "delays": 0, "transients": 2, "corruptions": 1,
    }


# ---------------------------------------------------------------------------
# tail snapshots: capture / restore / persist (content-addressed)
# ---------------------------------------------------------------------------


def test_tail_snapshot_resumes_both_engines_bit_exactly():
    q = _qbank(5)
    x = _stream(0, 1200)
    ref = fir_bit_layers_batch(x, q)[:, 0, :]
    for make in (lambda: FilterBankEngine(q),
                 lambda: ShardedFilterBankEngine(q)):
        eng = make()
        eng.push(x[:700])
        snap = eng.snapshot_tail()
        assert snap.samples_in == 700
        a = eng.push(x[700:])  # the uninterrupted continuation
        fresh = make()
        fresh.restore_tail(snap)
        b = fresh.push(x[700:])  # resumed from the frozen state
        assert np.array_equal(a, b)
        assert np.array_equal(b[:, 0, :], ref[:, 700 - TAPS + 1:])


def test_tail_snapshot_rejects_foreign_program_and_channels():
    q = _qbank(4)
    other = compile_bank(_qbank(4, taps=15))
    for eng in (FilterBankEngine(q), ShardedFilterBankEngine(q)):
        eng.push(_stream(1, 400))
        snap = eng.snapshot_tail()
        with pytest.raises(ValueError, match="belongs to program"):
            FilterBankEngine(other).restore_tail(snap)
        with pytest.raises(ValueError, match="channels"):
            FilterBankEngine(q, channels=2).restore_tail(snap)


def test_tail_snapshot_file_roundtrip_and_format_errors(tmp_path):
    eng = FilterBankEngine(_qbank(3), channels=2)
    eng.push(np.stack([_stream(2, 500), _stream(3, 500)]))
    snap = eng.snapshot_tail()
    path = os.path.join(tmp_path, "tail.npz")
    snap.save(path)
    back = TailSnapshot.load(path)
    assert back.program_key == snap.program_key
    assert back.samples_in == snap.samples_in == 500
    assert back.samples_out == snap.samples_out
    assert np.array_equal(back.tail, snap.tail)
    eng2 = FilterBankEngine(_qbank(3), channels=2)
    eng2.restore_tail(back)
    assert eng2.pending == eng.pending
    # every way the file can be bad is a loud SnapshotFormatError
    bad = os.path.join(tmp_path, "bad.npz")
    with open(bad, "wb") as f:
        f.write(b"not a zipfile")
    with pytest.raises(SnapshotFormatError):
        TailSnapshot.load(bad)
    prog = os.path.join(tmp_path, "prog.npz")  # wrong kind of artifact
    eng.program.save(prog)
    with pytest.raises(SnapshotFormatError, match="not a tail-snapshot"):
        TailSnapshot.load(prog)


# ---------------------------------------------------------------------------
# engine semantics on a 1x1 mesh (fault paths that need no second device)
# ---------------------------------------------------------------------------


def test_reset_invalidates_inflight_pendings():
    eng = ShardedFilterBankEngine(_qbank(4))
    p = eng.push_async(_stream(4, 600))
    eng.reset()  # regression: used to let result() reassemble stale rows
    with pytest.raises(PendingInvalidated):
        p.result()
    # the reset stream itself is unharmed
    x = _stream(5, 600)
    assert np.array_equal(
        eng.push(x)[:, 0, :], fir_bit_layers_batch(x, _qbank(4))[:, 0, :]
    )


def test_restore_tail_invalidates_inflight_pendings():
    eng = ShardedFilterBankEngine(_qbank(4))
    snap = eng.snapshot_tail()
    p = eng.push_async(_stream(6, 500))
    eng.restore_tail(snap)
    with pytest.raises(PendingInvalidated):
        p.result()


def test_corruption_is_detected_and_replayed_bit_exactly():
    q = _qbank(5)
    inj = FaultInjector().corrupt_output(0, at_chunk=1, times=1)
    eng = ShardedFilterBankEngine(q, fault_injector=inj, integrity_check=True)
    x = _stream(7, 1024)
    a = eng.push(x[:512])
    b = eng.push(x[512:])  # corrupted once, healed by snapshot replay
    y = np.concatenate([a, b], axis=2)[:, 0, :]
    assert np.array_equal(y, fir_bit_layers_batch(x, q)[:, 0, :])
    st = eng.fault_stats()
    assert st["corruptions"] == 1 and st["replayed_chunks"] == 1
    assert st["detections"] == 1 and st["recoveries"] == 0


def test_persistent_corruption_escalates_to_loss():
    inj = FaultInjector().corrupt_output(0, at_chunk=0, times=10)
    eng = ShardedFilterBankEngine(_qbank(4), fault_injector=inj,
                                  integrity_check=True)
    with pytest.raises(ShardLost, match="no surviving devices"):
        eng.push(_stream(8, 600))
    # max_heals replays + the escalating detection, all counted
    assert eng.fault.corruptions == eng.max_heals + 1
    assert eng.fault.replayed_chunks == eng.max_heals


def test_losing_the_only_shard_is_unrecoverable_not_a_hang():
    inj = FaultInjector().kill_shard(0, at_chunk=0)
    eng = ShardedFilterBankEngine(_qbank(4), fault_injector=inj)
    p = eng.push_async(_stream(9, 500))  # dispatch does not raise
    with pytest.raises(ShardLost, match="no surviving devices"):
        p.result()
    assert eng.fault_stats()["detections"] == 1
    assert eng.fault_stats()["recoveries"] == 0


def test_watchdog_timeout_escalates_to_loss():
    inj = FaultInjector().delay_shard(0, at_chunk=0, seconds=0.6)
    eng = ShardedFilterBankEngine(_qbank(4), fault_injector=inj,
                                  shard_timeout=0.05)
    with pytest.raises(ShardLost):
        eng.push(_stream(10, 500))
    st = eng.fault_stats()
    assert st["timeouts"] == 1 and st["health"]["timeout_s"] == 0.05


# ---------------------------------------------------------------------------
# AsyncBankServer failure semantics (retry / deadline / ordering)
# ---------------------------------------------------------------------------


def test_server_retries_transients_then_succeeds():
    q = _qbank(5)
    inj = FaultInjector().fail_push(0, at_chunk=1, times=2)
    eng = ShardedFilterBankEngine(q, fault_injector=inj)
    server = AsyncBankServer(eng, depth=2, max_retries=3, backoff_s=1e-4)
    x = _stream(11, 4 * 512)
    got = []
    for k in range(4):
        got += server.submit(x[k * 512:(k + 1) * 512])
    got += server.drain()
    y = np.concatenate([g for g in got if g.shape[2]], axis=2)[:, 0, :]
    assert np.array_equal(y, fir_bit_layers_batch(x, q)[:, 0, :])
    assert server.retries == 2 and server.failed_chunks == 0
    st = server.fault_stats()
    assert st["engine"]["transients"] == 2
    assert st["engine"]["replayed_chunks"] >= 2  # each retry re-armed


def test_server_exhausts_retries_and_the_stream_survives():
    q = _qbank(5)
    inj = FaultInjector().fail_push(0, at_chunk=0, times=10)
    eng = ShardedFilterBankEngine(q, fault_injector=inj)
    server = AsyncBankServer(eng, depth=2, max_retries=2, backoff_s=1e-4)
    x = _stream(12, 2 * 500)
    from repro.distributed.faultbank import RetriesExhausted

    server.submit(x[:500])
    server.submit(x[500:])
    with pytest.raises(RetriesExhausted):
        server.drain()
    assert server.retries_exhausted == 1 and server.failed_chunks == 1
    # the failed chunk is DROPPED, not wedged: the next drain resolves
    # the younger chunk, whose outputs continue the stream bit-exactly
    # (the tail state advanced at dispatch; only chunk 0's outputs die)
    rest = server.drain()
    assert len(rest) == 1 and server.chunks_out == 1
    ref = fir_bit_layers_batch(x, q)[:, 0, :]
    assert np.array_equal(rest[0][:, 0, :], ref[:, 500 - TAPS + 1:])


def test_server_deadline_expires_before_the_retry_budget():
    inj = FaultInjector().fail_push(0, at_chunk=0, times=10)
    eng = ShardedFilterBankEngine(_qbank(4), fault_injector=inj)
    server = AsyncBankServer(eng, depth=1, max_retries=50,
                             backoff_s=0.02, deadline_s=0.01)
    from repro.distributed.faultbank import DeadlineExceeded

    server.submit(_stream(13, 500))
    with pytest.raises(DeadlineExceeded):
        server.drain()
    assert server.deadline_expired == 1 and server.retries_exhausted == 0
    assert server.inflight == 0  # dropped, never a hang


def test_server_delivers_resolved_outputs_when_a_later_chunk_fails():
    # regression: drain() used to DISCARD outputs already resolved in
    # its loop when a younger chunk then failed terminally — chunk 0's
    # outputs died with chunk 1's RetriesExhausted
    q = _qbank(5)
    inj = FaultInjector().fail_push(0, at_chunk=1, times=10)
    eng = ShardedFilterBankEngine(q, fault_injector=inj)
    server = AsyncBankServer(eng, depth=2, max_retries=1, backoff_s=1e-4)
    from repro.distributed.faultbank import RetriesExhausted

    x = _stream(15, 2 * 500)
    server.submit(x[:500])
    server.submit(x[500:])
    with pytest.raises(RetriesExhausted):
        server.drain()
    # chunk 0 resolved before chunk 1 failed: buffered, not lost
    assert server.fault_stats()["buffered"] == 1
    rest = server.drain()
    assert len(rest) == 1 and server.fault_stats()["buffered"] == 0
    ref = fir_bit_layers_batch(x, q)[:, 0, :]
    assert np.array_equal(rest[0][:, 0, :], ref[:, :500 - TAPS + 1])


def test_server_backoff_never_sleeps_past_the_deadline():
    # regression: uncapped exponential backoff could sleep an arbitrary
    # multiple of deadline_s before re-checking — a 10 s backoff against
    # a 50 ms deadline used to stall the stream for seconds
    import time

    inj = FaultInjector().fail_push(0, at_chunk=0, times=100)
    eng = ShardedFilterBankEngine(_qbank(4), fault_injector=inj)
    server = AsyncBankServer(eng, depth=1, max_retries=1000,
                             backoff_s=10.0, deadline_s=0.05)
    from repro.distributed.faultbank import DeadlineExceeded

    server.submit(_stream(16, 500))
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceeded):
        server.drain()
    assert time.monotonic() - t0 < 2.0  # was ≥ 10 s before the clamp
    assert server.deadline_expired == 1 and server.inflight == 0


def test_server_backoff_is_capped(monkeypatch):
    import time

    sleeps = []
    monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
    inj = FaultInjector().fail_push(0, at_chunk=0, times=100)
    eng = ShardedFilterBankEngine(_qbank(4), fault_injector=inj)
    server = AsyncBankServer(eng, depth=1, max_retries=6,
                             backoff_s=1e-3, max_backoff_s=4e-3)
    from repro.distributed.faultbank import RetriesExhausted

    server.submit(_stream(17, 400))
    with pytest.raises(RetriesExhausted):
        server.drain()
    assert sleeps[:3] == [1e-3, 2e-3, 4e-3]  # doubling…
    assert max(sleeps) <= 4e-3  # …until the cap bites
    with pytest.raises(ValueError):
        AsyncBankServer(eng, max_backoff_s=0.0)


def test_server_fault_stats_are_json_ready():
    eng = ShardedFilterBankEngine(_qbank(4), fault_injector=FaultInjector())
    server = AsyncBankServer(eng)
    server.submit(_stream(14, 400))
    server.drain()
    st = server.fault_stats()
    json.dumps(st)  # the whole surface must serialize
    assert st["chunks_in"] == st["chunks_out"] == 1
    assert st["engine"]["n_bank_shards"] == 1
    assert st["engine"]["injected"]["kills"] == 0
    assert st["engine"]["health"]["heartbeats"] == [1]


# ---------------------------------------------------------------------------
# multi-device recovery legs (forced-8-device subprocess)
# ---------------------------------------------------------------------------


def test_kill_and_recover_8_devices():
    out = run_py("""
import numpy as np
from repro.distributed import bank_mesh
from repro.distributed.faultbank import FaultInjector
from repro.filters import (ShardedFilterBankEngine, fir_bit_layers_batch,
                           spread_lowpass_qbank)
from repro.serving import AsyncBankServer

taps = 31
rng = np.random.default_rng(0)

# -- kill one of four bank shards mid-stream, behind the server --------
q = spread_lowpass_qbank(13, taps)
n_chunks, chunk = 6, 512
x = rng.integers(-128, 128, n_chunks * chunk)
ref = fir_bit_layers_batch(x, q)[:, 0, :]
inj = FaultInjector().kill_shard(1, at_chunk=2)
eng = ShardedFilterBankEngine(q, mesh=bank_mesh(4, 1), n_bank_shards=4,
                              fault_injector=inj)
server = AsyncBankServer(eng, depth=2)
got = []
for k in range(n_chunks):
    got += server.submit(x[k * chunk:(k + 1) * chunk])
got += server.drain()
y = np.concatenate([g for g in got if g.shape[2]], axis=2)[:, 0, :]
assert np.array_equal(y, ref), "recovered stream != uninterrupted stream"
st = eng.fault_stats()
assert st["detections"] == 1 and st["recoveries"] == 1
assert st["lost_shards"] == 1 and st["replayed_chunks"] == 2
assert server.failed_chunks == 0 and server.chunks_out == n_chunks
assert eng.n_bank_shards == 3 and not st["degraded"]
print("KILL_RECOVER_OK", eng.describe())

# -- cascade: three kills degrade 4x1 to the plain 1x1 engine ----------
q2 = spread_lowpass_qbank(9, taps)
x2 = rng.integers(-128, 128, 8 * 400)
ref2 = fir_bit_layers_batch(x2, q2)[:, 0, :]
inj2 = (FaultInjector().kill_shard(0, at_chunk=1)
        .kill_shard(1, at_chunk=3).kill_shard(0, at_chunk=5))
eng2 = ShardedFilterBankEngine(q2, mesh=bank_mesh(4, 1), n_bank_shards=4,
                               fault_injector=inj2)
outs = [eng2.push(x2[k * 400:(k + 1) * 400]) for k in range(8)]
y2 = np.concatenate([o for o in outs if o.shape[2]], axis=2)[:, 0, :]
assert np.array_equal(y2, ref2), "degraded stream != uninterrupted stream"
st2 = eng2.fault_stats()
assert st2["detections"] == 3 and st2["recoveries"] == 3
assert st2["lost_shards"] == 3 and st2["degraded"]
assert eng2.n_bank_shards == 1 and "DEGRADED" in eng2.describe()
assert inj2.faults_injected()["kills"] == 3
print("CASCADE_OK", eng2.describe())
""", devices=8)
    assert "KILL_RECOVER_OK" in out and "CASCADE_OK" in out


def test_degraded_engine_runs_on_the_surviving_device():
    """Losing one of two shards degrades to the plain engine ON THE
    SURVIVOR: its operands are placed there, not on the default device
    (which is the one that was lost)."""
    out = run_py("""
import jax
import numpy as np
from repro.distributed import bank_mesh
from repro.distributed.faultbank import FaultInjector
from repro.filters import (ShardedFilterBankEngine, fir_bit_layers_batch,
                           spread_lowpass_qbank)

q = spread_lowpass_qbank(64, 31)
x = np.random.default_rng(0).integers(-128, 128, 4 * 300)
inj = FaultInjector().kill_shard(0, at_chunk=1)
eng = ShardedFilterBankEngine(q, mesh=bank_mesh(2, 1), n_bank_shards=2,
                              fault_injector=inj)
outs = [eng.push(x[k * 300:(k + 1) * 300]) for k in range(4)]
y = np.concatenate([o for o in outs if o.shape[2]], axis=2)
assert np.array_equal(y, fir_bit_layers_batch(x, q))
assert eng.fault_stats()["degraded"]
assert eng._plain.dispatch_plan.mode == "scheduled"
placed = {d for op in eng._plain._group_ops if op is not None
          for d in op.devices()}
assert placed == {jax.devices()[1]}, placed
print("SURVIVOR_OK")
""", devices=2)
    assert "SURVIVOR_OK" in out


def test_data_axis_meshes_recover_8_devices():
    out = run_py("""
import numpy as np
from repro.distributed import bank_mesh
from repro.distributed.faultbank import FaultInjector
from repro.filters import (ShardedFilterBankEngine, fir_bit_layers_batch,
                           spread_lowpass_qbank)
from repro.serving import AsyncBankServer

taps = 31
rng = np.random.default_rng(1)
q = spread_lowpass_qbank(8, taps)

# -- time-sharded 2x2: lose a bank row, keep the halo-exchange axis ----
x = rng.integers(-128, 128, 6 * 600)
ref = fir_bit_layers_batch(x, q)[:, 0, :]
inj = FaultInjector().kill_shard(1, at_chunk=2)
eng = ShardedFilterBankEngine(q, mesh=bank_mesh(2, 2), n_bank_shards=2,
                              data_mode="time", fault_injector=inj,
                              integrity_check=True)
assert eng.data_mode == "time"
outs = [eng.push(x[k * 600:(k + 1) * 600]) for k in range(6)]
y = np.concatenate([o for o in outs if o.shape[2]], axis=2)[:, 0, :]
assert np.array_equal(y, ref)
assert eng.n_bank_shards == 1 and eng.n_data == 2
assert eng.data_mode == "time"
print("TIME_RECOVER_OK", eng.describe())

# -- channel-sharded 2x2 behind the server: C=2 survives a bank kill ---
C = 2
xc = rng.integers(-128, 128, (C, 6 * 512))
refc = fir_bit_layers_batch(xc, q)
injc = FaultInjector().kill_shard(0, at_chunk=3)
engc = ShardedFilterBankEngine(q, channels=C, mesh=bank_mesh(2, 2),
                               n_bank_shards=2, data_mode="channels",
                               fault_injector=injc)
server = AsyncBankServer(engc, depth=2)
got = []
for k in range(6):
    got += server.submit(xc[:, k * 512:(k + 1) * 512])
got += server.drain()
yc = np.concatenate([g for g in got if g.shape[2]], axis=2)
assert np.array_equal(yc, refc)
assert server.failed_chunks == 0 and server.chunks_out == 6
assert engc.fault_stats()["recoveries"] == 1
print("CHANNELS_RECOVER_OK", engc.describe())
""", devices=8)
    assert "TIME_RECOVER_OK" in out and "CHANNELS_RECOVER_OK" in out


def test_chaos_differential_grid_8_devices():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = run_py(f"""
import sys
sys.path.insert(0, {root!r})
from tests.differential import adversarial_bank, chaos_check
from tests.test_sharded_bank import _skewed_bank

# kill grids over the two nastiest banks in the harness: the mixed
# adversarial bank (empty rows, extreme-layer pulses, dense rows) and
# the occupancy-skewed bank — single kills and a two-kill cascade,
# every point bit-exact vs the Eq. 2 oracle with the integrity probe on
adv = adversarial_bank(taps=31)
for kills in ([(1, 2)], [(3, 1)], [(0, 1), (1, 3)]):
    stats = chaos_check(adv, kills, n_bank_shards=4)
    assert stats["lost_shards"] == len(kills)
print("CHAOS_ADVERSARIAL_OK")

skew = _skewed_bank(n_dense=4, n_sparse=4)
for kills in ([(2, 1)], [(0, 2), (0, 4)]):
    stats = chaos_check(skew, kills, n_bank_shards=4, seed=7)
    assert stats["lost_shards"] == len(kills)
print("CHAOS_SKEWED_OK")
""", devices=8)
    assert "CHAOS_ADVERSARIAL_OK" in out and "CHAOS_SKEWED_OK" in out
