"""Device-sharded filter-bank engine: partition balance, caller-order
restoration, single-device degradation, the sharded plan, and the
multi-device paths (per-shard programs, halo exchange, channel sharding)
in a forced-8-device subprocess."""
import json

import numpy as np
import pytest

from repro.compiler import bank_plan, compile_bank
from repro.distributed import bank_mesh, partition_bank, sharded_plan
from repro.filters import (FilterBankEngine, ShardedFilterBankEngine,
                           fir_bit_layers_batch, spread_lowpass_qbank)
from repro.kernels.blmac_fir import pack_bank_trits
from tests._subproc import run_py
from tests.differential import adversarial_bank, five_way_check


def _qbank(n_filters: int, taps: int = 31) -> np.ndarray:
    return spread_lowpass_qbank(n_filters, taps)


def _skewed_bank(taps: int = 31, n_dense: int = 8, n_sparse: int = 8,
                 seed: int = 0) -> np.ndarray:
    """Half dense 16-bit rows, half single-pulse rows, interleaved — the
    occupancy-skew case where a naive round-robin split puts every dense
    filter on the same shard."""
    rng = np.random.default_rng(seed)
    half = taps // 2
    rows = []
    for i in range(n_dense + n_sparse):
        h = np.zeros(half + 1, np.int64)
        if i % 2 == 0:
            h[:] = rng.integers(-(1 << 15), 1 << 15, half + 1)
        else:
            h[i % (half + 1)] = 1  # single pulse, layer 0
        rows.append(np.concatenate([h, h[:-1][::-1]]))
    return np.stack(rows)


# ---------------------------------------------------------------------------
# partition specs (pure planning — no devices involved)
# ---------------------------------------------------------------------------


def test_partition_is_permutation_and_uneven_counts():
    q = _qbank(13)
    part = partition_bank(pack_bank_trits(q), 4, q.shape[1])
    sizes = [len(a) for a in part.assign]
    assert sum(sizes) == 13 and min(sizes) >= 1
    order = np.concatenate(part.assign)
    assert np.array_equal(np.sort(order), np.arange(13))
    assert np.array_equal(order[part.inv], np.arange(13))


def test_partition_balances_occupancy_skew():
    q = _skewed_bank(n_dense=8, n_sparse=8)
    packed = pack_bank_trits(q)
    part = partition_bank(packed, 4, q.shape[1])
    # dense rows carry ~3 orders of magnitude more pulses than the
    # single-pulse rows: a count-equal split would leave one shard with
    # 4 dense rows (imbalance ≈ 2); the cost-weighted cut must not
    assert part.imbalance < 1.5, part.cost
    # occupancy-sorted contiguity: no shard mixes the two populations
    # more than at one boundary (signature sort groups them)
    sigs = [packed[a].any(axis=-1).sum(axis=-1) for a in part.assign]
    assert all(s.max() - s.min() <= 16 for s in sigs)


def test_partition_clamps_shards_to_bank():
    q = _qbank(3)
    part = partition_bank(pack_bank_trits(q), 8, q.shape[1])
    assert part.n_shards == 3
    assert all(len(a) == 1 for a in part.assign)


# ---------------------------------------------------------------------------
# the sharded plan (device-free)
# ---------------------------------------------------------------------------


def test_autotuner_shards_wide_bank_and_declines_narrow():
    """The bank axis takes one shard per mesh row; a bank narrower than
    the mesh takes one shard per filter, and a one-filter bank does not
    shard at all."""
    wide = compile_bank(_qbank(256, taps=63))
    plan, part, schedules = sharded_plan(wide, (8, 1))
    assert plan.n_bank_shards == 8, "256-filter bank should shard on 8 rows"
    assert len(schedules) == plan.n_bank_shards == part.n_shards
    two, _, _ = sharded_plan(compile_bank(_qbank(2, taps=31)), (8, 1))
    assert two.n_bank_shards == 2
    plan1, _, _ = sharded_plan(compile_bank(_qbank(1, taps=31)), (8, 1))
    assert plan1.n_bank_shards == 1
    assert not plan1.sharded


def test_autotuner_can_decline_the_data_axis():
    prog = compile_bank(_qbank(4, taps=31))
    # unasked, the data axis stays idle: one device per shard
    plan, _, _ = sharded_plan(prog, (1, 2))
    assert plan.n_data == 1 and plan.data_mode == "none"
    assert not plan.sharded
    # asking for an unavailable mode is an error, not a silent fallback
    with pytest.raises(ValueError):
        sharded_plan(prog, (1, 2), channels=3, data_mode="channels")
    with pytest.raises(ValueError):
        sharded_plan(prog, (2, 1), data_mode="time")


def test_forced_shard_count_is_respected():
    prog = compile_bank(_qbank(16, taps=31))
    plan, part, _ = sharded_plan(prog, (8, 1), n_bank_shards=4)
    assert plan.n_bank_shards == 4 and part.n_shards == 4
    # clamped to the mesh's bank rows
    plan, _, _ = sharded_plan(prog, (2, 1), n_bank_shards=4)
    assert plan.n_bank_shards == 2


def test_sharded_compiled_planning_and_degraded_engine():
    """A mesh of one device runs one shard of the one plan: the shard's
    plan is `bank_plan` of the whole program, and the stream is
    bit-exact."""
    q = _qbank(64, taps=63)
    eng = ShardedFilterBankEngine(q, mesh=bank_mesh(1, 1))
    assert (eng.n_bank_shards, eng.n_data) == (1, 1)
    assert eng.plan.shard_plans == (bank_plan(eng.program)[0],)
    assert "predicted" not in eng.describe()
    rng = np.random.default_rng(8)
    x = rng.integers(-128, 128, (1, 600))
    y = eng.push(x)
    expect = fir_bit_layers_batch(x, q)
    assert np.array_equal(np.asarray(y, np.int64), expect)


# ---------------------------------------------------------------------------
# single-device degradation + the five-way differential
# ---------------------------------------------------------------------------


def test_single_device_mesh_degrades_to_plain_engine():
    q = _qbank(9)
    mesh = bank_mesh(1, 1)
    eng = ShardedFilterBankEngine(q, mesh=mesh)
    assert eng.n_bank_shards == 1 and eng.data_mode == "none"
    plain = FilterBankEngine(q)
    rng = np.random.default_rng(1)
    x = rng.integers(-128, 128, 700)
    a = eng.push(x)
    b = plain.push(x)
    assert np.array_equal(a, b)
    # streaming state stays in lock-step across ragged pushes
    for sz in (3, 250, 97):
        x2 = rng.integers(-128, 128, sz)
        assert np.array_equal(eng.push(x2), plain.push(x2))
    assert eng.pending == plain.pending


def test_five_way_differential_adversarial():
    rep = five_way_check(adversarial_bank(taps=31), n_out=24, tile=128)
    assert rep.sharded_mesh[0] >= 1


def test_five_way_differential_skewed():
    rep = five_way_check(_skewed_bank(n_dense=4, n_sparse=4), n_out=32)
    assert rep.n_filters == 8


# ---------------------------------------------------------------------------
# multi-device legs (forced 8-device subprocess)
# ---------------------------------------------------------------------------


def test_sharded_engine_8_devices():
    out = run_py("""
import numpy as np
from repro.distributed import bank_mesh
from repro.filters import (ShardedFilterBankEngine, fir_bit_layers_batch,
                           spread_lowpass_qbank)

taps = 31
q = spread_lowpass_qbank(13, taps)
rng = np.random.default_rng(0)
x = rng.integers(-128, 128, 4096)
ref = fir_bit_layers_batch(x, q)[:, 0, :]

# uneven B=13 over 4 bank shards x 2 time shards (halo exchange);
# the data axis is used only when data_mode names it
eng = ShardedFilterBankEngine(q, mesh=bank_mesh(4, 2), n_bank_shards=4,
                              data_mode="time")
assert eng.data_mode == "time" and eng.n_bank_shards == 4
assert np.array_equal(eng.push(x)[:, 0, :], ref)
print("TIME_SHARDED_OK")

# streamed ragged chunks through the same mesh
eng.reset()
outs = []
i = 0
for sz in (17, 1000, 3, 2000, 1076):
    outs.append(eng.push(x[i:i + sz]))
    i += sz
y = np.concatenate([o for o in outs if o.shape[2]], axis=2)[:, 0, :]
assert np.array_equal(y, ref)
print("STREAM_OK")

# channel sharding: C=4 over the data axis, no halo needed
C = 4
xc = rng.integers(-128, 128, (C, 2048))
refc = fir_bit_layers_batch(xc, q)
engc = ShardedFilterBankEngine(q, channels=C, mesh=bank_mesh(4, 2),
                               n_bank_shards=4, data_mode="channels")
assert engc.data_mode == "channels"
assert np.array_equal(engc.push(xc), refc)
print("CHANNELS_OK")

# caller-order restoration under a shuffled bank: outputs must follow
# the CALLER's row order, not the occupancy sort
perm = rng.permutation(13)
engp = ShardedFilterBankEngine(q[perm], mesh=bank_mesh(8, 1))
assert np.array_equal(engp.push(x)[:, 0, :], ref[perm])
print("ORDER_OK")
""", devices=8)
    for marker in ("TIME_SHARDED_OK", "STREAM_OK", "CHANNELS_OK", "ORDER_OK"):
        assert marker in out


def test_five_way_differential_8_devices():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = run_py(f"""
import sys
sys.path.insert(0, {root!r})
from tests.differential import adversarial_bank, five_way_check
rep = five_way_check(adversarial_bank(taps=31), n_out=24, tile=128)
assert rep.sharded_mesh[0] >= 1
print("FIVE_WAY_8DEV_OK", rep.sharded_mesh)
""", devices=8)
    assert "FIVE_WAY_8DEV_OK" in out


# Each case pushes two chunks through a 4-device engine and reports the
# second push's output: the reassembly under test wraps every
# `_materialize` call, recording the shard blocks it assembles, the
# partition rows it assembles them with, and the host memory the call
# allocates besides what the shard reads keep (tracemalloc sees numpy's
# buffers).  The output is allocated before the first read, so that
# each shard is scattered while the next one is read.
_REASSEMBLY = """
import json, tracemalloc
import numpy as np
from repro.distributed import bank_mesh
from repro.distributed.faultbank import FaultInjector
from repro.filters import (ShardedFilterBankEngine, fir_bit_layers_batch,
                           spread_lowpass_qbank)

E = ShardedFilterBankEngine
read_shard, materialize = E._materialize_shard, E._materialize
calls = []


def recorded_shard(self, s, p, y, off):
    before = tracemalloc.get_traced_memory()[0]
    part = read_shard(self, s, p, y, off)
    call = calls[-1]
    call["parts"].append(part)
    call["read"] += tracemalloc.get_traced_memory()[0] - before
    return part


def recorded(self, p):
    calls.append({"parts": [], "assign": p._assign, "read": 0})
    base = tracemalloc.get_traced_memory()[0]
    tracemalloc.reset_peak()
    out = materialize(self, p)
    call = calls[-1]
    call["grew"] = tracemalloc.get_traced_memory()[1] - base - call["read"]
    return out


E._materialize_shard, E._materialize = recorded_shard, recorded
# count the device-to-host copies started (at dispatch, ahead of the reads)
from jax._src.array import ArrayImpl
start_copy, copies = ArrayImpl.copy_to_host_async, [0]


def counted_copy(self):
    copies[0] += 1
    return start_copy(self)


ArrayImpl.copy_to_host_async = counted_copy
tracemalloc.start()
q = spread_lowpass_qbank(40, 31)[np.random.default_rng(0).permutation(40)]
# both pushes pad to one 2,048-sample shape: one compile per shard
x = np.random.default_rng(1).integers(-128, 128, 4066)
ref = fir_bit_layers_batch(x, q)
kill = lambda s, k: FaultInjector().kill_shard(s, at_chunk=k)
engines = {
    "bank": lambda: E(q, mesh=bank_mesh(4, 1), n_bank_shards=4),
    "time": lambda: E(q, mesh=bank_mesh(2, 2), n_bank_shards=2,
                      data_mode="time"),
    "degraded": lambda: E(q, mesh=bank_mesh(2, 1), n_bank_shards=2,
                          fault_injector=kill(0, 0)),
    "rearmed": lambda: E(q, mesh=bank_mesh(4, 1), n_bank_shards=4,
                         fault_injector=kill(1, 1)),
}
res = {}
for name, make in engines.items():
    eng = make()
    first = eng.push(x[:2048])
    reads, copies[0] = eng.read_stats(), 0
    pending = eng.push_async(x[2048:])
    started = copies[0]
    out = pending.result()
    reads = {k: v - reads[k] for k, v in eng.read_stats().items()}
    call = calls[-1]
    res[name] = {
        "exact": bool(np.array_equal(np.concatenate([first, out], axis=2),
                                     ref)),
        "dtype": str(out.dtype),
        "c_contiguous": bool(out.flags.c_contiguous),
        "writeable": bool(out.flags.writeable),
        "shares": bool(np.shares_memory(out, first) or any(
            np.shares_memory(out, a) for part in call["parts"]
            for a in (part, part.base) if isinstance(a, np.ndarray))),
        "grew": call["grew"] / out.nbytes,
        "current_assign": call["assign"] is eng.partition.assign,
        "shards": len(call["assign"]),
        "parts": len(call["parts"]),
        "degraded": eng._plain is not None,
        "data_mode": eng.data_mode,
        "replayed": eng.fault_stats()["replayed_chunks"],
        "reads": reads,
        "copies": [started, copies[0]],
    }
print(json.dumps(res))
"""
_REASSEMBLY_CASES = {
    "bank": dict(shards=4, degraded=False, data_mode="none", replayed=0),
    "time": dict(shards=2, degraded=False, data_mode="time", replayed=0),
    "degraded": dict(shards=1, degraded=True, data_mode="none", replayed=1),
    "rearmed": dict(shards=3, degraded=False, data_mode="none", replayed=1),
}


@pytest.fixture(scope="module")
def reassembled():
    out = run_py(_REASSEMBLY, devices=4)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("case", sorted(_REASSEMBLY_CASES))
def test_sharded_push_output_is_a_fresh_exact_array(reassembled, case):
    """The pushed output equals the oracle bit for bit, and is a new
    C-contiguous, writeable int32 array that shares no memory with any
    shard block or with the previous push's output.  A chunk re-armed
    after a re-partition (``rearmed``) or a degradation (``degraded``)
    is assembled with the partition it was replayed on."""
    got = reassembled[case]
    assert got["exact"]
    assert got["dtype"] == "int32"
    assert got["c_contiguous"] and got["writeable"]
    assert not got["shares"]
    assert got["current_assign"]
    assert got["parts"] == got["shards"]
    for key, want in _REASSEMBLY_CASES[case].items():
        assert got[key] == want, (key, got)


@pytest.mark.parametrize("case", sorted(_REASSEMBLY_CASES))
def test_sharded_reassembly_is_one_pass(reassembled, case):
    """Besides the shard reads, assembling the output allocates the
    output and nothing of its size besides: no concatenated or permuted
    bank-sized intermediate (a second pass would grow it to ~2×)."""
    assert 1.0 <= reassembled[case]["grew"] < 1.25, reassembled[case]


# read_stats() over the second push, and the host copies started by its
# dispatch and by the whole push: every device block's copy starts at
# dispatch, and every shard but the last is scattered while a later one
# is unread.  ``rearmed`` counts its aborted attempt (shards 0, 2 and 3
# dispatched, shard 0 read and scattered before shard 1's loss) and the
# 3-shard replay; the degraded engine's one block is numpy, read and
# written inline.
_READS_PER_PUSH = {
    "bank": ({"prefetched_reads": 4, "overlapped_scatters": 3}, [4, 4]),
    "time": ({"prefetched_reads": 2, "overlapped_scatters": 1}, [2, 2]),
    "degraded": ({"prefetched_reads": 0, "overlapped_scatters": 0}, [0, 0]),
    "rearmed": ({"prefetched_reads": 1 + 3, "overlapped_scatters": 1 + 2},
                [3, 3 + 3]),
}


@pytest.mark.parametrize("case", sorted(_READS_PER_PUSH))
def test_sharded_read_stats_per_push(reassembled, case):
    reads, copies = _READS_PER_PUSH[case]
    assert reassembled[case]["reads"] == reads
    assert reassembled[case]["copies"] == copies


# Faults while scatters are in flight, on a 4-device engine: the second
# push's shard-0 scatter is held for 0.3 s (and in the ``worker_*``
# cases then raises) while shard 2 faults.  Every scatter is logged
# with the output array it wrote, so the test can tell the aborted
# attempt's output from the one returned.
_FAULTS = """
import json, time
import numpy as np
from repro.distributed import bank_mesh
from repro.distributed.faultbank import FaultInjector
from repro.filters import (ShardedFilterBankEngine, fir_bit_layers_batch,
                           spread_lowpass_qbank)
from repro.filters import sharded

E = ShardedFilterBankEngine
q = spread_lowpass_qbank(40, 31)[np.random.default_rng(0).permutation(40)]
x = np.random.default_rng(1).integers(-128, 128, 4066)
ref = fir_bit_layers_batch(x, q)
scatter, replay_one = sharded._scatter, E._replay_one
held = {}      # rows -> what the held scatter does when it wakes
outs, writes, replays = [], [], []


def logged_scatter(out, rows, part):
    t0 = time.monotonic()
    action = held.pop(id(rows), None)
    try:
        if action is not None:
            time.sleep(0.3)
            if action == "raise":
                raise RuntimeError("scatter failed")
        scatter(out, rows, part)
    finally:
        if not any(o is out for o in outs):
            outs.append(out)
        k = next(i for i, o in enumerate(outs) if o is out)
        writes.append({"out": k, "held": action is not None,
                       "t0": t0, "t1": time.monotonic()})


def logged_replay(self, p):
    replays.append(time.monotonic())
    return replay_one(self, p)


sharded._scatter, E._replay_one = logged_scatter, logged_replay
cases = {
    "corrupt": (FaultInjector().corrupt_output(2, at_chunk=1), None),
    "lost": (FaultInjector().kill_shard(2, at_chunk=1), None),
    "worker_and_corrupt": (FaultInjector().corrupt_output(2, at_chunk=1),
                           "raise"),
    "worker_alone": (None, "raise"),
}
res = {}
for name, (inj, action) in cases.items():
    eng = E(q, mesh=bank_mesh(4, 1), n_bank_shards=4, integrity_check=True,
            fault_injector=inj)
    first = eng.push(x[:2048])
    del outs[:], writes[:], replays[:]
    held[id(eng.partition.assign[0])] = action or "wait"
    got = {"raised": None, "context": None}
    try:
        out = eng.push(x[2048:])
    except RuntimeError as e:
        out = None
        got.update(raised=type(e).__name__,
                   context=type(e.__context__).__name__
                   if e.__context__ is not None else None)
    held_w = [w for w in writes if w["held"]]
    got.update(
        exact=out is not None and bool(
            np.array_equal(np.concatenate([first, out], axis=2), ref)),
        returned=next((i for i, o in enumerate(outs) if o is out), None),
        writes=[sum(w["out"] == k for w in writes) for k in range(len(outs))],
        held=len(held_w),
        held_done_before_replay=bool(held_w) and bool(replays)
        and held_w[0]["t1"] <= replays[0],
        replays=len(replays),
        replayed_chunks=eng.fault_stats()["replayed_chunks"],
    )
    res[name] = got
print(json.dumps(res))
"""


@pytest.fixture(scope="module")
def faulted():
    out = run_py(_FAULTS, devices=4)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("case,shards_after", [("corrupt", 4), ("lost", 3)])
def test_shard_fault_during_scatter_is_replayed_exactly(faulted, case,
                                                        shards_after):
    """Shard 2 faults while shard 0's scatter is still running: the
    materialize waits for the scatters it handed out (shards 0 and 1)
    before the fault reaches the replay, and the caller gets the
    replay's output, exact, never the aborted attempt's."""
    got = faulted[case]
    assert got["raised"] is None
    assert got["exact"]
    assert got["held"] == 1 and got["held_done_before_replay"]
    assert got["replayed_chunks"] == 1
    # the aborted output took shards 0 and 1; the returned one, every
    # shard of the partition the chunk was replayed on
    assert got["writes"] == [2, shards_after]
    assert got["returned"] == 1


@pytest.mark.parametrize("case,context", [
    ("worker_alone", None), ("worker_and_corrupt", "ShardCorruption")])
def test_scatter_worker_error_is_raised(faulted, case, context):
    """A scatter that raises on its worker reaches the caller, also when
    a shard fault is raised meanwhile (the fault is its context), and
    no output is returned."""
    got = faulted[case]
    assert got["raised"] == "RuntimeError"
    assert got["context"] == context
    assert got["returned"] is None
    assert got["replays"] == 0


def test_dropped_engines_leave_no_scatter_threads():
    """Each engine's scatter pool ends with the engine: building,
    pushing through and dropping 50 four-shard engines leaves the
    process's thread count where it started."""
    out = run_py("""
import gc, threading, time
import numpy as np
from repro.distributed import bank_mesh
from repro.filters import ShardedFilterBankEngine as E, spread_lowpass_qbank

# one jitted program per shard schedule, shared by every engine, so the
# loop compiles once
make, shared = E._make_scheduled_fn, {}
E._make_scheduled_fn = lambda self, sched, tile: shared.setdefault(
    (id(sched), tile), make(self, sched, tile))
q = spread_lowpass_qbank(16, 15)
x = np.random.default_rng(0).integers(-128, 128, 300)
before, most, overlapped = threading.active_count(), 0, 0
for _ in range(50):
    eng = E(q, mesh=bank_mesh(4, 1), n_bank_shards=4, tile=128)
    eng.push(x)
    most = max(most, threading.active_count())
    overlapped += eng.read_stats()["overlapped_scatters"]
    del eng
gc.collect()
deadline = time.monotonic() + 10
while threading.active_count() > before and time.monotonic() < deadline:
    time.sleep(0.05)
print(before, most, threading.active_count(), overlapped)
""", devices=4)
    before, most, after, overlapped = map(int, out.split()[-4:])
    assert overlapped == 50 * 3  # every engine scattered on its pool
    assert most > before
    assert after <= before


def test_async_double_buffered_server():
    from repro.serving import AsyncBankServer

    q = _qbank(6)
    eng = ShardedFilterBankEngine(q)
    server = AsyncBankServer(eng, depth=2)
    rng = np.random.default_rng(2)
    x = rng.integers(-128, 128, 4 * 600)
    ref = fir_bit_layers_batch(x, q)[:, 0, :]
    got = []
    for k in range(4):
        for done in server.submit(x[k * 600: (k + 1) * 600]):
            got.append(done)
    assert server.inflight == 2  # double buffer stayed full
    got.extend(server.drain())
    assert server.inflight == 0
    y = np.concatenate([g for g in got if g.shape[2]], axis=2)[:, 0, :]
    assert np.array_equal(y, ref)
    assert server.chunks_in == server.chunks_out == 4


def test_pending_chunk_result_is_idempotent():
    q = _qbank(3)
    eng = ShardedFilterBankEngine(q)
    x = np.arange(500) % 100
    p = eng.push_async(x)
    a = p.result()
    b = p.result()
    assert a is b  # resolved once, cached


def test_all_zero_bank_sharded():
    q = np.zeros((5, 31), np.int64)
    eng = ShardedFilterBankEngine(q)
    x = np.random.default_rng(3).integers(-128, 128, 400)
    y = eng.push(x)
    assert y.shape == (5, 1, 400 - 31 + 1)
    assert not y.any()


def test_rejects_bad_inputs():
    q = _qbank(4)
    with pytest.raises(ValueError):
        ShardedFilterBankEngine(q, channels=0)
    eng = ShardedFilterBankEngine(q, channels=2)
    with pytest.raises(ValueError):
        eng.push(np.zeros((3, 100)))  # wrong channel count
