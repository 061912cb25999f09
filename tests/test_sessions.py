"""Multi-tenant session serving: `repro.serving.BankSessionServer`.

The load-bearing property: ANY schedule of pushes across N sessions —
independently-paced chunk sizes, arbitrary step() points, mid-stream
filter hot-swap, pause/resume, program swap — produces bit-exactly the
stream N dedicated per-session `FilterBankEngine`s of the same program
would produce.  Batching into shared lanes is a pure scheduling
decision, never an arithmetic one.
"""
import json

import numpy as np
import pytest

from repro.compiler import TailSnapshot, compile_bank
from repro.core.costmodel import predict_session_step_us, SESSION_LANE_US
from repro.filters import (FilterBankEngine, fir_bit_layers_batch,
                           spread_lowpass_qbank)
from repro.serving import AdmissionRejected, BankSessionServer
from tests._subproc import run_py

TAPS = 31


def _program(n_filters: int, taps: int = TAPS, bits: int = 16):
    return compile_bank(spread_lowpass_qbank(n_filters, taps, coeff_bits=bits))


def _push_both(session, ref, rows, chunk, ref_out):
    session.push(chunk)
    ref_out.append(ref.push(chunk[None, :])[np.asarray(rows), 0])


# ---------------------------------------------------------------------------
# the bit-exactness property: arbitrary interleavings vs dedicated engines
# ---------------------------------------------------------------------------


def test_any_interleaving_matches_dedicated_engines():
    rng = np.random.default_rng(0)
    prog = _program(16)
    srv = BankSessionServer(prog, n_slots=3, interpret=True, auto_step=False)
    sels = [[0, 3], [5], [7, 8, 9], [1, 15], [2]]
    sessions = [srv.open_session(r) for r in sels]
    refs = [FilterBankEngine(prog, channels=1, interpret=True) for _ in sels]
    ref_out = [[] for _ in sels]
    # random schedule: every iteration a random subset of sessions
    # pushes a random-sized chunk (including tiny priming chunks), and
    # the server steps at random points — more sessions than slots, so
    # steps routinely take multiple rounds
    for _ in range(12):
        for i in rng.permutation(len(sessions)):
            if rng.random() < 0.7:
                chunk = rng.integers(-128, 128, int(rng.integers(1, 50)))
                _push_both(sessions[i], refs[i], sels[i], chunk, ref_out[i])
        if rng.random() < 0.6:
            srv.step()
    srv.step()
    for i, s in enumerate(sessions):
        got = s.pull()
        want = np.concatenate(ref_out[i], axis=1)
        assert np.array_equal(got, want), f"session {i} diverged"
    st = srv.serve_stats()
    assert st["samples_out"] == sum(r.samples_out for r in refs)


def test_interleaving_with_hot_swap_and_pause_resume():
    # one session through three eras — original selection, hot-swapped
    # selection, resumed-from-snapshot — against ONE dedicated engine
    # that just keeps streaming: the tail carries across both events
    rng = np.random.default_rng(1)
    prog = _program(12)
    srv = BankSessionServer(prog, n_slots=2, interpret=True, auto_step=False)
    rows = [2, 7]
    s = srv.open_session(rows)
    ref = FilterBankEngine(prog, channels=1, interpret=True)
    ref_out = []
    for _ in range(4):
        chunk = rng.integers(-128, 128, int(rng.integers(5, 60)))
        _push_both(s, ref, rows, chunk, ref_out)
    srv.step()
    era1 = s.pull()
    assert np.array_equal(era1, np.concatenate(ref_out, axis=1))
    # mid-stream selection hot-swap: tail carries, output shape changes
    rows = [0, 4, 9]
    assert s.swap_filters(rows).shape[1] == 0  # already flushed + pulled
    ref_out = []
    for _ in range(3):
        chunk = rng.integers(-128, 128, int(rng.integers(5, 60)))
        _push_both(s, ref, rows, chunk, ref_out)
    srv.step()
    # mid-stream pause → resume (through the snapshot object)
    snap = s.pause()
    era2 = s.pull()  # pull still works on the paused handle
    assert snap.session == s.session_id
    assert np.array_equal(era2, np.concatenate(ref_out, axis=1))
    s = srv.resume_session(snap, rows)
    ref_out = []
    for _ in range(3):
        chunk = rng.integers(-128, 128, int(rng.integers(5, 60)))
        _push_both(s, ref, rows, chunk, ref_out)
    srv.step()
    era3 = s.pull()
    assert np.array_equal(era3, np.concatenate(ref_out, axis=1))


def test_program_hot_swap_is_zero_downtime_and_bit_exact():
    rng = np.random.default_rng(2)
    qb_a = spread_lowpass_qbank(8, TAPS)
    qb_b = spread_lowpass_qbank(8, TAPS, coeff_bits=12)
    srv = BankSessionServer(qb_a, n_slots=2, interpret=True, auto_step=False)
    rows = [1, 6]
    s = srv.open_session(rows)
    ref = FilterBankEngine(srv.program, channels=1, interpret=True)
    x1 = rng.integers(-128, 128, 90)
    s.push(x1)
    srv.step()
    want1 = ref.push(x1[None, :])[rows, 0]
    assert np.array_equal(s.pull(), want1)
    old_key = srv.program.key
    srv.swap_program(qb_b)
    assert srv.program.key != old_key and srv.program_swaps == 1
    # the dedicated reference for the new era inherits the same raw
    # input history — exactly what the server's per-session tails carry
    ref_b = FilterBankEngine(srv.program, channels=1, interpret=True)
    ref_b._tail = ref._tail.copy()
    x2 = rng.integers(-128, 128, 90)
    s.push(x2)
    srv.step()
    want2 = ref_b.push(x2[None, :])[rows, 0]
    assert np.array_equal(s.pull(), want2)
    # swapping identical content is a ProgramCache hit, not a recompile
    srv.swap_program(qb_b)
    assert srv.program_swaps == 2
    with pytest.raises(ValueError):
        srv.swap_program(spread_lowpass_qbank(8, TAPS + 2))  # taps differ


# ---------------------------------------------------------------------------
# session lifecycle: snapshots, admission, eviction
# ---------------------------------------------------------------------------


def test_snapshot_session_field_round_trips_through_disk(tmp_path):
    prog = _program(6)
    srv = BankSessionServer(prog, n_slots=2, interpret=True, auto_step=False)
    s = srv.open_session([0, 2], session_id="tenant-42")
    s.push(np.arange(100))
    srv.step()
    s.pull()
    snap = s.pause()
    path = tmp_path / "tenant-42.npz"
    snap.save(path)
    loaded = TailSnapshot.load(path)
    assert loaded.session == "tenant-42"
    assert loaded.program_key == prog.select([0, 2]).key
    # a resumed stream continues bit-exactly from the file
    s2 = srv.resume_session(loaded, [0, 2])
    assert s2.session_id == "tenant-42"
    ref = FilterBankEngine(prog, channels=1, interpret=True)
    ref.push(np.arange(100)[None, :])
    x = np.arange(100, 160) - 128  # 8-bit samples
    s2.push(x)
    srv.step()
    assert np.array_equal(s2.pull(), ref.push(x[None, :])[[0, 2], 0])
    # resuming under the wrong selection is a loud error
    with pytest.raises(ValueError):
        srv.resume_session(loaded, [0, 3])


def test_admission_control_rejects_over_budget():
    prog = _program(4)
    srv = BankSessionServer(
        prog, n_slots=2, interpret=True, step_budget_us=1.0
    )
    with pytest.raises(AdmissionRejected) as ei:
        srv.open_session([0])
    assert ei.value.predicted_us > ei.value.budget_us == 1.0
    assert srv.serve_stats()["admission_rejections"] == 1
    # the budget uses the cost model's round structure
    base = srv.predicted_step_us(extra_sessions=1)
    assert base == predict_session_step_us(srv._dispatch_us(), 1, 2)


def test_eviction_parks_idle_lru_and_push_readmits():
    prog = _program(4)
    srv = BankSessionServer(
        prog, n_slots=2, interpret=True, max_sessions=2, auto_step=False
    )
    a = srv.open_session([0])
    b = srv.open_session([1])
    c = srv.open_session([2])  # over the cap: parks the LRU idle (a)
    assert a.parked and not b.parked and not c.parked
    assert srv.evictions == 1
    st = srv.serve_stats()
    assert st["active"] == 2 and st["parked"] == 1
    # a parked session's stream survives parking bit-exactly: push
    # re-admits it transparently (parking someone else)
    ref = FilterBankEngine(prog, channels=1, interpret=True)
    x = np.arange(80)
    a.push(x)
    assert not a.parked and srv.evictions == 2
    srv.step()
    assert np.array_equal(a.pull(), ref.push(x[None, :])[[0], 0])
    # with every session busy, the cap is a hard rejection
    for s in srv.sessions.values():
        if not s.parked:
            s.push(np.arange(5))
    with pytest.raises(AdmissionRejected):
        srv.open_session([3])


def test_serve_stats_are_json_ready():
    prog = _program(6)
    srv = BankSessionServer(prog, n_slots=2, interpret=True)
    s = srv.open_session([0, 1])
    s.push(np.arange(64))
    s.push(np.arange(64))
    st = srv.serve_stats()
    json.dumps(st)  # the whole surface must serialize
    assert st["sessions"] == st["active"] == 1
    assert st["chunks_in"] == 2 and st["steps"] >= 1
    assert 0.0 < st["occupancy"] <= 1.0
    assert st["per_session"][s.session_id]["latency_p50_ms"] is not None
    assert st["predicted_step_us"] > 0


def test_session_validation_errors():
    prog = _program(4)
    srv = BankSessionServer(prog, n_slots=2, interpret=True)
    with pytest.raises(ValueError):
        srv.open_session([])  # empty selection
    with pytest.raises(ValueError):
        srv.open_session([4])  # out of range
    s = srv.open_session([0], session_id="dup")
    with pytest.raises(ValueError):
        srv.open_session([1], session_id="dup")
    with pytest.raises(ValueError):
        s.push(np.zeros((2, 8)))  # sessions are single-lane streams
    s.close()
    with pytest.raises(ValueError):
        s.push(np.arange(8))  # closed
    with pytest.raises(ValueError):
        BankSessionServer(prog, n_slots=0)


def test_apply_lanes_is_stateless_and_validated():
    prog = _program(4)
    eng = FilterBankEngine(prog, channels=2, interpret=True)
    rng = np.random.default_rng(3)
    buf = rng.integers(-128, 128, (2, 100)).astype(np.int32)
    y = eng.apply_lanes(buf)
    assert y.shape == (4, 2, 100 - TAPS + 1)
    assert np.array_equal(y, fir_bit_layers_batch(buf, prog.qbank))
    assert eng.samples_in == 0 and eng._tail.shape[1] == 0  # stateless
    with pytest.raises(ValueError):
        eng.apply_lanes(buf[:1])  # wrong lane count
    with pytest.raises(ValueError):
        eng.apply_lanes(buf[:, : TAPS - 1])  # shorter than one window


def test_predict_session_step_us_round_structure():
    # one slot-rounding boundary: 8 active over 8 slots is one round,
    # 9 active spills a second full dispatch
    one = predict_session_step_us(1000.0, 8, 8)
    two = predict_session_step_us(1000.0, 9, 8)
    assert one == 1000.0 + 8 * SESSION_LANE_US
    assert two == 2 * one
    assert predict_session_step_us(1000.0, 0, 8) == 0.0
    with pytest.raises(ValueError):
        predict_session_step_us(1000.0, 1, 0)


# ---------------------------------------------------------------------------
# acceptance: 64 sessions over a 256-filter bank, hot-swap + pause/resume
# ---------------------------------------------------------------------------


def test_64_sessions_over_256_filter_bank_bit_exact():
    rng = np.random.default_rng(4)
    prog = _program(256, taps=15)
    srv = BankSessionServer(
        prog, n_slots=16, tile=128, interpret=True, auto_step=False
    )
    n_sessions = 64
    sels = [np.arange(i * 4, i * 4 + 4) for i in range(n_sessions)]
    sessions = [srv.open_session(sel) for sel in sels]
    streams = [
        rng.integers(-128, 128, 96).astype(np.int32)
        for _ in range(n_sessions)
    ]
    got = [[] for _ in range(n_sessions)]
    cuts = [
        np.sort(rng.integers(1, 96, 2)).tolist() for _ in range(n_sessions)
    ]
    for k in range(3):  # three independently-sized chunks per session
        if k == 1:
            # one mid-stream hot-swap (same rows back: exercises the
            # flush-then-retarget path without changing the reference)
            got[7].append(sessions[7].swap_filters(sels[7]))
            # one mid-stream pause/resume
            snap = sessions[13].pause()
            got[13].append(sessions[13].pull())
            sessions[13] = srv.resume_session(snap, sels[13])
        for i, s in enumerate(sessions):
            lo = 0 if k == 0 else cuts[i][k - 1]
            hi = cuts[i][k] if k < 2 else 96
            if hi > lo:
                s.push(streams[i][lo:hi])
        srv.step()
        for i, s in enumerate(sessions):
            got[i].append(s.pull())
    oracle = fir_bit_layers_batch(
        np.stack(streams), prog.qbank
    )  # (256, 64, 96-15+1): filter b applied to stream c
    for i in range(n_sessions):
        out = np.concatenate([g for g in got[i] if g.shape[1]], axis=1)
        want = oracle[sels[i], i, :]
        assert out.shape == want.shape
        assert np.array_equal(out, want), f"session {i} diverged"
    st = srv.serve_stats()
    assert st["occupancy"] > 0.9  # 64 ready sessions over 16 lanes
    assert st["rounds"] >= 9  # ≈ 4 rounds/step minus priming absorptions


# ---------------------------------------------------------------------------
# forced-8-device leg: the session server composes with a forced mesh
# ---------------------------------------------------------------------------


def test_sessions_under_forced_device_count():
    out = run_py(
        """
import numpy as np
from repro.filters import FilterBankEngine, spread_lowpass_qbank
from repro.serving import BankSessionServer

qb = spread_lowpass_qbank(8, 31)
srv = BankSessionServer(qb, n_slots=4, interpret=True, auto_step=False)
sels = [[0, 1], [5], [2, 6, 7]]
sessions = [srv.open_session(r) for r in sels]
refs = [FilterBankEngine(srv.program, channels=1, interpret=True)
        for _ in sels]
rng = np.random.default_rng(0)
want = []
for s, r, sel in zip(sessions, refs, sels):
    x = rng.integers(-128, 128, 70)
    s.push(x)
    want.append(r.push(x[None, :])[np.asarray(sel), 0])
srv.step()
for s, w in zip(sessions, want):
    assert np.array_equal(s.pull(), w)
print("OK", srv.serve_stats()["rounds"])
""",
        devices=8,
    )
    assert "OK" in out


# ---------------------------------------------------------------------------
# observability: the step's spans and the useful-row counters
# ---------------------------------------------------------------------------


def _program_spans(log_dir):
    """``[name, ids]`` of every ``blmac.`` host event in the trace."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("blmac."):
                    out.append([ev.name, dict(ev.stats), int(ev.start_ns)])
    return [s[:2] for s in sorted(out, key=lambda s: s[2])]


def test_step_spans_and_useful_row_counters(tmp_path):
    import jax

    prog = _program(16)
    srv = BankSessionServer(prog, n_slots=2, interpret=True, auto_step=False,
                            journal=str(tmp_path / "wal"))
    sessions = [srv.open_session(r) for r in ([0, 1, 2], [5], [7, 8])]
    rng = np.random.default_rng(3)
    with jax.profiler.trace(str(tmp_path / "trace")):
        for s in sessions:
            s.push(rng.integers(-128, 128, 100))
        assert srv.step() == 3
    st = srv.serve_stats()
    # two rounds of 2 lanes through all 16 filters; 3 + 1 + 2 rows used
    assert st["rounds"] == 2
    assert st["rows_computed"] == 2 * 2 * 16
    assert st["rows_used"] == 6
    spans = _program_spans(str(tmp_path / "trace"))
    names = [n for n, _ in spans]
    # the pushes' chunk records, then the step and its group commit
    assert names[:3] == ["blmac.journal_append"] * 3
    assert spans[3] == ["blmac.step", {"step": 1}]
    assert names.count("blmac.lane_pack") == 2
    assert names.count("blmac.row_slice") == 2
    assert names.count("blmac.push") == 2  # one apply_lanes per round
    assert names[-1] == "blmac.journal_sync"
    srv.close()
