"""`push_stats()`: the engines' work counters match the shapes they ran.

Outputs delivered are rows × channels × n_out; outputs computed are the
tile-group-padded rows × channels × tile-padded columns the kernels
produced; bytes read back are what the host copied off the device.
"""
import numpy as np
import pytest

from repro.compiler import compile_bank
from repro.filters import (FilterBankEngine, ShardedFilterBankEngine,
                           spread_lowpass_qbank)

TAPS = 31
B, C = 37, 2


def _chunks(n_chunks, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(-128, 128, (C, n)) for _ in range(n_chunks)]


@pytest.fixture(scope="module")
def program():
    return compile_bank(spread_lowpass_qbank(B, TAPS))


def test_packed_engine_counts_delivered_computed_and_read(program):
    eng = FilterBankEngine(program, channels=C, mode="packed", bank_tile=8,
                           tile=128)
    rows = sum(g.packed.shape[0] for g in eng.bank_schedule.groups)
    assert rows > B  # the last tile group is padded
    assert eng.push_stats() == {"pushes": 0, "outputs_delivered": 0,
                                "outputs_computed": 0, "bytes_read_back": 0,
                                "wide_pushes": 0}
    want = dict.fromkeys(eng.push_stats(), 0)
    tail = 0
    for x in _chunks(3, 200):
        y = eng.push(x)
        n = tail + x.shape[1]  # samples the push framed
        tail = TAPS - 1
        n_out = n - TAPS + 1
        assert y.shape == (B, C, n_out)
        cols = -(-(-(-n // 128) * 128 - TAPS + 1) // 128) * 128
        want["pushes"] += 1
        want["outputs_delivered"] += B * C * n_out
        want["outputs_computed"] += rows * C * cols
        want["bytes_read_back"] += B * C * n_out * 4
        assert eng.push_stats() == want
    # the stateless lane dispatch counts the same way
    buf = _chunks(1, 300, seed=1)[0]
    eng.apply_lanes(buf)
    assert eng.push_stats()["pushes"] == 4
    assert eng.push_stats()["outputs_delivered"] == \
        want["outputs_delivered"] + B * C * (300 - TAPS + 1)


def test_priming_push_counts_a_push_and_no_work(program):
    eng = FilterBankEngine(program, channels=C, mode="packed", tile=128)
    eng.push(np.zeros((C, TAPS - 5), np.int32))
    assert eng.push_stats() == {"pushes": 1, "outputs_delivered": 0,
                                "outputs_computed": 0, "bytes_read_back": 0,
                                "wide_pushes": 0}


def test_specialized_engine_counts_per_filter_programs():
    q = spread_lowpass_qbank(3, TAPS)
    eng = FilterBankEngine(q, channels=C, mode="specialized", tile=128)
    y = eng.push(_chunks(1, 300)[0])
    # 300 samples pad to 384: each filter × channel program frames 354
    # outputs in 3 tiles and returns them all
    assert y.shape == (3, C, 270)
    assert eng.push_stats() == {
        "pushes": 1, "outputs_delivered": 3 * C * 270,
        "outputs_computed": 3 * C * 384,
        "bytes_read_back": 3 * C * 354 * 4, "wide_pushes": 0}


def test_sharded_engine_on_one_device_counts_like_the_plain_engine(program):
    eng = ShardedFilterBankEngine(program, channels=C, tile=128,
                                  chunk_hint=256)
    shard = eng.plan.shard_plans[0]
    plain = FilterBankEngine(program, channels=C, mode="packed", tile=128,
                             bank_tile=shard.bank_tile, merge=shard.merge)
    for x in _chunks(2, 256):
        np.testing.assert_array_equal(eng.push(x), plain.push(x))
    s, p = eng.push_stats(), plain.push_stats()
    assert s["pushes"] == 2
    assert s["outputs_delivered"] == p["outputs_delivered"]
    assert s["outputs_computed"] == p["outputs_computed"]
    # the shard's whole padded block comes back: 256 and 286 samples
    # pad to 256 and 384 columns
    assert s["bytes_read_back"] == B * C * (256 + 384) * 4


def _serve_args(**kw):
    import argparse

    args = dict(fir_bank=16, taps=15, channels=1, chunk=256, chunks=4,
                depth=2, sessions=0, slots=2, journal_path="",
                bank_shards=0, program_path="")
    args.update(kw)
    return argparse.Namespace(**args)


def test_serve_launcher_prints_push_stats(capsys):
    from repro.launch.serve import serve_fir_bank

    serve_fir_bank(_serve_args())
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[serve] pushes:")]
    # 4 pushes of 256 samples through 16 filters: 4 × 16 × 256 minus the
    # first push's 14 priming samples per filter reach the caller
    assert line and line[0].startswith(
        f"[serve] pushes: 4, {16 * (4 * 256 - 14)} of ")


def test_push_report_reads_the_counters():
    from repro.launch.serve import push_report

    assert push_report({"pushes": 2, "outputs_delivered": 90,
                        "outputs_computed": 120, "bytes_read_back": 480}) \
        == ("[serve] pushes: 2, 90 of 120 computed outputs delivered "
            "(75.0%), 240 bytes read back per push")
    assert "(0.0%)" in push_report(dict.fromkeys(
        ("pushes", "outputs_delivered", "outputs_computed",
         "bytes_read_back"), 0))


def test_session_launcher_prints_the_useful_row_share(capsys):
    from repro.launch.serve import serve_sessions

    serve_sessions(_serve_args(sessions=4, chunks=6))
    out = capsys.readouterr().out
    # 4 sessions of 4 rows each over a 16-row bank on 2 lanes
    assert "[serve] useful rows: " in out
    line = [ln for ln in out.splitlines() if "useful rows" in ln][0]
    used, computed = (int(w) for w in line.split()[3:6:2])
    assert 0 < used <= computed
