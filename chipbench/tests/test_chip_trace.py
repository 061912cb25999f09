"""The reductions on a real chip trace: three pushes of
``fir127_grid.sweep`` recorded on one TPU v5e with the program's spans
(``data/fir127_sweep_3push.json``: the trace as ``spans.py --out``
writes it, and the result line the run printed)."""
import json
import pathlib
import types

import pytest

from chipbench import roofline, run, spans, tracing

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = json.loads((BENCH / "tests" / "data" /
                   "fir127_sweep_3push.json").read_text())
TRACE = DATA["trace"]
PUSHES = 3
GROUPS = 6  # tile groups of the 9,900-filter schedule


def _program_spans(name):
    return [s for s in TRACE["program_spans"] if s[0] == name]


def test_readers_reproduce_what_the_run_printed():
    bench = run.load_benchmark()
    w, cfg, _ = run.cell(bench, "fir127_grid.sweep")
    reduced = tracing.reduce(TRACE)
    ctx = types.SimpleNamespace(
        trace=reduced, counters={"pushes": PUSHES, "channels": 1,
                                 "n_out": 4096},
        peak=roofline.peaks(DATA["result"]["device"]["kind"]), config=cfg,
        taps=cfg["taps"], filters=9900, why=w["why"])
    printed = DATA["result"]["metrics"]
    assert set(printed) == {"kernel_ms.stream", "device_roofline.stream",
                            "other_device_ms.stream", "idle_share.stream"}
    for name, m in printed.items():
        assert run.metric_reader(BENCH, name)(ctx) == \
            pytest.approx(m["value"], rel=1e-12)
    assert tracing.breakdown(reduced, TRACE) == DATA["result"]["breakdown"]
    assert reduced["devices"][0]["busy_s"] == \
        pytest.approx(DATA["result"]["device"]["busy_s"])


def test_each_push_runs_its_steps_in_order_with_its_chunk_id():
    pushes = _program_spans("blmac.push")
    assert [p[3]["chunk"] for p in pushes] == [3, 4, 5]  # after 3 warm-ups
    for p in pushes:
        inner = [s for s in TRACE["program_spans"]
                 if p[1] <= s[1] and s[1] + s[2] <= p[1] + p[2]]
        steps = [s[0] for s in inner if s[0] in spans.STEPS]
        assert steps == ["blmac.stage", "blmac.stage", "blmac.dispatch",
                         "blmac.wait", "blmac.readback"]
        groups = [s[3]["group"] for s in inner if s[0] == "blmac.group"]
        assert groups == list(range(GROUPS))


def test_device_ops_fall_inside_the_spans_of_their_own_push():
    """The shared clock: the host's spans and the chip's operations are
    on one time line, so each push's kernels start after its dispatch
    began and end before its wait ended."""
    dispatch = _program_spans("blmac.dispatch")
    wait = _program_spans("blmac.wait")
    assert len(dispatch) == len(wait) == PUSHES
    ops = sorted(TRACE["devices"][0]["ops"], key=lambda o: o[1])
    kernels = [o for o in ops if "blmac_bank_kernel" in o[0]]
    assert len(kernels) == PUSHES * GROUPS
    assert all(o[3] for o in kernels)  # tracing.KERNEL_MARK marks them
    for i, (_, start, dur, _) in enumerate(kernels):
        k = i // GROUPS
        assert dispatch[k][1] <= start
        assert start + dur <= wait[k][1] + wait[k][2]
    # nothing runs on the chip outside a push; the framing's first
    # operations show on the chip up to 0.5 ms before the host's
    # dispatch span begins: the two clocks agree to within that
    pushes = _program_spans("blmac.push")
    for o in ops:
        k = max(i for i, p in enumerate(pushes) if p[1] <= o[1])
        assert o[1] + o[2] <= pushes[k][1] + pushes[k][2]
        assert o[1] >= dispatch[k][1] - 1_000_000


def test_the_split_of_three_pushes_whose_results_the_check_kept():
    """What the three recorded pushes show.  The closed loop keeps the
    results of these three for its check, so none of their ``cb.push``
    spans frees the previous push's 162 MB result: the program's
    ``blmac.push`` is all of ``cb.push`` but the span calls.  In a full
    window most pushes drop the result before them, and freeing it falls
    inside ``cb.push`` but outside every program span, so there the
    steps cover less of ``cb.push`` and more idle time stays unnamed
    (PERF.md, Findings); this trace cannot show that."""
    cb = [s for s in TRACE["host_spans"] if s[0] == "cb.push"]
    for outer, push in zip(cb, _program_spans("blmac.push")):
        assert 0 < outer[2] - push[2] < 30_000  # ns: no free in cb.push
    s = spans.summary(TRACE, chips=1)
    assert s["pushes"] == PUSHES
    assert s["push_ms"] == pytest.approx(70.90474666666667, rel=1e-9)
    assert s["steps_share_of_push"] == pytest.approx(0.997764752373889,
                                                     rel=1e-9)
    ms = s["span_ms_per_push"]
    assert {k: round(v, 3) for k, v in ms.items()} == {
        "blmac.push": 70.882, "blmac.stage": 3.185,
        "blmac.dispatch": 14.876, "blmac.group": 1.331,
        "blmac.wait": 1.971, "blmac.readback": 50.713}
    # the readback of the 162 MB result is most of a push
    assert ms["blmac.readback"] > 0.5 * s["push_ms"]
    idle = s["chips"][0]["idle_s_by_span"]
    assert max(idle, key=idle.get) == "blmac.readback"
    unnamed = idle.get("cb.push", 0.0) + idle.get("none", 0.0)
    assert unnamed / sum(idle.values()) == pytest.approx(8.6e-4, abs=1e-5)
