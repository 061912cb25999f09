"""`spans`: the program's spans in a trace, on hand-made traces whose
answers are known: time per span, the share of a push its steps cover,
and idle time put down to the innermost span."""
import pytest

from chipbench import spans, tracing
from chipbench.tests.test_tracing import HAND

# HAND with program spans nested inside the benchmark's: push 0 stages at
# 1000..1100 and dispatches at 1100..1400 (tile group 0 at 1120..1300),
# waits at 1400..1550 and reads back at 1550..1600; push 1 reads its
# shards at 1600..1950 and reassembles at 1950..2000
NESTED = dict(HAND, program_spans=[
    ["blmac.push", 1000, 600, {"chunk": 0}],
    ["blmac.stage", 1000, 100, {}],
    ["blmac.dispatch", 1100, 300, {}],
    ["blmac.group", 1120, 180, {"group": 0}],
    ["blmac.wait", 1400, 150, {}],
    ["blmac.readback", 1550, 50, {}],
    ["blmac.push", 1600, 400, {"chunk": 1}],
    ["blmac.shard_read", 1600, 200, {"shard": 0}],
    ["blmac.shard_read", 1800, 150, {"shard": 1}],
    ["blmac.reassemble", 1950, 50, {}],
    ["blmac.stage", 2500, 100, {}],  # outside the window
])


def test_idle_time_goes_to_the_innermost_span_open():
    r = tracing.reduce(NESTED)
    # chip 0's one gap, 1500..1900, spans push 0's wait and readback and
    # push 1's read of shard 0 and part of shard 1
    assert spans.idle_by_span(r, NESTED) == {
        "blmac.wait": pytest.approx(5e-8),
        "blmac.readback": pytest.approx(5e-8),
        "blmac.shard_read": pytest.approx(3e-7)}
    # chip 1 is idle at 1000..1200 and 1300..2000: every step of both
    # pushes but tile group 0's last 100 ns
    assert spans.idle_by_span(r, NESTED, device=1) == {
        "blmac.stage": pytest.approx(1e-7),
        "blmac.dispatch": pytest.approx(1.2e-7),
        "blmac.group": pytest.approx(8e-8),
        "blmac.wait": pytest.approx(1.5e-7),
        "blmac.readback": pytest.approx(5e-8),
        "blmac.shard_read": pytest.approx(3.5e-7),
        "blmac.reassemble": pytest.approx(5e-8)}
    # after a span ends its parent has the time; outside every span, none
    gaps = {"devices": [{"gaps": [(1050, 1150), (2200, 2400)]}]}
    trace = dict(HAND, host_spans=[["cb.push", 1000, 120]],
                 program_spans=[["blmac.stage", 1000, 60, {}]])
    assert spans.idle_by_span(gaps, trace) == {
        "blmac.stage": pytest.approx(1e-8), "cb.push": pytest.approx(6e-8),
        "none": pytest.approx(2.3e-7)}
    assert spans.timeline(trace) == [[1000, 1060, "blmac.stage"],
                                     [1060, 1120, "cb.push"]]


def test_without_program_spans_idle_gaps_are_named_as_before():
    # HAND's gaps each lie inside one cb.push, so splitting them at span
    # edges names them as `tracing.idle_by_span` does
    r = tracing.reduce(HAND)
    for device in (0, 1):
        assert spans.idle_by_span(r, HAND, device) == \
            tracing.idle_by_span(r, HAND, device)
        bare = dict(HAND, program_spans=[])
        assert spans.idle_by_span(r, bare, device) == \
            tracing.idle_by_span(r, HAND, device)


def test_totals_count_the_spans_inside_the_window():
    t = spans.totals(NESTED)
    assert t["blmac.push"] == {"s": pytest.approx(1e-6), "count": 2}
    assert t["blmac.stage"] == {"s": pytest.approx(1e-7), "count": 1}
    assert t["blmac.shard_read"] == {"s": pytest.approx(3.5e-7), "count": 2}
    assert t["blmac.group"]["count"] == 1


def test_summary_splits_the_pushes():
    s = spans.summary(NESTED)
    assert s["pushes"] == 2
    assert s["push_ms"] == pytest.approx(5e-4)  # (600 + 400) ns / 2
    assert s["span_ms_per_push"]["blmac.wait"] == pytest.approx(7.5e-5)
    # push 0: stage, dispatch, wait and readback cover 1000..1600; push
    # 1: shard reads and reassembly cover 1600..2000
    assert s["steps_share_of_push"] == pytest.approx(1.0)
    assert [c["busy_s"] for c in s["chips"]] == \
        [pytest.approx(6e-7), pytest.approx(1e-7)]
    assert s["chips"][0]["idle_s_by_span"] == {
        "blmac.shard_read": pytest.approx(3e-7),
        "blmac.wait": pytest.approx(5e-8),
        "blmac.readback": pytest.approx(5e-8)}
    gappy = dict(NESTED, program_spans=[
        sp for sp in NESTED["program_spans"] if sp[0] != "blmac.wait"])
    assert spans.summary(gappy)["steps_share_of_push"] == \
        pytest.approx(1 - 150 / 1000)
