"""The reduction from a profiler trace to the per-layer metrics: kernel
time, busy time, idle share and shard skew, on a hand-made trace in the
form `tracing.extract` gives, whose answers are known."""
import importlib.util
import pathlib
import types

import pytest

from chipbench import tracing

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"

# window 1000..2000 ns; two chips
HAND = {
    "window": [1000, 2000],
    "devices": [
        {"name": "/device:TPU:0", "ops": [
            ["fusion.1", 900, 200, False],      # clipped to 1000..1100
            ["custom-call.2", 1100, 300, True],  # kernel 1100..1400
            ["fusion.3", 1300, 200, False],      # overlaps: busy to 1500
            ["copy.4", 1900, 300, False],        # clipped to 1900..2000
            ["fusion.5", 2500, 100, False],      # outside the window
        ]},
        {"name": "/device:TPU:1", "ops": [
            ["custom-call.2", 1200, 100, True],
        ]},
    ],
    "host_spans": [["cb.push", 1000, 600], ["cb.push", 1600, 400]],
}


def _read(name, trace, counters, why="the bank kernel"):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ctx = types.SimpleNamespace(trace=trace, counters=counters, peak=None,
                                config=None, taps=None, filters=None,
                                why=why)
    return mod.read(ctx)


def test_reduce_hand_made_trace():
    r = tracing.reduce(HAND)
    assert r["window_s"] == pytest.approx(1e-6)
    d0, d1 = r["devices"]
    assert d0["busy_s"] == pytest.approx(600e-9)  # 1000..1500, 1900..2000
    assert d0["kernel_s"] == pytest.approx(300e-9)
    assert d0["gaps"] == [(1500, 1900)]
    assert d1["busy_s"] == pytest.approx(100e-9)
    assert d1["gaps"] == [(1000, 1200), (1300, 2000)]
    counters = {"pushes": 2}
    assert _read("kernel_ms.stream", r, counters) == pytest.approx(150e-6)
    assert _read("other_device_ms.stream", r, counters) == \
        pytest.approx(150e-6)
    assert _read("idle_share.stream", r, counters) == pytest.approx(40.0)
    assert _read("idle_share.sharded", r, counters) == pytest.approx(65.0)
    assert _read("shard_skew.sharded", r, counters) == \
        pytest.approx(600 / 350)


def test_idle_gaps_are_named_by_the_host_span_they_fall_in():
    r = tracing.reduce(HAND)
    assert tracing.idle_by_span(r, HAND) == {"cb.push": pytest.approx(4e-7)}
    b = tracing.breakdown(r, HAND)
    assert b["device_ops"][0][0] == "custom-call.2"
    assert b["idle_gaps"] == [["cb.push", pytest.approx(4e-7)]]


def test_readers_return_nothing_without_a_trace():
    for name in ("kernel_ms.stream", "other_device_ms.stream",
                 "idle_share.stream", "idle_share.sharded",
                 "shard_skew.sharded", "device_roofline.stream"):
        assert _read(name, None, {"pushes": 3}) is None


def test_kernel_reader_refuses_a_busy_chip_with_no_kernel_marked():
    unmarked = dict(HAND, devices=[{"name": d["name"], "ops": [
        op[:3] + [False] for op in d["ops"]]} for d in HAND["devices"]])
    r = tracing.reduce(unmarked)
    with pytest.raises(RuntimeError, match="marked"):
        _read("kernel_ms.stream", r, {"pushes": 2})
    # a cell that does not name the kernel reads nothing instead
    assert _read("kernel_ms.stream", r, {"pushes": 2}, why="readback") is None
