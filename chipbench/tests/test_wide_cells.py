"""The 16-bit I/Q configuration and its cell: the cell finds its files
by name, the I/Q bank is the 127-tap grid's bit for bit, the
reference is exact at 16 bits, the two wide readers read what the trace
holds, the I/Q mix's engine is refused where it returns no int64, and a
16-bit closed loop runs through the harness at a size a CPU test can."""
import json
import shutil
import types

import numpy as np
import pytest

from chipbench import design, generator, reference, run
from chipbench.tests import tiny

CELLS = {"fir127_ci16.sweep_iq": ("fir127_ci16", "closed")}


def _bench():
    return run.load_benchmark(tiny.BENCH.parent)


def test_ci16_designs_the_127_tap_grid_bank():
    bench = _bench()
    cfgs = {}
    for c in bench["configs"]:
        with open(tiny.BENCH.parent / c["file"]) as f:
            cfgs[c["name"]] = json.load(f)
    wide, grid = cfgs["fir127_ci16"], cfgs["fir127_grid"]
    assert (wide["sample_bits"], grid["sample_bits"]) == (16, 8)
    assert design.design_key(wide) == design.design_key(grid)
    assert design.checksum(design.design(wide)) == \
        design.checksum(design.design(grid))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_run_cell_resolves_the_new_cells(name):
    w, cfg, traffic = run.cell(_bench(), name)
    assert (cfg["name"], traffic["loop"]) == CELLS[name]
    assert w["chips"] == 1


def test_fir_exact_equals_fir_direct_on_int16_extremes():
    rng = np.random.default_rng(5)
    q = rng.integers(-2 ** 15, 2 ** 15, (6, 127))
    q[0] = -2 ** 15  # the widest products and sums a 16-bit bank makes
    q[1] = 2 ** 15 - 1
    x = rng.integers(-2 ** 15, 2 ** 15, 600).astype(np.int16)
    x[:254] = np.tile([-2 ** 15, 2 ** 15 - 1], 127)
    x[254:381] = -2 ** 15
    assert reference.exact_bound_holds(x, q)
    want = reference.fir_direct(x, q)
    assert np.abs(want).max() == 127 * 2 ** 30
    np.testing.assert_array_equal(reference.fir_exact(x, q), want)


def _ctx(ops_s, busy_s, pushes=4, **counters):
    trace = {"window_s": 20.0, "devices": [
        {"busy_s": busy_s, "kernel_s": 0.0, "ops_s": ops_s, "gaps": []}]}
    return types.SimpleNamespace(
        trace=trace, counters=dict(pushes=pushes, **counters),
        peak=dict(tiny.PEAK, hbm_bytes_per_s=8e11, int8_ops=4e14),
        filters=9900, taps=127, config={}, why="kernel")


@pytest.mark.parametrize("ops_s, want", [
    ({"_blmac_plane_combine.1___s32_9900_2_8192": 0.006,
      "blmac_plane_combine.2": 0.002, "_blmac_bank_kernel.1": 0.05}, 2.0),
    ({"_blmac_bank_kernel.1": 0.05}, None)])
def test_plane_combine_reader_sums_its_ops_per_push(ops_s, want):
    read = run.metric_reader(tiny.BENCH, "plane_combine_ms.wide")
    got = read(_ctx(ops_s, busy_s=0.1))
    assert got == pytest.approx(want) if want else got is None


def test_wide_roofline_reader_counts_eight_bytes_an_output():
    read = run.metric_reader(tiny.BENCH, "device_roofline.wide")
    ctx = _ctx({}, busy_s=0.04, channels=2, n_out=4096)
    nbytes = 8 * 9900 * 2 * 4096 + 2 * 2 * 4096 + 2 * 9900 * 127
    # bytes bound it: 8e11 bytes/s against 4e14 operations/s
    least = nbytes / 8e11
    assert read(ctx) == pytest.approx(100 * least * 4 / 0.04)
    assert read(_ctx({}, busy_s=0.0, channels=2, n_out=4096)) is None


class _Int32Engine:
    """An engine that returns int32 whatever the samples: a program
    without the 16-bit path."""

    def __init__(self, b):
        self.b = b

    def push(self, chunk):
        assert chunk.shape[1] == 0, "the probe is an empty push"
        return np.zeros((self.b,) + chunk.shape, np.int32)


def test_iq_mix_refuses_an_engine_without_int64_outputs(monkeypatch):
    from chipbench import drivers

    hooks = generator.hooks(tiny.BENCH, "sweep_iq")
    monkeypatch.setattr(drivers, "build_engine",
                        lambda program, mix, c, n: _Int32Engine(4))
    with pytest.raises(SystemExit, match="int64"):
        hooks.make_engine(None, {}, 2, 256)


def test_iq_cell_runs_exactly_through_the_harness(tmp_path):
    bench = tiny.make(tmp_path, traffic=())
    cfg = dict(tiny.CONFIG, name="tiny16", taps=63, n_filters=16,
               sample_bits=16)
    with open(tmp_path / "configs" / "tiny16.json", "w") as f:
        json.dump(cfg, f)
    mix = dict(generator.load(tiny.BENCH, "sweep_iq"), chunk=1024)
    with open(tmp_path / "traffic" / "sweep_iq.json", "w") as f:
        json.dump(mix, f)
    shutil.copy(tiny.BENCH / "traffic" / "sweep_iq.py",
                tmp_path / "traffic" / "sweep_iq.py")
    bench["configs"].append({"name": "tiny16", "source": "test",
                             "file": "configs/tiny16.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny16.sweep_iq", "config": "tiny16",
                               "traffic": "sweep_iq", "chips": 1,
                               "why": "test"})
    out = tiny.run(bench, tmp_path, "tiny16.sweep_iq")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert out["metrics"]["out_samples_per_s"]["value"] > 0
