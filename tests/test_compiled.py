"""The compiled execution lanes and the per-backend calibration table.

Covers: xla-lane bit-exactness against the numpy oracle (one-shot and
streamed), the compiled autotuner sweep preferring the fused XLA lowering
at wide merges, calibration fit/persist round-trips under an isolated
cache dir, and backward compatibility of the default (interpret-only)
sweep and plan layout.
"""
import json
import os

import numpy as np
import pytest

from repro.core import po2_quantize_batch
from repro.core.costmodel import (REFERENCE_CALIBRATIONS, BankDispatchPlan,
                                  calibrate_backend, calibration_path,
                                  ensure_calibration, get_calibration)
from repro.compiler import compile_bank, lower
from repro.filters import (FilterBankEngine, ShardedFilterBankEngine,
                           design_bank, fir_bit_layers_batch)
from repro.kernels import autotune_bank_dispatch
from repro.kernels.blmac_fir import LANES
from repro.kernels.runtime import (COMPILED_MERGE_CANDIDATES,
                                   MERGE_CANDIDATES, autotune_sharded_dispatch,
                                   default_lane, resolve_lane)
from tests._subproc import run_py


@pytest.fixture(scope="module", autouse=True)
def _isolated_calibration(tmp_path_factory):
    """Point the calibration cache at a module-scoped temp dir so tests
    never read or write the user's real table, while still sharing one
    fitted file across the tests in this module (fits cost seconds)."""
    prev = os.environ.get("REPRO_CACHE_DIR")
    os.environ["REPRO_CACHE_DIR"] = str(tmp_path_factory.mktemp("cal"))
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_CACHE_DIR", None)
        else:
            os.environ["REPRO_CACHE_DIR"] = prev


def _qbank(n, taps=63):
    cuts = 0.05 + 0.9 * (np.arange(n) + 0.5) / n
    q, _ = po2_quantize_batch(
        design_bank(taps, [("lowpass", float(c)) for c in cuts]), 16
    )
    return q


# ---------------------------------------------------------------------------
# lane resolution + plan layout backward compatibility
# ---------------------------------------------------------------------------


def test_lane_resolution():
    assert default_lane() in LANES
    assert resolve_lane(None) == default_lane()
    assert resolve_lane(True) == default_lane()
    assert resolve_lane("xla") == "xla"
    with pytest.raises(ValueError):
        resolve_lane("cuda")


def test_plan_lane_defaults_to_interpret():
    # positional construction predates the lane field and must keep working
    plan = BankDispatchPlan("scheduled", 512, 128, 8, 123.0)
    assert plan.lane == "interpret"


def test_default_sweep_is_interpret_only():
    q = _qbank(64)
    plan, _ = autotune_bank_dispatch(compile_bank(q), chunk_hint=8192)
    assert plan.lane == "interpret"
    assert plan.merge in MERGE_CANDIDATES


# ---------------------------------------------------------------------------
# calibration table: fit, persist, reread, fall back
# ---------------------------------------------------------------------------


def test_calibrate_backend_fits_and_persists():
    cal = calibrate_backend("xla")
    assert cal.lane == "xla" and cal.source == "fitted"
    assert cal.cpu_model  # stamped with this host's CPU
    for field in ("call_us", "step_us", "mac_us", "unpack_us",
                  "spec_call_us", "spec_op_us"):
        assert getattr(cal, field) > 0.0, field
    # persisted next to the program cache, keyed per lane
    with open(calibration_path()) as f:
        table = json.load(f)
    assert table["xla"]["source"] == "fitted"
    # pure read returns the fitted entry; fit-at-first-use short-circuits
    assert get_calibration("xla") == cal
    assert ensure_calibration("xla") == cal


def test_get_calibration_ignores_foreign_cpu_entry():
    path = calibration_path()
    with open(path) as f:
        table = json.load(f)
    saved = json.dumps(table)
    table["xla"]["cpu_model"] = "some other machine entirely"
    with open(path, "w") as f:
        json.dump(table, f)
    try:
        assert get_calibration("xla") == REFERENCE_CALIBRATIONS["xla"]
    finally:
        with open(path, "w") as f:
            f.write(saved)


def test_get_calibration_unknown_lane_raises():
    with pytest.raises(ValueError):
        get_calibration("cuda")


# ---------------------------------------------------------------------------
# xla lane: bit-exactness
# ---------------------------------------------------------------------------


def test_xla_lane_bit_exact_vs_oracle():
    q = _qbank(24, taps=63)
    prog = compile_bank(q)
    rng = np.random.default_rng(5)
    x = rng.integers(-128, 128, (2, 1500))
    y_oracle = lower(prog, "oracle")(x)
    y_xla = lower(prog, "scheduled", lane="xla")(x)
    assert np.array_equal(np.asarray(y_xla, np.int64), y_oracle)


def test_xla_lane_matches_interpret_on_adversarial_geometry():
    from tests.differential import adversarial_bank

    q = adversarial_bank(taps=15)
    prog = compile_bank(q)
    rng = np.random.default_rng(6)
    x = rng.integers(-128, 128, (1, 700))
    a = lower(prog, "scheduled", interpret=True, tile=128)(x)
    b = lower(prog, "scheduled", lane="xla", tile=128)(x)
    assert np.array_equal(a, b)


def test_engine_compiled_streaming_bit_exact():
    q = _qbank(32, taps=31)
    rng = np.random.default_rng(7)
    x = rng.integers(-128, 128, (1, 900))
    eng = FilterBankEngine(q, channels=1, interpret=True, compiled="xla")
    assert eng.dispatch_plan.lane == "xla"
    assert eng.lane == "xla"
    cuts = [0, 130, 131, 512, 900]
    y = np.concatenate(
        [eng.push(x[:, a:b]) for a, b in zip(cuts, cuts[1:])], axis=2
    )
    expect = fir_bit_layers_batch(x, q)
    assert np.array_equal(np.asarray(y, np.int64), expect)


# ---------------------------------------------------------------------------
# compiled autotuner sweep
# ---------------------------------------------------------------------------


def test_compiled_sweep_picks_xla_wide_merge_on_wide_bank():
    q = _qbank(256)
    prog = compile_bank(q)
    plan, sched = autotune_bank_dispatch(prog, chunk_hint=16384,
                                         compiled="xla")
    # at B=256 the fused XLA lowering wins, and it wins at a wider merge
    # than the interpreter ever picks (the merge-heuristic inversion)
    assert plan.lane == "xla"
    assert plan.mode == "scheduled"
    assert plan.merge in COMPILED_MERGE_CANDIDATES
    assert plan.merge > 1
    assert sched is not None and sched.tile_size == plan.bank_tile
    # repeat dispatch is an LRU hit returning the identical plan object
    again, _ = autotune_bank_dispatch(prog, chunk_hint=16384, compiled="xla")
    assert again is plan
    # the compiled sweep never perturbs the default one
    base, _ = autotune_bank_dispatch(prog, chunk_hint=16384)
    assert base.lane == "interpret"


def test_sharded_compiled_planning_and_degraded_engine():
    q = _qbank(64)
    prog = compile_bank(q)
    plan, part, scheds = autotune_sharded_dispatch(
        prog, channels=1, mesh_shape=(8, 1), chunk_hint=16384, compiled="xla"
    )
    assert all(p.lane == "xla" for p in plan.shard_plans
               if p.mode == "scheduled")
    # single-device mesh degrades to a plain engine that keeps the
    # compiled lane — and stays bit-exact
    eng = ShardedFilterBankEngine(q, compiled="xla")
    rng = np.random.default_rng(8)
    x = rng.integers(-128, 128, (1, 600))
    y = eng.push(x)
    expect = fir_bit_layers_batch(x, q)
    assert np.array_equal(np.asarray(y, np.int64), expect)


# ---------------------------------------------------------------------------
# persistent compilation cache placement (entry points)
# ---------------------------------------------------------------------------


def test_compilation_cache_uses_env_dir(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: the helper keeps it, sets no
    other directory, and compiled entries land there."""
    out = run_py(f"""
import os
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
import jax, jax.numpy as jnp
from repro.kernels.runtime import use_compilation_cache
assert use_compilation_cache() == {str(tmp_path)!r}
assert jax.config.jax_compilation_cache_dir == {str(tmp_path)!r}
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
print("ENTRIES", len(os.listdir({str(tmp_path)!r})))
""", devices=1)
    assert int(out.split("ENTRIES")[1]) > 0


def test_compilation_cache_defaults_to_checkout():
    """Unset: the cache is the fixed ``<checkout>/.jax_cache``, never a
    temp, pid- or time-derived path."""
    out = run_py("""
import os
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
import jax
from repro.kernels.runtime import CHECKOUT, use_compilation_cache
path = use_compilation_cache()
assert path == str(CHECKOUT / ".jax_cache") == jax.config.jax_compilation_cache_dir
assert (CHECKOUT / "chip_smoke.py").exists()
print("CACHE", path)
""", devices=1)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert out.split("CACHE")[1].strip() == os.path.join(root, ".jax_cache")
