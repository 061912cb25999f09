"""Tests for the cross-filter CSE optimizing pass (`repro.compiler.optimize`).

The contract under test, in order of importance:

  1. **Bit-exactness** — an optimized program produces the parent's
     outputs on every backend (the `cse_check` differential leg:
     oracle, scheduled + combine GEMM, specialized, vmachine, sharded,
     the engine).
  2. **Accounting** — total pulses and §3.3 adds never increase; §4
     cycles price one extra cycle per combine use.
  3. **Caching** — the pass is content-addressed on ``(parent.key,
     "cse", level)``, memoized (mines exactly once across the engine,
     the plans and the cycle predictor), and survives save/load
     with tamper detection.
"""
import numpy as np
import pytest

from repro.compiler import (BlmacProgram, OptimizedProgram, cache_stats,
                            clear_caches, compile_bank, cse_pass, lower)
from repro.filters import FilterBankEngine

from tests.differential import (adversarial_bank, cse_check,
                                random_type1_bank, sampled_sweep_bank)


def _toy_bank():
    bank = np.zeros((3, 15), np.int64)
    bank[:, 7] = [9, 9, 9]  # 9 = 2^0 + 2^3: one shared 2-term pattern
    return bank


# ---------------------------------------------------------------------------
# the pass itself
# ---------------------------------------------------------------------------


def test_cse_toy_shares_center_tap():
    parent = compile_bank(_toy_bank())
    opt = cse_pass(parent)
    assert isinstance(opt, OptimizedProgram)
    assert opt.n_real == 3 and opt.n_shared == 1
    # three 2-pulse rows collapse onto one shared 2-pulse virtual row
    assert int(opt.pulse_counts.sum()) == 2
    assert np.array_equal(opt.use_counts, [1, 1, 1])
    assert np.array_equal(opt.effective_qbank(), parent.qbank)
    assert opt.total_adds() < parent.total_adds()
    assert opt.out_filters == 3 and opt.n_filters == 4


def test_cse_declines_when_nothing_shared():
    bank = np.zeros((2, 15), np.int64)
    bank[0, 7] = 1  # single pulses: no 2-term patterns at all
    bank[1, 7] = 4
    parent = compile_bank(bank)
    assert cse_pass(parent) is parent


def test_cse_level_ilp_is_documented_stretch():
    parent = compile_bank(_toy_bank())
    with pytest.raises(NotImplementedError, match="1912.04210"):
        cse_pass(parent, level="ilp")
    with pytest.raises(ValueError, match="level"):
        cse_pass(parent, level=3)
    with pytest.raises(TypeError):
        cse_pass(np.zeros((2, 15)))


def test_cse_max_shared_caps_virtual_rows():
    parent = compile_bank(random_type1_bank(8, 31, seed=5))
    opt = cse_pass(parent, max_shared=3)
    assert isinstance(opt, OptimizedProgram) and opt.n_shared <= 3
    assert np.array_equal(opt.effective_qbank(), parent.qbank)


def test_cse_row_structure_hooks_point_to_bank():
    opt = cse_pass(compile_bank(_toy_bank()))
    with pytest.raises(NotImplementedError, match="combine"):
        opt.select(np.array([0]))
    with pytest.raises(NotImplementedError, match=r"\.bank"):
        opt.partition(2)
    bank = opt.bank
    assert type(bank) is BlmacProgram and bank is opt.bank  # cached
    assert np.array_equal(bank.packed, opt.packed)


# ---------------------------------------------------------------------------
# differential bit-exactness (every backend)
# ---------------------------------------------------------------------------


def test_cse_bit_exact_random_bank():
    report = cse_check(random_type1_bank(6, 31, seed=1), interpret=True)
    assert report["n_shared"] > 0
    assert report["adds_optimized"] <= report["adds_parent"]
    assert report["auto_cse"] == "optimized"


def test_cse_bit_exact_sweep_bank():
    report = cse_check(
        sampled_sweep_bank(taps=127, n_filters=6, seed=2), interpret=True
    )
    assert report["n_shared"] > 0
    assert report["adds_optimized"] < report["adds_parent"]


def test_cse_bit_exact_adversarial_bank():
    # empty rows / single pulses / truncated rows — the pass may decline
    # entirely, and cse_check must hold either way
    report = cse_check(adversarial_bank(31, seed=3), interpret=True)
    assert report["adds_optimized"] <= report["adds_parent"]


def test_cse_engine_decline_executes_parent():
    parent = compile_bank(random_type1_bank(10, 31, seed=7))
    opt = cse_pass(parent)
    eng = FilterBankEngine(opt, channels=1, mode="auto", interpret=True)
    # the one plan runs the optimized layout; it serves the parent's
    # filters, bit-identically
    assert eng.dispatch_plan.cse == "optimized"
    assert eng.program is opt
    assert eng.n_filters == parent.n_filters
    rng = np.random.default_rng(0)
    x = rng.integers(-128, 128, size=(1, 600), dtype=np.int32)
    assert np.array_equal(
        eng.push(x), lower(parent, "scheduled", interpret=True)(x)
    )


# ---------------------------------------------------------------------------
# memoization: CSE mines exactly once across every client
# ---------------------------------------------------------------------------


def test_cse_runs_exactly_once_across_clients():
    from repro.compiler import bank_plan
    from repro.distributed import sharded_plan

    q = random_type1_bank(8, 31, seed=11)
    clear_caches()
    parent = compile_bank(q)
    opt = cse_pass(parent)
    c1 = cache_stats()
    assert c1["counters"]["cse_passes"] == 1
    assert c1["cse"]["misses"] == 1 and c1["cse"]["size"] == 1

    # engine construction, both plans and the cycle predictor all
    # consume the SAME optimized artifact: no re-mining anywhere
    eng = FilterBankEngine(opt, channels=1, mode="auto", interpret=True)
    bank_plan(opt)
    sharded_plan(opt.bank, (2, 1))
    cycles = opt.machine_cycles()
    assert cycles.shape == (opt.n_real,)
    assert cse_pass(parent) is opt
    assert cse_pass(opt) is opt  # idempotent
    c2 = cache_stats()
    assert c2["counters"]["cse_passes"] == 1
    assert c2["cse"]["hits"] >= 1
    assert eng.dispatch_plan.cse == "optimized"


def test_cse_memo_is_bounded():
    from repro.compiler.optimize import _CSE_MEMO, CSE_MEMO_MAX

    clear_caches()
    for seed in range(CSE_MEMO_MAX + 5):
        cse_pass(compile_bank(random_type1_bank(2, 15, seed=seed)))
    assert len(_CSE_MEMO) <= CSE_MEMO_MAX


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------


def test_cse_save_load_roundtrip(tmp_path):
    parent = compile_bank(random_type1_bank(5, 31, seed=13))
    opt = cse_pass(parent)
    path = tmp_path / "opt.npz"
    opt.save(path)
    assert BlmacProgram.load(path) is opt  # memo hit: the same object

    clear_caches()
    loaded = BlmacProgram.load(path)
    assert isinstance(loaded, OptimizedProgram)
    assert loaded.key == opt.key and loaded.parent_key == parent.key
    assert np.array_equal(loaded.combine, opt.combine)
    assert np.array_equal(loaded.use_counts, opt.use_counts)
    assert np.array_equal(loaded.packed, opt.packed)
    assert np.array_equal(loaded.effective_qbank(), parent.qbank)
    rng = np.random.default_rng(0)
    x = rng.integers(-128, 128, 400)
    assert np.array_equal(
        lower(loaded, "scheduled", interpret=True)(x),
        lower(parent, "scheduled", interpret=True)(x),
    )


def test_cse_load_rejects_tampered_combine(tmp_path):
    import json

    from repro.compiler.program import ProgramFormatError

    opt = cse_pass(compile_bank(random_type1_bank(5, 31, seed=17)))
    path = tmp_path / "opt.npz"
    opt.save(path)
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    header = json.loads(str(arrays["header"]))
    combine = arrays["combine"].copy()
    combine[0, 0] += 2  # silently serve the wrong filters? no.
    arrays["combine"] = combine
    np.savez(tmp_path / "evil.npz", **arrays)
    with pytest.raises(ProgramFormatError, match="key"):
        BlmacProgram.load(tmp_path / "evil.npz")
    assert header["cse"]["parent_key"] == opt.parent_key


# ---------------------------------------------------------------------------
# decode round-trip properties (hypothesis leg in test_optimize_props.py)
# ---------------------------------------------------------------------------


def roundtrip_properties(bank: np.ndarray) -> None:
    parent = compile_bank(bank)
    opt = cse_pass(parent)
    assert int(opt.pulse_counts.sum()) <= int(parent.pulse_counts.sum())
    assert opt.total_adds() <= parent.total_adds()
    if not isinstance(opt, OptimizedProgram):
        return
    from repro.core.csd import csd_decode, unpack_trits

    # the packed augmented trits decode to the augmented qbank halves...
    half = bank.shape[1] // 2
    digits = np.swapaxes(unpack_trits(opt.packed, half + 1), 1, 2)
    halves = csd_decode(digits.astype(np.int64))
    assert np.array_equal(halves, opt.qbank[:, : half + 1])
    # ...and the combine folds them back onto the parent's exact bank
    assert np.array_equal(opt.effective_qbank(), parent.qbank)


def test_cse_properties_on_sweep_sample():
    roundtrip_properties(sampled_sweep_bank(taps=63, n_filters=8, seed=23))


def test_cse_properties_on_random_banks():
    for seed in range(4):
        roundtrip_properties(random_type1_bank(4, 31, seed=seed))
