"""Sparsity-schedule coverage: layer-skip, occupancy grouping, fast path.

Oracle-vs-kernel equality on the banks a schedule can get wrong (all-zero
rows, single pulses at the extreme layers, mixed occupancy in hostile
order), schedule-compilation unit tests, the bank plan, and the
pack-time int32 bound.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (assert_int32_bound, layer_occupancy,
                        layer_pulse_counts, occupancy_signatures,
                        po2_quantize_batch)
from repro.core.csd import csd_digits
from repro.filters import FilterBankEngine, design_bank, fir_bit_layers_batch
from repro.compiler import bank_plan, compile_bank
from repro.kernels import (pack_bank_trits, plan_bank_schedule,
                           superlayer_schedule)
from repro.kernels.blmac_fir import blmac_fir_bank  # packed-operand entry

from differential import adversarial_bank, four_way_check


def _sym(half_rows) -> np.ndarray:
    return np.stack(
        [np.concatenate([h, h[:-1][::-1]]) for h in np.atleast_2d(half_rows)]
    )


# ---------------------------------------------------------------------------
# schedule compilation (pure planning, no kernels)
# ---------------------------------------------------------------------------

def test_superlayer_schedule_empty_and_single():
    assert superlayer_schedule((), 4) == ((), 0, ())
    sched, tail, sel = superlayer_schedule((7,), 4)
    assert sched == ((0, ((0, 0),)),) and tail == 7 and sel == (7,)


def test_superlayer_schedule_merge_and_gaps():
    # layers {16, 4, 3, 1, 0}, merge=2: {16}, {4,3}, {1,0}
    sched, tail, sel = superlayer_schedule((0, 1, 3, 4, 16), 2)
    assert sel == (16, 4, 3, 1, 0)
    assert sched == (
        (0, ((0, 0),)),          # layer 16
        (13, ((1, 1), (2, 0))),  # acc << (16-3), then 2·d4 + d3
        (3, ((3, 1), (4, 0))),   # acc << (3-0), then 2·d1 + d0
    )
    assert tail == 0


def test_superlayer_schedule_merge1_is_pure_bit_layers():
    sched, tail, sel = superlayer_schedule((0, 2, 5), 1)
    assert all(len(parts) == 1 and parts[0][1] == 0 for _, parts in sched)
    assert [s for s, _ in sched] == [0, 3, 2] and tail == 0


def test_schedule_decodes_to_weights():
    """Replaying a schedule over the digit layers reproduces the weights —
    the same recursion the kernel runs, on numpy."""
    rng = np.random.default_rng(3)
    w = rng.integers(-(1 << 15), 1 << 15, 9)
    digits = csd_digits(w)  # (M, L)
    occ = np.nonzero(layer_occupancy(digits[None]).any(axis=0))[0]
    for merge in (1, 3, 8):
        sched, tail, sel = superlayer_schedule(tuple(occ), merge)
        acc = np.zeros_like(w)
        for shift_in, parts in sched:
            acc <<= shift_in
            for sel_idx, rel in parts:
                acc += digits[:, sel[sel_idx]].astype(np.int64) << rel
        assert np.array_equal(acc << tail, w), merge


def test_occupancy_helpers():
    d = np.zeros((2, 3, 5), np.int8)
    d[0, 1, 2] = 1
    d[1, 0, 0] = -1
    d[1, 2, 4] = 1
    occ = layer_occupancy(d)
    assert occ.tolist() == [
        [False, False, True, False, False],
        [True, False, False, False, True],
    ]
    assert layer_pulse_counts(d)[1].tolist() == [1, 0, 0, 0, 1]
    sigs = occupancy_signatures(occ)
    assert sigs.tolist() == [0b00100, 0b10001]


# ---------------------------------------------------------------------------
# kernel equality on adversarial occupancy
# ---------------------------------------------------------------------------

def test_all_zero_bank_runs_no_kernel():
    q = np.zeros((5, 15), np.int64)
    packed = pack_bank_trits(q)
    plan = plan_bank_schedule(packed, bank_tile=4)
    assert all(not g.sel_layers for g in plan.groups)
    assert plan.n_superlayers == 0
    x = np.arange(200)
    y = blmac_fir_bank(jnp.asarray(x), packed, 15, tile=128, fast_path=False)
    assert y.shape == (5, 200 - 15 + 1)
    assert not np.asarray(y).any()


def test_single_pulse_filters_every_layer():
    """One filter per bit layer, each a lone centre-tap pulse: the
    schedule must place every pulse at its exact weight."""
    half = 7
    rows = []
    for layer in range(15):
        h = np.zeros(half + 1, np.int64)
        h[half] = 1 << layer
        rows.append(h)
    q = _sym(rows)
    rng = np.random.default_rng(5)
    x = rng.integers(-128, 128, (1, 300))
    for bank_tile in (1, 4, 16):
        y = blmac_fir_bank(
            jnp.asarray(x), pack_bank_trits(q), q.shape[1],
            tile=128, bank_tile=bank_tile, fast_path=False,
        )
        assert np.array_equal(
            np.asarray(y, np.int64), fir_bit_layers_batch(x, q)
        ), bank_tile


@pytest.mark.parametrize("merge", [1, 4, 8])
def test_mixed_occupancy_order_restored(merge):
    """Hostile interleaving of dense / sparse / empty rows: grouping must
    sort internally and hand back rows in the caller's order."""
    q = adversarial_bank(taps=31)
    packed = pack_bank_trits(q)
    plan = plan_bank_schedule(packed, bank_tile=2, merge=merge)
    assert not np.array_equal(plan.perm, np.arange(len(q)))  # sort happened
    rng = np.random.default_rng(7)
    x = rng.integers(-128, 128, (2, 500))
    y = blmac_fir_bank(
        jnp.asarray(x), packed, 31, tile=128, bank_tile=2, merge=merge,
        fast_path=False,
    )
    assert np.array_equal(np.asarray(y, np.int64), fir_bit_layers_batch(x, q))


def test_grouped_tiles_skip_layers():
    """A tile of low-layer-only filters must compile fewer superlayers
    than the dense tiles — that is the whole point of grouping."""
    rng = np.random.default_rng(11)
    dense = rng.integers(-(1 << 15), 1 << 15, (4, 8))
    sparse = rng.integers(-3, 4, (4, 8))
    q = _sym(np.concatenate([dense, sparse]))[np.array([0, 4, 1, 5, 2, 6, 3, 7])]
    plan = plan_bank_schedule(pack_bank_trits(q), bank_tile=4, merge=1)
    n_super = sorted(len(g.schedule) for g in plan.groups)
    assert len(plan.groups) == 2
    assert n_super[0] <= 3  # sparse tile: ±3 coeffs → ≤3 populated layers
    assert n_super[1] >= 15  # dense 16-bit tile
    x = rng.integers(-128, 128, (1, 400))
    y = blmac_fir_bank(jnp.asarray(x), pack_bank_trits(q), 15, tile=128,
                       bank_tile=4, merge=1, fast_path=False)
    assert np.array_equal(np.asarray(y, np.int64), fir_bit_layers_batch(x, q))


def test_fast_path_matches_bank_path():
    q = _sym(np.random.default_rng(13).integers(-(1 << 15), 1 << 15, (1, 16)))
    packed = pack_bank_trits(q)
    x = np.random.default_rng(14).integers(-128, 128, 700)
    fast = blmac_fir_bank(jnp.asarray(x), packed, 31, tile=128)
    slow = blmac_fir_bank(jnp.asarray(x), packed, 31, tile=128, fast_path=False)
    assert np.array_equal(np.asarray(fast), np.asarray(slow))
    assert np.array_equal(
        np.asarray(fast, np.int64), fir_bit_layers_batch(x, q)[:, 0, :]
    )


# ---------------------------------------------------------------------------
# bank plan + engine dispatch
# ---------------------------------------------------------------------------

def test_autotuner_scales_with_bank_width():
    """The plan's bank tile tracks the bank's width (whole bank in one
    tile up to 256 filters, then tiles that keep the padding small);
    tile and merge stay fixed, and the schedule is the program's memo."""
    def program(n, taps=63):
        cuts = 0.05 + 0.9 * (np.arange(n) + 0.5) / n
        q, _ = po2_quantize_batch(
            design_bank(taps, [("lowpass", float(c)) for c in cuts]), 16
        )
        return compile_bank(q)

    for n, bank_tile in ((1, 8), (17, 24), (256, 256), (300, 152)):
        prog = program(n)
        plan, sched = bank_plan(prog)
        assert (plan.mode, plan.tile, plan.merge) == ("scheduled", 256, 8)
        assert plan.bank_tile == sched.tile_size == bank_tile
        again, same = bank_plan(prog)  # the program's memo: no re-plan
        assert again == plan and same is sched is prog.schedule()


def test_engine_scheduled_streaming_on_adversarial_bank():
    q = adversarial_bank(taps=15)
    rng = np.random.default_rng(17)
    x = rng.integers(-128, 128, (1, 900))
    eng = FilterBankEngine(q, channels=1, tile=128, mode="packed")
    cuts = [0, 50, 51, 400, 900]
    y = np.concatenate(
        [eng.push(x[:, a:b]) for a, b in zip(cuts, cuts[1:])], axis=2
    )
    assert np.array_equal(y, fir_bit_layers_batch(x, q))


# ---------------------------------------------------------------------------
# four-way differential through the scheduled path
# ---------------------------------------------------------------------------

def test_four_way_adversarial_bank():
    rep = four_way_check(adversarial_bank(taps=31), n_out=24, tile=128)
    assert rep.n_filters == 7


def test_four_way_sweep_sampled_bank():
    from differential import sampled_sweep_bank

    rep = four_way_check(
        sampled_sweep_bank(taps=127, n_filters=6), n_out=24, tile=128
    )
    assert rep.n_filters == 6


# ---------------------------------------------------------------------------
# pack-time int32 bound (the single overflow check every path shares)
# ---------------------------------------------------------------------------

def test_int32_bound_asserted_once_at_pack_time():
    ok = _sym(np.full((1, 128), (1 << 15) - 1, np.int64))  # 255 taps, max coeffs
    assert ok.shape[1] == 255
    bound = assert_int32_bound(ok, sample_bits=8)
    assert bound < 1 << 31
    pack_bank_trits(ok)  # must not raise: the paper's operating point fits
    with pytest.raises(OverflowError):
        assert_int32_bound(ok, sample_bits=16)  # 16-bit samples do NOT fit
    with pytest.raises(OverflowError):
        pack_bank_trits(ok, sample_bits=16)
