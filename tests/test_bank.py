"""Batched filter-bank kernel and streaming engine vs the numpy oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import po2_quantize_batch
from repro.filters import (FilterBankEngine, design_bank, fir_bit_layers_batch,
                           fir_direct)
from repro.kernels import blmac_fir_bank, pack_bank_trits
from repro.kernels.blmac_fir import (blmac_fir_dynamic, blmac_fir_specialized,
                                     specialized_program)


def _qbank(n_filters: int, taps: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    specs = []
    for i in range(n_filters):
        lo = 0.05 + 0.85 * i / max(n_filters, 2)
        if i % 3 == 2:
            specs.append(("bandpass", (lo * 0.5 + 0.01, min(lo + 0.1, 0.97))))
        else:
            specs.append(("lowpass", lo + 0.02 * rng.random()))
    q, _ = po2_quantize_batch(design_bank(taps, specs), 16)
    return q


@pytest.mark.parametrize("taps", [7, 63, 127])
@pytest.mark.parametrize("n_filters,channels", [(1, 1), (5, 2), (17, 1)])
def test_bank_matches_batch_oracle(taps, n_filters, channels):
    q = _qbank(n_filters, taps)
    rng = np.random.default_rng(taps * n_filters)
    x = rng.integers(-128, 128, (channels, 900))
    y = blmac_fir_bank(jnp.asarray(x), q, tile=256)
    assert np.array_equal(np.asarray(y), fir_bit_layers_batch(x, q))


def test_bank_16_filters_single_call_bit_exact():
    """The acceptance-criterion shape: one pallas_call, ≥16 filters,
    multi-channel, bit-exact against the batched reference."""
    q = _qbank(16, 63)
    rng = np.random.default_rng(1)
    x = rng.integers(-128, 128, (3, 2000))
    y = blmac_fir_bank(jnp.asarray(x), q, tile=512)
    assert y.shape == (16, 3, 2000 - 63 + 1)
    assert np.array_equal(np.asarray(y), fir_bit_layers_batch(x, q))


def test_bank_1d_signal_and_every_filter_matches_direct():
    q = _qbank(6, 31)
    rng = np.random.default_rng(2)
    x = rng.integers(-128, 128, 500)
    y = np.asarray(blmac_fir_bank(jnp.asarray(x), q, tile=128))
    assert y.shape == (6, 500 - 31 + 1)
    for b in range(6):
        assert np.array_equal(y[b], fir_direct(x, q[b]))


def test_bank_tile_padding_paths():
    """Bank sizes that don't divide the bank tile exercise the pad rows."""
    q = _qbank(9, 15)
    rng = np.random.default_rng(3)
    x = rng.integers(-128, 128, 400)
    ref = fir_bit_layers_batch(x, q)[:, 0, :]
    for bank_tile in (1, 4, 8, 16):
        y = blmac_fir_bank(jnp.asarray(x), q, tile=128, bank_tile=bank_tile)
        assert np.array_equal(np.asarray(y), ref), bank_tile


def test_batch_oracle_matches_direct():
    q = _qbank(4, 21)
    rng = np.random.default_rng(4)
    x = rng.integers(-1000, 1000, (2, 300))
    y = fir_bit_layers_batch(x, q)
    for b in range(4):
        for c in range(2):
            assert np.array_equal(y[b, c], fir_direct(x[c], q[b]))


def test_batch_oracle_rejects_asymmetric():
    with pytest.raises(ValueError):
        fir_bit_layers_batch(np.zeros(50, np.int64), np.arange(22).reshape(2, 11))


def test_dynamic_single_filter_is_bank_of_one():
    from repro.core.csd import csd_digits

    q = _qbank(1, 55)[0]
    rng = np.random.default_rng(5)
    x = rng.integers(-128, 128, 700)
    trits = csd_digits(q[: 55 // 2 + 1], n_digits=17).T
    y = blmac_fir_dynamic(jnp.asarray(x), trits, 55, 17, tile=256)
    assert np.array_equal(np.asarray(y), fir_direct(x, q))


def test_pack_bank_trits_rejects_bad_banks():
    with pytest.raises(ValueError):
        pack_bank_trits(np.ones((2, 4), np.int64))  # even taps
    with pytest.raises(ValueError):
        pack_bank_trits(np.arange(10).reshape(2, 5))  # asymmetric


def test_specialized_program_cache_hits():
    q = _qbank(1, 31)[0]
    from repro.kernels.blmac_fir import pulses_msb_first

    pulses = pulses_msb_first(q)
    before = specialized_program.cache_info()
    x = jnp.asarray(np.random.default_rng(6).integers(-128, 128, 400))
    a = blmac_fir_specialized(x, pulses, 31, 128)
    mid = specialized_program.cache_info()
    b = blmac_fir_specialized(x, pulses, 31, 128)
    after = specialized_program.cache_info()
    assert mid.misses == before.misses + 1  # one compile per schedule
    assert after.misses == mid.misses and after.hits == mid.hits + 1
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# streaming overlap-save engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["packed", "specialized"])
def test_engine_stream_equals_one_shot(mode):
    q = _qbank(10, 31)
    rng = np.random.default_rng(8)
    x = rng.integers(-128, 128, (2, 2100))
    eng = FilterBankEngine(q, channels=2, tile=256, mode=mode)
    cuts = [0, 13, 30, 31, 600, 601, 1500, 2100]
    outs = [eng.push(x[:, a:b]) for a, b in zip(cuts, cuts[1:])]
    y = np.concatenate(outs, axis=2)
    assert np.array_equal(y, fir_bit_layers_batch(x, q))
    assert eng.samples_in == 2100
    assert eng.samples_out == 2100 - 31 + 1
    assert eng.pending == 30


def test_engine_priming_returns_empty():
    q = _qbank(3, 15)
    eng = FilterBankEngine(q, channels=1, tile=128)
    y = eng.push(np.arange(7))
    assert y.shape == (3, 1, 0)
    y = eng.push(np.arange(7))
    assert y.shape == (3, 1, 0)
    y = eng.push(np.arange(3))  # 17 samples total -> 3 windows
    assert y.shape == (3, 1, 3)


def test_engine_auto_mode_selection():
    """auto = cost-model dispatch: narrow banks go per-filter specialized,
    banks past the compile-budget cap always go to the scheduled path."""
    from repro.kernels.runtime import SPECIALIZE_BANK_MAX

    small = FilterBankEngine(_qbank(2, 15))
    assert small.mode == "specialized"
    assert small.dispatch_plan is not None
    assert small.dispatch_plan.predicted_us > 0
    wide = FilterBankEngine(_qbank(SPECIALIZE_BANK_MAX + 1, 15))
    assert wide.mode == "packed"
    assert wide.dispatch_plan.mode == "scheduled"
    assert wide.bank_tile == wide.bank_schedule.tile_size
    # forced modes bypass the autotuner entirely
    forced = FilterBankEngine(_qbank(2, 15), mode="packed")
    assert forced.mode == "packed" and forced.dispatch_plan is None
    alias = FilterBankEngine(_qbank(2, 15), mode="scheduled")
    assert alias.mode == "packed"


def test_engine_reset_and_taps1():
    q = np.array([[3]], np.int64)  # taps=1: no tail at all
    eng = FilterBankEngine(q, channels=1)
    y1 = eng.push(np.arange(10))
    assert np.array_equal(y1[0, 0], 3 * np.arange(10))
    assert eng.pending == 0
    eng.reset()
    assert eng.samples_in == 0 and eng.samples_out == 0


def test_engine_rejects_bad_input():
    q = _qbank(2, 15)
    with pytest.raises(ValueError):
        FilterBankEngine(q, channels=0)
    with pytest.raises(ValueError):
        FilterBankEngine(q, mode="warp")
    eng = FilterBankEngine(q, channels=2)
    with pytest.raises(ValueError):
        eng.push(np.zeros((3, 10)))


@pytest.mark.parametrize("mode", ["packed", "specialized"])
def test_engine_sub_tap_chunks_after_priming(mode):
    """Chunks shorter than taps-1 — including single samples — after the
    engine is already primed must each yield exactly chunk-sized output."""
    q = _qbank(3, 15)
    rng = np.random.default_rng(20)
    x = rng.integers(-128, 128, (1, 60))
    eng = FilterBankEngine(q, channels=1, tile=128, mode=mode)
    eng.push(x[:, :14])  # exactly taps-1: still priming
    assert eng.pending == 14
    outs = [eng.push(x[:, i : i + 1]) for i in range(14, 60)]  # 1 at a time
    assert all(o.shape == (3, 1, 1) for o in outs)
    y = np.concatenate(outs, axis=2)
    assert np.array_equal(y, fir_bit_layers_batch(x, q))


def test_engine_empty_chunk_is_identity():
    q = _qbank(2, 15)
    eng = FilterBankEngine(q, channels=2, tile=128)
    rng = np.random.default_rng(21)
    x = rng.integers(-128, 128, (2, 40))
    y1 = eng.push(x)
    pend = eng.pending
    y_empty = eng.push(np.zeros((2, 0), np.int64))
    assert y_empty.shape == (2, 2, 0)
    assert eng.pending == pend and eng.samples_in == 40
    y2 = eng.push(x)  # stream continues seamlessly after the empty push
    full = fir_bit_layers_batch(np.concatenate([x, x], axis=1), q)
    assert np.array_equal(np.concatenate([y1, y2], axis=2), full)


def test_engine_empty_chunk_while_priming():
    q = _qbank(2, 15)
    eng = FilterBankEngine(q, channels=1, tile=128)
    assert eng.push(np.zeros(0, np.int64)).shape == (2, 1, 0)
    eng.push(np.arange(5))
    assert eng.push(np.zeros(0, np.int64)).shape == (2, 1, 0)
    assert eng.pending == 5


@pytest.mark.parametrize("mode", ["packed", "specialized"])
def test_engine_final_chunk_not_tile_multiple(mode):
    """A final chunk that leaves the padded buffer off the tile grid: the
    windows reaching into the zero padding must be dropped, not returned."""
    q = _qbank(4, 31)
    rng = np.random.default_rng(22)
    x = rng.integers(-128, 128, (1, 777))  # 777 = 6*128 + 9, taps 31
    eng = FilterBankEngine(q, channels=1, tile=128, mode=mode)
    y = np.concatenate(
        [eng.push(x[:, :512]), eng.push(x[:, 512:])], axis=2
    )
    assert y.shape == (4, 1, 777 - 31 + 1)
    assert np.array_equal(y, fir_bit_layers_batch(x, q))


def test_engine_tail_state_and_output_dtype():
    """The carried tail must stay int32 whatever integer dtype is pushed;
    outputs are int32 on an 8-bit stream (any integer dtype whose values
    fit int8) and int64 on a 16-bit one (int16 data) — the serving-side
    contract."""
    q = _qbank(2, 15)
    eng = FilterBankEngine(q, channels=1, tile=128)
    for dtype, out in ((np.int8, np.int32), (np.int32, np.int32),
                       (np.int64, np.int32), (np.int16, np.int64)):
        if dtype == np.int16:
            eng.reset()
            eng.push(np.ones(14, dtype))
        y = eng.push(np.ones(20, dtype))
        assert y.dtype == out
        assert eng._tail.dtype == np.int32
        assert eng._tail.shape == (1, 14)
    eng.reset()
    assert eng._tail.dtype == np.int32 and eng._tail.shape == (1, 0)


def test_engine_predicted_cycles_matches_cost_model():
    from repro.core import MachineSpec, machine_cycles_batch

    q = _qbank(5, 63)
    eng = FilterBankEngine(q, channels=1)
    cyc = eng.predicted_machine_cycles()
    assert np.array_equal(cyc, machine_cycles_batch(q))
    spec = MachineSpec(taps=63, fused_last_add=True, start_overhead=2)
    fused = eng.predicted_machine_cycles(spec)
    assert np.array_equal(
        fused, machine_cycles_batch(q, overhead=2, fused_last_add=True)
    )
    assert eng.predicted_mean_cycles() == pytest.approx(cyc.mean())
    # cached: same spec parameters → same array object
    assert eng.predicted_machine_cycles(spec) is fused
    with pytest.raises(ValueError):
        eng.predicted_machine_cycles(MachineSpec(taps=127))
