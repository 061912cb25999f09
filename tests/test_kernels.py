"""Pallas kernels vs their jnp oracles (interpret mode), shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import po2_quantize
from repro.filters import design_bank, fir_direct
from repro.kernels import (blmac_fir, pulse_dequantize, pulse_matmul_op,
                           pulse_quantize)
from repro.kernels.ref import blmac_fir_ref, fir_direct_ref, pulse_decode_ref


@pytest.mark.parametrize("taps", [7, 55, 127])
@pytest.mark.parametrize("n", [300, 2500])
@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32])
@pytest.mark.parametrize("specialize", [True, False])
def test_blmac_fir_sweep(taps, n, dtype, specialize):
    rng = np.random.default_rng(taps * n)
    cut = 0.2 + 0.5 * rng.random()
    h = design_bank(taps, [("lowpass", float(cut))])[0]
    q, _ = po2_quantize(h, 16)
    # paper §2.1 regime: sample VALUES stay 8-bit (dtype is storage);
    # 16b coeffs × 8b samples × ≤255 taps fits the int32 accumulator
    x = rng.integers(-128, 128, size=n).astype(dtype)
    y = blmac_fir(jnp.asarray(x), q, specialize=specialize, tile=512)
    expect = fir_direct(x.astype(np.int64), q)
    assert np.array_equal(np.asarray(y), expect)


def test_blmac_fir_refs_agree():
    rng = np.random.default_rng(0)
    h = design_bank(63, [("bandpass", (0.25, 0.7))])[0]
    q, _ = po2_quantize(h, 16)
    x = jnp.asarray(rng.integers(-128, 128, 700), jnp.int32)
    a = blmac_fir_ref(x, q)
    b = fir_direct_ref(x, q)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_blmac_fir_rejects_asymmetric():
    with pytest.raises(ValueError):
        blmac_fir(jnp.zeros(100, jnp.int32), np.arange(31))


@pytest.mark.parametrize("planes", [1, 2, 4])
@pytest.mark.parametrize("k,n,m", [(128, 128, 8), (512, 256, 16), (256, 384, 4)])
def test_pulse_matmul_sweep(planes, k, n, m):
    rng = np.random.default_rng(planes * k + n)
    w = rng.standard_normal((k, n)) * np.exp2(rng.integers(-8, 8, (k, n)))
    codes, ge = pulse_quantize(w, planes)
    wd = pulse_dequantize(codes, ge)
    x = rng.standard_normal((m, k)).astype(np.float32)
    y_kern = pulse_matmul_op(jnp.asarray(x), jnp.asarray(codes),
                             jnp.asarray(ge), planes, bm=max(1, m // 2),
                             bk=128, bn=128)
    y_ref = x @ wd
    scale = np.abs(y_ref).max() + 1e-9
    assert np.abs(np.asarray(y_kern) - y_ref).max() / scale < 1e-5
    # jnp decode oracle agrees with numpy decode
    wd2 = np.asarray(pulse_decode_ref(jnp.asarray(codes), jnp.asarray(ge)))
    np.testing.assert_allclose(wd2, wd, rtol=1e-6)


def test_pulse_quantize_error_decreases_with_planes():
    rng = np.random.default_rng(1)
    w = rng.standard_normal((256, 64))
    errs = []
    for p in (1, 2, 3, 4):
        codes, ge = pulse_quantize(w, p)
        errs.append(np.abs(pulse_dequantize(codes, ge) - w).mean())
    assert errs == sorted(errs, reverse=True)
    assert errs[3] < 0.01 * np.abs(w).mean()


def test_pulse_quantize_exact_for_po2_weights():
    """P=1 is exact when weights ARE signed powers of two (paper's
    variable-precision claim in its purest form)."""
    rng = np.random.default_rng(2)
    w = np.exp2(rng.integers(-6, 6, (64, 32)).astype(np.float64))
    w *= rng.choice([-1.0, 1.0], w.shape)
    codes, ge = pulse_quantize(w, 1)
    np.testing.assert_allclose(pulse_dequantize(codes, ge), w, rtol=0)


def test_zero_column_group():
    w = np.zeros((64, 8))
    codes, ge = pulse_quantize(w, 2)
    assert np.abs(pulse_dequantize(codes, ge)).max() == 0.0


# ---------------------------------------------------------------------------
# the bank kernel's exact bf16 contraction: worst case and out-of-bound
# ---------------------------------------------------------------------------


def _all_ones_bank(taps: int, n_layers: int) -> np.ndarray:
    """Packed bank of two filters whose every folded tap carries a trit
    in each of ``n_layers`` layers: +1 everywhere (digit 2**n - 1 per
    tap), and −1 everywhere.  Not CSD — the widest superlayer digits any
    packed operand can put in front of the kernel."""
    from repro.core.csd import pack_trits

    trits = np.ones((2, n_layers, taps // 2 + 1), np.int8)
    trits[1] = -1
    return pack_trits(trits)


@pytest.mark.parametrize("samples", ["min", "alternating"])
def test_bank_kernel_exact_at_bf16_bound(samples):
    """All +1 (and all −1) trits at merge 8, the longest accepted filter
    (255 taps → m_pad = 128) and ±128 samples folded to ±256: every
    operand and partial sum sits at the edge of `bf16_dot_safe`, and the
    kernel body is still bit-exact against the oracle."""
    from repro.filters import fir_bit_layers_batch
    from repro.kernels.blmac_fir import (BF16_MERGE_MAX, bf16_dot_safe,
                                         blmac_fir_bank, plan_bank_schedule)

    taps, n = 255, 255 + 600
    packed = _all_ones_bank(taps, BF16_MERGE_MAX)
    sched = plan_bank_schedule(packed, None, BF16_MERGE_MAX)
    (shift_in, parts), = sched.groups[0].schedule
    assert sum(1 << rel for _, rel in parts) == 255
    assert bf16_dot_safe(128, parts)
    if samples == "min":
        x = np.full((1, n), -128)  # every fold −256, centre −128
    else:
        x = np.where(np.arange(n) % 2, 127, -128)[None, :]
    y = blmac_fir_bank(jnp.asarray(x), packed, taps, tile=256,
                       merge=BF16_MERGE_MAX, fast_path=False, interpret=True)
    w = np.full((2, taps), 255, np.int64)
    w[1] = -255
    assert np.array_equal(np.asarray(y), fir_bit_layers_batch(x, w))


@pytest.mark.parametrize("interpret", [
    pytest.param(False, id="mosaic"), pytest.param(True, id="interpret")])
def test_bank_kernel_raises_beyond_bf16_bound(interpret):
    """A superlayer of nine merged layers (digit 511) is not bf16-exact:
    the Pallas bank kernel refuses it, compiled or interpreted, instead
    of rounding."""
    from repro.kernels.blmac_fir import blmac_fir_bank

    taps = 31
    packed = _all_ones_bank(taps, 9)
    x = jnp.zeros((1, 200), jnp.int32)
    with pytest.raises(ValueError, match="bf16"):
        blmac_fir_bank(x, packed, taps, tile=128, merge=9, fast_path=False,
                       interpret=interpret)


def _lowered_texts():
    """(scope, kernel name or None, lowered text with locations) of each
    kernel entry point, lowered on the CPU at a small shape."""
    from repro.compiler import compile_bank
    from repro.filters import spread_lowpass_qbank
    from repro.kernels.blmac_fir import (_bank_call, _combine_shared,
                                         frame_signal_batch, plane_combine,
                                         specialized_program)

    prog = compile_bank(spread_lowpass_qbank(8, 31))
    sched = prog.schedule(8, 4)
    g = sched.groups[0]
    frames, _ = frame_signal_batch(jnp.zeros((1, 512), jnp.int32), 31, 128)
    op = jnp.asarray(g.packed.view(np.int32))
    bank = _bank_call.lower(frames, op, taps=31, schedule=g.schedule,
                            tail_shift=g.tail_shift, tile=128, bank_tile=8,
                            interpret=True)
    planes = plane_combine.lower(jnp.zeros((8, 2, 256), jnp.int32), 200,
                                 True)
    spec = specialized_program(prog.pulse_schedules()[0], 31, 128,
                               True).lower(jnp.zeros(512, jnp.int32))
    comb = _combine_shared.lower(jnp.zeros((6, 1, 128), jnp.int32),
                                 jnp.ones((4, 2), jnp.int32), n_real=4)
    return [("blmac/bank_kernel", "blmac_bank_kernel", bank),
            ("blmac/plane_combine", "blmac_plane_combine", planes),
            ("blmac/specialized", "blmac_specialized", spec),
            ("blmac/combine", None, comb)]


def test_kernels_carry_stable_names_in_their_hlo():
    """Each kernel entry point lowers under its own `jax.named_scope`,
    and each Pallas call under its own ``name``, so a trace reader finds
    them by name after a refactor."""
    for scope, name, lowered in _lowered_texts():
        text = lowered.as_text(debug_info=True)
        assert scope in text
        if name is not None:
            assert name in text
