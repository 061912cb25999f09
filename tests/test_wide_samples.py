"""16-bit sample streams: a stream's width comes from its data, a 16-bit
stream runs as two 8-bit planes through the unchanged bank kernels and
comes back as exact int64, and every engine refuses samples wider than
it serves.

The reference is the plain int64 FIR (`repro.filters.fir_direct`) of the
whole stream, so the overlap-save tail is checked to carry 16-bit
history across pushes."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.compiler import compile_bank
from repro.filters import (FilterBankEngine, ShardedFilterBankEngine,
                           fir_direct, spread_lowpass_qbank)
from repro.filters.bank import split_planes
from repro.kernels.blmac_fir import COMBINE_COLS, plane_combine
from repro.serving import BankSessionServer

EXTREMES = [-32768, 32767, -32768, -32768, 32767, 32767, 0, -1, 1]


def _reference(q, x):
    """(B, C, n_out) int64: the FIR of every row on every channel."""
    return np.stack([np.stack([fir_direct(xc, row) for xc in x])
                     for row in q])


def _stream(channels, lengths, dtype=np.int16, seed=0):
    info = np.iinfo(dtype)
    rng = np.random.default_rng(seed)
    chunks = [rng.integers(info.min, int(info.max) + 1, (channels, n),
                           dtype=dtype) for n in lengths]
    chunks[0][:, :len(EXTREMES)] = np.asarray(EXTREMES).clip(info.min,
                                                             info.max)
    return chunks


def _push_all(eng, chunks):
    ys = [eng.push(x) for x in chunks]
    return np.concatenate(ys, axis=2), np.concatenate(chunks, axis=1)


@pytest.mark.parametrize("taps", [127, 255])
@pytest.mark.parametrize("channels", [1, 2])
def test_int16_stream_is_exact_int64_across_pushes(taps, channels):
    q = spread_lowpass_qbank(12, taps)
    eng = FilterBankEngine(compile_bank(q), channels=channels,
                           mode="packed", bank_tile=8, tile=128)
    # the second chunk is shorter than the filter: the tail carries
    # 16-bit samples through a priming-length push
    chunks = _stream(channels, [taps + 90, taps // 2, 300])
    y, x = _push_all(eng, chunks)
    assert y.dtype == np.int64
    want = _reference(q, x)
    assert np.abs(want).max() >= 2 ** 31  # the outputs need int64
    np.testing.assert_array_equal(y, want)
    stats = eng.push_stats()
    assert stats["wide_pushes"] == stats["pushes"] == 3
    # 8 bytes an output, read in rows of whole combine blocks
    rows = 12 * channels
    read = [rows * 8 * -(-n // COMBINE_COLS) * COMBINE_COLS
            for n in (90 + 1, taps // 2, 300)]
    assert stats["bytes_read_back"] == sum(read)


@pytest.mark.parametrize("dtype", [np.int8, np.int32])
def test_int8_valued_stream_keeps_the_int32_result(dtype):
    q = spread_lowpass_qbank(12, 31)
    chunks = _stream(2, [200, 150], dtype=np.int8, seed=1)
    y, x = _push_all(FilterBankEngine(compile_bank(q), channels=2,
                                      mode="packed", tile=128),
                     [c.astype(dtype) for c in chunks])
    assert y.dtype == np.int32
    np.testing.assert_array_equal(y, _reference(q, x))


def test_specialized_mode_serves_int16_streams_exactly():
    """A narrow bank (3 filters, one 8-row bank tile at merge 1, the
    paper's one matmul per bit layer) serves a 16-bit stream exactly."""
    q = spread_lowpass_qbank(3, 31)
    eng = FilterBankEngine(q, channels=2, tile=128, merge=1)
    assert eng.bank_tile == 8
    y, x = _push_all(eng, _stream(2, [200, 100]))
    assert y.dtype == np.int64
    np.testing.assert_array_equal(y, _reference(q, x))


@pytest.mark.parametrize("first, second", [
    (np.int16, np.int8), (np.int8, np.int16), (np.int16, np.int32)])
def test_a_width_change_mid_stream_raises_until_reset(first, second):
    eng = FilterBankEngine(spread_lowpass_qbank(4, 31), channels=1,
                           mode="packed", tile=128)
    eng.push(np.ones((1, 64), first))
    with pytest.raises(ValueError, match="reset"):
        eng.push(np.ones((1, 64), second))
    eng.reset()
    assert eng.sample_bits is None
    eng.push(np.ones((1, 64), second))


@pytest.mark.parametrize("chunk", [
    np.array([[0, 128]]), np.array([[-129, 0]]), np.array([[0, 40000]]),
    np.array([[0.5, 1.0]]), np.array([[0, 200]], np.uint8)])
def test_values_outside_an_8bit_stream_raise(chunk):
    eng = FilterBankEngine(spread_lowpass_qbank(4, 31), channels=1,
                           mode="packed", tile=128)
    with pytest.raises(ValueError):
        eng.push(chunk)
    assert eng.push_stats()["pushes"] == 0


def test_a_16bit_tail_restored_keeps_the_stream_16bit():
    q = spread_lowpass_qbank(4, 31)
    eng = FilterBankEngine(compile_bank(q), channels=1, mode="packed",
                           tile=128)
    eng.push(_stream(1, [100])[0])
    snap = eng.snapshot_tail()
    other = FilterBankEngine(compile_bank(q), channels=1, mode="packed",
                             tile=128)
    other.restore_tail(snap)
    with pytest.raises(ValueError, match="16-bit"):
        other.push(np.zeros((1, 50), np.int8))


@pytest.mark.parametrize("through_disk", [False, True])
def test_a_quiet_16bit_tail_restored_keeps_the_stream_16bit(tmp_path,
                                                             through_disk):
    """A 16-bit stream whose last samples fit int8 is still 16-bit after
    a restore: the width rides in the snapshot, not in the tail's values."""
    from repro.compiler.state import TailSnapshot

    q = spread_lowpass_qbank(4, 31)
    eng = FilterBankEngine(compile_bank(q), channels=1, mode="packed",
                           tile=128)
    eng.push(np.arange(-40, 40, dtype=np.int16)[None, :])
    snap = eng.snapshot_tail()
    assert snap.sample_bits == 16
    if through_disk:
        snap.save(tmp_path / "tail.npz")
        snap = TailSnapshot.load(tmp_path / "tail.npz")
    other = FilterBankEngine(compile_bank(q), channels=1, mode="packed",
                             tile=128)
    other.restore_tail(snap)
    with pytest.raises(ValueError, match="16-bit"):
        other.push(np.zeros((1, 50), np.int8))
    y = other.push(np.arange(50, dtype=np.int16)[None, :])
    assert y.dtype == np.int64
    with pytest.raises(ValueError, match="16-bit"):
        ShardedFilterBankEngine(compile_bank(q), channels=1,
                                tile=128).restore_tail(snap)


def _session_push(chunk):
    server = BankSessionServer(compile_bank(spread_lowpass_qbank(8, 31)),
                               n_slots=2, auto_step=False)
    server.open_session([0, 1]).push(chunk)


def _sharded_push(chunk):
    ShardedFilterBankEngine(compile_bank(spread_lowpass_qbank(8, 31)),
                            channels=1, tile=128).push(chunk[None, :])


def _sharded_lanes(chunk):
    ShardedFilterBankEngine(compile_bank(spread_lowpass_qbank(8, 31)),
                            channels=1, tile=128).apply_lanes(chunk[None, :])


def _plain_lanes(chunk):
    FilterBankEngine(spread_lowpass_qbank(8, 31), channels=1,
                     mode="packed", tile=128).apply_lanes(chunk[None, :])


@pytest.mark.parametrize("push", [_session_push, _sharded_push,
                                  _sharded_lanes, _plain_lanes])
def test_8bit_only_paths_refuse_16bit_values(push):
    chunk = np.zeros(64, np.int16)
    chunk[5] = 300
    with pytest.raises(ValueError, match="8-bit"):
        push(chunk)
    push(chunk.clip(-128, 127))  # the same chunk in range is served


def test_planes_recombine_to_every_int16():
    x = np.arange(-32768, 32768, dtype=np.int32)[None, :]
    planes = split_planes(x)
    lo, hi = planes
    assert lo.min() == -128 and lo.max() == 127
    assert hi.min() == -128 and hi.max() == 128
    np.testing.assert_array_equal(256 * hi + lo, x[0])


EDGES = np.array([0, 1, -1, 2 ** 35, -2 ** 35, 2 ** 35 - 1, -2 ** 35 + 1,
                  2 ** 32, -2 ** 32, 2 ** 31, -2 ** 31 - 1, 255, -256])


@pytest.mark.parametrize("channels, n_out", [(1, 256), (2, 300)])
def test_plane_combine_kernel_is_exact_at_the_edges(channels, n_out):
    rng = np.random.default_rng(3)
    rows, cols = 130, 384  # a partial block of rows and of columns
    v = rng.integers(-2 ** 35, 2 ** 35 + 1, (rows, channels, cols))
    v.flat[:EDGES.size] = EDGES
    lo = ((v + 128) & 255) - 128
    hi = (v - lo) >> 8
    # the int32 extremes of both planes, whatever the value they make
    lo[-1, :, :4] = hi[-1, :, :4] = [-2 ** 31, 2 ** 31 - 1, -2 ** 31,
                                     2 ** 31 - 1]
    want = hi.astype(np.int64) * 256 + lo
    y = jnp.asarray(np.concatenate([lo, hi], axis=1).astype(np.int32))
    out = np.asarray(plane_combine(y, n_out, True))
    n_pad = -(-n_out // COMBINE_COLS) * COMBINE_COLS  # whole blocks
    assert out.shape == (rows, channels, 2 * n_pad) and out.dtype == np.int32
    np.testing.assert_array_equal(out[:, :, :2 * n_out].view(np.int64),
                                  want[:, :, :n_out])
