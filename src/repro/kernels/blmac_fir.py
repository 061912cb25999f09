"""Pallas TPU kernels: BLMAC FIR filtering, single filters and whole banks.

TPU adaptation of the paper's machine (DESIGN.md §2): the FPGA executes one
add per pulse per *sample*; these kernels execute one VPU vector add per
pulse per *tile of output samples* (lane-parallel, pulse-serial).  The
symmetric pre-add (Eq. 3) is fused.  All arithmetic is exact: int32
accumulators — the §2.1 bound (16-bit coeffs × 8-bit samples × ≤255
taps fits 32 bits) is asserted ONCE at pack time
(`core.csd.assert_int32_bound`), not per call — and, in the bank
kernel, bf16 MXU contractions held to `bf16_dot_safe`.

Three modes:

  * **specialized** — the CSD pulse list of ONE filter is baked into the
    kernel at trace time: the emitted program is literally `acc ±= u_j`
    per pulse plus one shift per bit layer — the paper's add-count cost
    model *is* the instruction count.  One (cheap) recompile per distinct
    pulse schedule, held in an LRU cache (`specialized_program`), exactly
    like reprogramming the FPGA weight memory.
  * **bank** — the workhorse for filter *banks*: a `pallas_call` over a
    3-D grid `(bank_tile, channel, signal_tile)` applies B filters to C
    channels.  Trits travel as **packed uint32 words** (16 two-bit trit
    codes per word, `core.csd.pack_trits` layout: 0b00=0, 0b01=+1,
    0b11=−1, signed CSD end-to-end — ~2× fewer pulses than binary
    layers, paper Tab. 3) and are unpacked in-kernel with shifts and
    masks.  Each grid step builds the framed `(K, tile)` window matrix
    ONCE from static slices of the frame and reuses it for every
    surviving layer and every filter in the bank tile.

    The Horner loop is **schedule-driven**, not fixed-length: at pack
    time `plan_bank_schedule` sorts the filters by layer-occupancy
    signature, partitions them into occupancy-homogeneous bank tiles,
    and emits per-tile-group schedules of *superlayers* — runs of
    ``merge`` adjacent CSD layers contracted in one
    ``(bank_tile, K) @ (K, tile)`` exact bf16 matmul, with one
    ``acc << shift`` per populated superlayer.  Bit layers empty across
    the whole tile cost **zero** kernel work (layer-skip); the schedule
    is static per compiled signature and jit-cached exactly like
    `specialized_program`.
  * **dynamic** — legacy single-filter runtime-trit entry point: a B=1
    scheduled bank call whose compile cache is keyed on layer occupancy,
    not the pulse list (trits stay a runtime operand).  `blmac_fir_bank`
    itself fast-paths B≤1 *packed* banks to the specialized program —
    the route that erased the PR-1 B=1 framing regression.

16-bit samples run as two 8-bit planes through the same kernels:
`plane_combine` (one Pallas kernel, ``blmac_plane_combine``) turns the
planes' int32 outputs into exact int64 words on the device.

Input layout: the host frames each channel into overlapping tiles
(n_tiles, tile + taps − 1 padded to a lane multiple); BlockSpec then maps
one frame per grid step into VMEM.  The ~taps/tile halo duplication
(≈12% at tile=1024, taps=127) is the price of clean non-overlapping
BlockSpecs and is counted in the roofline maths.

Since the one-program refactor this module is pure *execution*: the
pack-time half of the pipeline (trit packing, occupancy sorting,
superlayer scheduling) lives in `repro.compiler` — `pack_bank_trits`,
`plan_bank_schedule`, `BankSchedule` and friends are re-exported here
for backward compatibility.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compiler.program import compile_packed, pack_bank_trits  # noqa: F401
from ..compiler.schedule import (  # noqa: F401 — re-exported, moved in PR 5
    BankSchedule, MAX_BANK_TILE, MERGE_DEFAULT, TileGroup, default_bank_tile,
    plan_bank_schedule, superlayer_schedule)
from ..core.csd import csd_digits, pack_trits, unpack_trits
from .runtime import resolve_interpret, span

LANE = 128
TRITS_PER_WORD = 16


def _pad_to(n: int, m: int) -> int:
    return (n + m - 1) // m * m


# ---------------------------------------------------------------------------
# host-side framing (overlap-save layout)
# ---------------------------------------------------------------------------

def frame_signal_batch(
    x: jnp.ndarray, taps: int, tile: int
) -> tuple[jnp.ndarray, int]:
    """(C, T) → (C, n_tiles, frame_len) overlapping frames per channel;
    returns padded frames and the number of valid output samples."""
    t = x.shape[-1]
    n_out = t - taps + 1
    if n_out <= 0:
        raise ValueError("signal shorter than the filter")
    n_tiles = -(-n_out // tile)
    frame_len = _pad_to(tile + taps - 1, LANE)
    pad = (n_tiles - 1) * tile + frame_len - t
    xp = jnp.pad(x, ((0, 0), (0, max(0, pad))))
    idx = jnp.arange(n_tiles)[:, None] * tile + jnp.arange(frame_len)[None, :]
    return xp[:, idx], n_out


def frame_signal(x: jnp.ndarray, taps: int, tile: int) -> tuple[jnp.ndarray, int]:
    """(T,) → (n_tiles, frame_len) overlapping frames; returns padded frames
    and the number of valid output samples."""
    frames, n_out = frame_signal_batch(x[None, :], taps, tile)
    return frames[0], n_out


# ---------------------------------------------------------------------------
# specialized single-filter kernel (pulse schedule baked in at trace time)
# ---------------------------------------------------------------------------

def _window(frame_ref, j: int, tile: int) -> jnp.ndarray:
    """(1, tile) samples x[t + j] of the frame in ``frame_ref`` (block
    ``(1, 1, frame_len)``): a static lane-offset slice read from the ref,
    the form Mosaic lowers (a gather inside the kernel it refuses)."""
    return frame_ref[0, :, pl.ds(j, tile)]


def _fold(frame_ref, j: int, taps: int, tile: int) -> jnp.ndarray:
    """Row j of the symmetric fold, u_j[t] = x[t+j] + x[t+taps-1-j]
    (the centre row j = taps // 2 is not folded)."""
    if j == taps // 2:
        return _window(frame_ref, j, tile)
    return _window(frame_ref, j, tile) + _window(frame_ref, taps - 1 - j, tile)


def _fir_kernel_specialized(frame_ref, out_ref, *, pulses, taps, tile):
    """One grid step = one output tile.  `pulses` is a static tuple of
    (layer, j, sign) triples, MSB layer first."""
    # symmetric fold, built lazily: only the taps that carry pulses
    needed = sorted({j for (_, j, _) in pulses})
    u = {j: _fold(frame_ref, j, taps, tile) for j in needed}
    acc = jnp.zeros((1, tile), jnp.int32)
    layer_of = None
    for layer, j, sign in pulses:  # MSB layer first, grouped by layer
        if layer_of is None:
            layer_of = layer
        while layer_of > layer:  # Horner: one shift per layer boundary
            acc = acc << 1
            layer_of -= 1
        acc = acc + u[j] if sign > 0 else acc - u[j]
    if layer_of is not None and layer_of > 0:
        acc = acc << layer_of
    out_ref[...] = acc


def pulses_msb_first(qcoeffs: np.ndarray) -> tuple[tuple[int, int, int], ...]:
    """Static pulse schedule from quantized symmetric coefficients."""
    taps = qcoeffs.shape[0]
    digits = csd_digits(np.asarray(qcoeffs[: taps // 2 + 1], np.int64))
    out = []
    for layer in range(digits.shape[1] - 1, -1, -1):
        for j in np.nonzero(digits[:, layer])[0]:
            out.append((int(layer), int(j), int(digits[j, layer])))
    return tuple(out)


@functools.lru_cache(maxsize=1024)
def specialized_program(pulses, taps: int, tile: int, interpret: bool):
    """Compiled BLMAC program for one pulse schedule.

    LRU-cached on the pulse tuple: reprogramming a filter that was seen
    before is a dict hit, a new schedule costs one (cheap) trace — the
    software analogue of reloading the FPGA weight memory.  The returned
    callable is additionally jit-cached per input length.
    """
    kern = functools.partial(
        _fir_kernel_specialized, pulses=pulses, taps=taps, tile=tile
    )

    @jax.jit
    @jax.named_scope("blmac/specialized")
    def run(x: jnp.ndarray) -> jnp.ndarray:
        frames, n_out = frame_signal(x.astype(jnp.int32), taps, tile)
        n_tiles, frame_len = frames.shape
        # Mosaic blocks: the last two dims are (8, 128)-multiples or the
        # array's own, hence the unit middle axis on the frames
        y = pl.pallas_call(
            kern,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((1, 1, frame_len), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, n_tiles * tile), jnp.int32),
            interpret=interpret,
            name="blmac_specialized",
        )(frames.reshape(n_tiles, 1, frame_len))
        return y.reshape(-1)[:n_out]

    return run


def blmac_fir_specialized(
    x: jnp.ndarray,
    pulses,
    taps: int,
    tile: int = 1024,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Apply one pulse-specialized filter; compiles at most once per
    distinct (pulse schedule, taps, tile, backend)."""
    return specialized_program(
        tuple(pulses), taps, tile, resolve_interpret(interpret)
    )(x)


# ---------------------------------------------------------------------------
# batched bank kernel (packed-trit operands, 3-D grid, layer-skip schedule)
# ---------------------------------------------------------------------------

def _fir_kernel_bank(
    frame_ref, packed_ref, out_ref, *, taps, tile, schedule, tail_shift,
    bank_tile, n_words, k_pad
):
    """One grid step = one (bank tile × signal tile) block of one channel.

    `packed_ref` holds 2-bit trit codes, 16 per uint32 word (viewed as
    int32 — the `& 3` mask makes arithmetic vs logical shift moot), laid
    out (bank_tile, n_sel, n_words) over the folded half-filter, where
    the n_sel slices are ONLY the bit layers populated somewhere in this
    bank tile (MSB first — see `plan_bank_schedule`).

    `schedule` drives the Horner recursion: a static tuple of superlayer
    entries ``(shift_in, ((sel_idx, rel_weight), ...))``, MSB first.  Each
    entry shifts the accumulator left by the layer gap to the previous
    superlayer, sums its ``merge``-adjacent trit layers into one small-
    integer digit matrix, and contracts it against the shared window
    matrix in ONE ``(bank_tile, K) @ (K, tile)`` bf16 matmul with f32
    accumulation — exact under `bf16_dot_safe`, which `_bank_call`
    asserts for every superlayer.  Layers (and whole superlayers) empty
    across the tile appear nowhere: the emitted program length tracks
    the occupancy, not the worst case.

    Every step is a form Mosaic lowers: static lane-offset slices of the
    frame ref (no in-kernel gather), one trit layer read from the ref at
    a time, a 2-D lane-iota unpack (no reshape), and a bf16 MXU dot (the
    MXU takes no int32 operands).
    """
    half = taps // 2
    # The (K, tile) window matrix, built once per grid step and shared by
    # every superlayer and every filter in the bank tile.  Row j holds the
    # symmetric fold u_j[t] = x[t+j] + x[t+taps-1-j] (centre row: no
    # fold); rows past the centre are zero and meet only zero trits.
    rows = [_fold(frame_ref, j, taps, tile) for j in range(half + 1)]
    if k_pad > half + 1:
        rows.append(jnp.zeros((k_pad - half - 1, tile), jnp.int32))
    u = jnp.concatenate(rows, axis=0)
    u = u.astype(jnp.float32).astype(jnp.bfloat16)  # |u_j| <= 2**8: exact

    # trit m of the folded half-filter sits in word m // 16 at bit 2*(m % 16)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bank_tile, k_pad), 1)
    word_of = lane // TRITS_PER_WORD
    shift = 2 * (lane % TRITS_PER_WORD)

    def trit_layer(sel_idx):
        words = packed_ref[:, sel_idx, :]  # (bank_tile, n_words) int32
        w = jnp.zeros((bank_tile, k_pad), jnp.int32)
        for i in range(n_words):  # lanes past m_pad match no word: zero
            w = jnp.where(word_of == i, words[:, i:i + 1], w)
        codes = (w >> shift) & 3
        return (codes == 1).astype(jnp.int32) - (codes == 3).astype(jnp.int32)

    acc = jnp.zeros((bank_tile, tile), jnp.int32)
    for shift_in, parts in schedule:  # MSB → LSB over populated superlayers
        if shift_in:
            acc = acc << shift_in
        d = None
        for sel_idx, rel in parts:
            dl = trit_layer(sel_idx)
            if rel:
                dl = dl << rel
            d = dl if d is None else d + dl
        # one MXU matmul per populated superlayer: every pulse in the
        # tile is one lane-parallel add inside this contraction
        y = jnp.dot(
            d.astype(jnp.float32).astype(jnp.bfloat16), u,
            preferred_element_type=jnp.float32,
        )
        acc = acc + y.astype(jnp.int32)
    if tail_shift:
        acc = acc << tail_shift
    out_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=(
        "taps", "schedule", "tail_shift", "tile", "bank_tile", "interpret"
    ),
)
@jax.named_scope("blmac/bank_kernel")
def _bank_call(
    frames: jnp.ndarray,  # (C, n_tiles, frame_len) int32
    packed: jnp.ndarray,  # (B_pad, n_sel, n_words) int32, selected layers
    taps: int,
    schedule: tuple,
    tail_shift: int,
    tile: int,
    bank_tile: int,
    interpret: bool,
) -> jnp.ndarray:
    """Scheduled bank call → (B_pad, C·n_tiles·tile) int32.  jit's
    static-argument cache makes this the bank analogue of
    `specialized_program`: one compile per distinct (schedule, geometry)
    signature, every later dispatch a cache hit.

    Raises ValueError for a schedule with a superlayer outside
    `bf16_dot_safe`: the kernel's contraction would not be exact."""
    n_chan, n_tiles, frame_len = frames.shape
    b_pad, n_sel, n_words = packed.shape
    m_pad = n_words * TRITS_PER_WORD
    for _, parts in schedule:
        if not bf16_dot_safe(m_pad, parts):
            raise ValueError(
                f"superlayer {parts} is outside the bank kernel's exact "
                f"bf16 contraction (digits <= {BF16_EXACT_INT}, "
                f"m_pad={m_pad}): plan it with merge <= {BF16_MERGE_MAX}"
            )
    kern = functools.partial(
        _fir_kernel_bank,
        taps=taps,
        tile=tile,
        schedule=schedule,
        tail_shift=tail_shift,
        bank_tile=bank_tile,
        n_words=n_words,
        k_pad=_pad_to(m_pad, LANE),
    )
    # Mosaic blocks: the last two dims are (8, 128)-multiples or the
    # array's own — frames get a unit middle axis, the output is one
    # (B_pad, C·n_tiles·tile) matrix of (bank_tile, tile) blocks
    return pl.pallas_call(
        kern,
        grid=(b_pad // bank_tile, n_chan, n_tiles),
        in_specs=[
            pl.BlockSpec(
                (1, 1, frame_len), lambda b, c, s: (c * n_tiles + s, 0, 0)
            ),
            pl.BlockSpec((bank_tile, n_sel, n_words), lambda b, c, s: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (bank_tile, tile), lambda b, c, s: (b, c * n_tiles + s)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b_pad, n_chan * n_tiles * tile), jnp.int32
        ),
        interpret=interpret,
        name="blmac_bank_kernel",
    )(frames.reshape(n_chan * n_tiles, 1, frame_len), packed)


# ---------------------------------------------------------------------------
# compiled lowering lanes
# ---------------------------------------------------------------------------
#
# The scheduled kernel above runs on four execution lanes:
#
#   "interpret" — the Pallas interpreter (the CPU test lane; the historic
#       CI target every BENCH_fir number was recorded on),
#   "mosaic"    — pallas_call compiled for TPU: the same kernel body,
#   "triton"    — pallas_call compiled for GPU,
#   "xla"       — the SAME superlayer schedule lowered as a plain jitted
#       XLA program (no Pallas): the always-available compiled CI target,
#       since the Pallas interpreter is the only Pallas mode a CPU host
#       can run.
#
# The XLA lane keeps the two properties that make the Pallas kernel fast:
# the packed trit words are the *operand* (the 2-bit→{-1,0,+1} decode
# happens inside the jitted program, so XLA fuses it into the dot's LHS
# and trits never round-trip through memory as unpacked int8), and each
# populated superlayer is ONE integer contraction — here against the
# window matrix of EVERY (channel, signal-tile) grid cell at once,
# ``(B_pad, M) @ (M, C·n_tiles·tile)``, which is exactly the
# wide-matmul-unit regime where the compiled autotuner sweep
# re-evaluates the merge heuristic: superlayers whose digit bound stays
# below the f32 mantissa limit run bit-exactly on the float GEMM units
# (`f32_dot_safe`), which caps the winning merge near the f32-safe span
# instead of "fuse everything".
# The cost is materializing that im2col-style window matrix
# (``m_pad × signal`` int32, ~`m_pad`× the signal bytes) instead of one
# (M, tile) block per grid step — the right trade below VMEM-scale
# signals, and the reason the Pallas lanes keep the blocked layout.

LANES = ("interpret", "mosaic", "triton", "xla")

# float32 mantissa: integers of magnitude < 2**24 are exactly
# representable, and sums/products that stay under the bound are exact
F32_EXACT_BOUND = 1 << 24


def f32_dot_safe(m_pad: int, parts) -> bool:
    """Whether one superlayer's contraction is EXACT in float32.

    Under the §2.1 regime every int32 path already assumes (8-bit
    samples — the same precondition the pack-time accumulator bound is
    stated for), the symmetric-fold window entries obey ``|u_j| <= 2**8``
    and the superlayer digit is bounded by its trit shifts,
    ``|d_j| <= sum(2**rel)``.  When ``m_pad * bound(d) * 2**8 < 2**24``
    every partial sum of the dot is an integer below the f32 mantissa
    limit, so running it on the float GEMM units is bit-exact — and on
    CPU XLA those units are ~an order of magnitude faster than the int32
    matmul loop (the wide-matmul-unit effect the compiled merge
    heuristic re-evaluates; see `repro.core.costmodel`).
    """
    bound = sum(1 << rel for _, rel in parts)
    return m_pad * bound * 256 <= F32_EXACT_BOUND


# bfloat16 keeps 8 significant bits: every integer of magnitude <= 2**8
# is exact, so digits and folded 8-bit samples enter the MXU unrounded
BF16_EXACT_INT = 1 << 8
# the widest merge whose superlayer digit (<= 2**merge - 1) stays exact
BF16_MERGE_MAX = 8


def bf16_dot_safe(m_pad: int, parts) -> bool:
    """Whether one superlayer's contraction is EXACT as the Pallas bank
    kernel runs it: bf16 operands, f32 accumulation on the MXU.

    Under the same 8-bit-sample regime as `f32_dot_safe`, the folded
    window entries obey ``|u_j| <= 2**8`` (bf16-exact).  The digit needs
    ``|d_j| <= sum(2**rel) <= 2**8`` (bf16-exact: any merge <=
    `BF16_MERGE_MAX`), and every partial sum must stay an integer below
    the f32 mantissa limit — `f32_dot_safe`, which holds up to the
    largest accepted filter (255 taps → m_pad = 128:
    128 · 255 · 2**8 < 2**24).  `_bank_call` raises on a schedule
    outside this bound rather than return a rounded result.
    """
    bound = sum(1 << rel for _, rel in parts)
    return bound <= BF16_EXACT_INT and f32_dot_safe(m_pad, parts)


def _lane_interpret(lane: str, interpret: bool) -> bool:
    """Pallas ``interpret`` flag for a lane (the "xla" lane never reaches
    a pallas_call)."""
    if lane == "interpret":
        return True
    if lane in ("mosaic", "triton", "xla"):
        return False
    raise ValueError(f"unknown lane {lane!r}; expected one of {LANES}")


@functools.partial(
    jax.jit,
    static_argnames=("taps", "schedule", "tail_shift", "tile", "n_real"),
)
@jax.named_scope("blmac/bank_xla")
def _bank_call_xla(
    frames: jnp.ndarray,  # (C, n_tiles, frame_len) int32
    packed: jnp.ndarray,  # (B_pad, n_sel, n_words) int32, selected layers
    taps: int,
    schedule: tuple,
    tail_shift: int,
    tile: int,
    combine: jnp.ndarray | None = None,  # (n_real, n_shared) int32
    n_real: int | None = None,
) -> jnp.ndarray:
    """The scheduled bank computation as ONE fused XLA program — same
    schedule semantics as `_fir_kernel_bank`, same (B_pad, C, n_tiles,
    tile) result, bit-exact.

    ``combine`` (CSE-optimized programs, `repro.compiler.optimize`) adds
    a second small GEMM to the fused program: rows past ``n_real`` are
    shared partial-sum rows, folded back as ``y[:n_real] + combine @
    y[n_real:]`` — int32 ring arithmetic, so the result equals the
    parent program's output bit-for-bit even if a shared row wraps."""
    n_chan, n_tiles, frame_len = frames.shape
    b_pad, n_sel, n_words = packed.shape
    m_pad = n_words * TRITS_PER_WORD
    half = taps // 2
    # window matrix for EVERY grid cell at once: row j of cell (c, s)
    # holds the symmetric fold u_j[t] = x[t+j] + x[t+taps-1-j]
    j = jnp.arange(m_pad, dtype=jnp.int32)[:, None]
    t = jnp.arange(tile, dtype=jnp.int32)[None, :]
    fwd = frames[..., jnp.minimum(j + t, frame_len - 1)]
    rev = frames[..., jnp.clip(taps - 1 - j + t, 0, frame_len - 1)]
    u = jnp.where(j < half, fwd + rev, jnp.where(j == half, fwd, 0))
    # (C, n_tiles, m_pad, tile) → (m_pad, C·n_tiles·tile): the RHS every
    # superlayer contraction shares
    u = jnp.moveaxis(u, 2, 0).reshape(m_pad, n_chan * n_tiles * tile)

    shifts = 2 * jnp.arange(TRITS_PER_WORD, dtype=jnp.int32)

    def trit_layer(sel_idx):
        # fused unpack: packed words are the operand; the 2-bit decode is
        # part of the jitted program, feeding the dot LHS directly
        codes = (packed[:, sel_idx, :, None] >> shifts) & 3
        d = (codes == 1).astype(jnp.int32) - (codes == 3).astype(jnp.int32)
        return d.reshape(b_pad, m_pad)

    # superlayers whose digit bound admits the exact-f32 contraction run
    # on the float GEMM units (see `f32_dot_safe`); the window matrix is
    # converted once (|u_j| <= 2**8: exact)
    u_f32 = (
        u.astype(jnp.float32)
        if any(f32_dot_safe(m_pad, parts) for _, parts in schedule)
        else None
    )
    acc = jnp.zeros((b_pad, u.shape[1]), jnp.int32)
    for shift_in, parts in schedule:  # MSB → LSB over populated superlayers
        if shift_in:
            acc = acc << shift_in
        d = None
        for sel_idx, rel in parts:
            dl = trit_layer(sel_idx)
            if rel:
                dl = dl << rel
            d = dl if d is None else d + dl
        if f32_dot_safe(m_pad, parts):
            # every partial sum is an integer < 2**24: the f32 dot is
            # bit-exact, and the f32->s32 convert of exact integers is too.
            # HIGHEST keeps a TPU from rounding the operands to bf16
            y = jnp.dot(
                d.astype(jnp.float32), u_f32,
                precision=jax.lax.Precision.HIGHEST,
            ).astype(jnp.int32)
        else:
            y = jnp.dot(d, u, preferred_element_type=jnp.int32)
        acc = acc + y
    if tail_shift:
        acc = acc << tail_shift
    if combine is not None:
        acc = acc[:n_real] + jnp.dot(
            combine, acc[n_real:], preferred_element_type=jnp.int32
        )
    return acc.reshape(acc.shape[0], n_chan, n_tiles, tile)


def pulses_from_packed(packed_row: np.ndarray, taps: int):
    """(n_layers, n_words) packed trits → MSB-first static pulse tuple
    (the `specialized_program` input) — the small-bank fast path's bridge
    from the bank operand format to the pulse-baked kernel."""
    half = taps // 2
    digits = unpack_trits(packed_row, half + 1)  # (L, M) int8
    out = []
    for layer in range(digits.shape[0] - 1, -1, -1):
        for j in np.nonzero(digits[layer])[0]:
            out.append((int(layer), int(j), int(digits[layer, j])))
    return tuple(out)


FAST_PATH_MAX = 1  # banks up to this size dispatch to specialized programs


def blmac_fir_bank(
    x: jnp.ndarray,  # (C, T) or (T,)
    packed: np.ndarray,  # (B, n_layers, n_words) uint32 from pack_bank_trits
    taps: int,
    tile: int = 1024,
    bank_tile: int | None = None,
    interpret: bool | None = None,
    merge: int = MERGE_DEFAULT,
    schedule: BankSchedule | None = None,
    fast_path: bool = True,
    lane: str | None = None,
    combine: np.ndarray | None = None,
    n_real: int | None = None,
) -> jnp.ndarray:
    """Apply a B-filter bank to a C-channel signal with the scheduled
    bank kernel (one `pallas_call` per occupancy tile group).

    Returns int32 (B, C, T - taps + 1).  Bit-exact against
    `repro.filters.fir_bit_layers_batch` on integer inputs, whatever the
    schedule: grouping permutes filters internally and restores the
    caller's order on the way out.

    ``fast_path`` routes banks of ≤ `FAST_PATH_MAX` filters to the
    pulse-specialized kernel — a B=1 "bank" paid 0.70× the per-filter
    baseline in PR 1 purely in framing/padding overhead; now it costs
    exactly its pulse count.  Pass a precomputed ``schedule`` (from
    `plan_bank_schedule`) to skip planning on the hot path — the
    `FilterBankEngine` does this once at construction.  ``lane``
    selects the execution lane (see `LANES`; compiled lanes skip the
    fast path — specialized programs are an interpret-era optimization).
    ``combine``/``n_real`` execute a CSE-optimized shared-row bank (see
    `bank_schedule_apply`); the result then has ``n_real`` rows.
    """
    x = jnp.asarray(x)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None, :]
    packed = np.asarray(packed)
    n_filters = packed.shape[0]
    interpret = resolve_interpret(interpret)

    if (
        fast_path
        and schedule is None
        and combine is None
        and n_filters <= FAST_PATH_MAX
        and lane in (None, "interpret")
    ):
        xi = x.astype(jnp.int32)
        n_out = xi.shape[-1] - taps + 1
        ys = [
            jnp.stack(
                [
                    blmac_fir_specialized(
                        xi[c], pulses_from_packed(packed[b], taps), taps,
                        tile, interpret,
                    )
                    for c in range(xi.shape[0])
                ]
            )
            for b in range(n_filters)
        ]
        y = jnp.stack(ys)[:, :, :n_out]
        return y[:, 0, :] if squeeze else y

    if schedule is None:
        schedule = plan_bank_schedule(packed, bank_tile, merge)
    frames, n_out = frame_signal_batch(x.astype(jnp.int32), taps, tile)
    y = bank_schedule_apply(frames, schedule, taps, tile, interpret, lane=lane,
                            combine=combine, n_real=n_real)
    # one combined slice: separate [:, :, :n_out] then [:, 0, :] would copy
    # the full (B, C, signal) buffer twice on the host
    return y[:, 0, :n_out] if squeeze else y[:, :, :n_out]


def bank_schedule_apply(
    frames: jnp.ndarray,  # (C, n_tiles, frame_len) int32 framed signal
    schedule: BankSchedule,
    taps: int,
    tile: int,
    interpret: bool,
    device_groups: list | None = None,
    lane: str | None = None,
    combine: jnp.ndarray | None = None,
    n_real: int | None = None,
) -> jnp.ndarray:
    """Run every tile group of a `BankSchedule` over pre-framed signal and
    reassemble rows in the caller's filter order → (B, C, n_tiles*tile).

    ``device_groups`` optionally supplies pre-uploaded packed operands
    (one per group, int32 view) so streaming callers don't re-stage the
    bank every chunk.  ``lane`` selects the execution lane (see `LANES`);
    None keeps the legacy behaviour — a pallas_call honouring the
    ``interpret`` flag — while ``"xla"`` routes to the fused compiled
    lowering `_bank_call_xla` (bit-exact against every other lane).

    ``combine``/``n_real`` execute a CSE-optimized program's shared-row
    layout (`repro.compiler.optimize`): rows past ``n_real`` are shared
    partial sums, folded back after reassembly as one small int32 GEMM
    plus an add — on the single-group xla path the GEMM fuses into the
    lowered program itself.  The result then has ``n_real`` rows."""
    n_chan, n_tiles, _ = frames.shape
    if combine is not None:
        combine = jnp.asarray(np.asarray(combine, np.int32))
    if lane is not None and lane != "xla":
        interpret = _lane_interpret(lane, interpret)
    if len(schedule.groups) == 1 and lane == "xla":
        # Single tile group (the common autotuned shape): fold the
        # caller-order restore into the dot's LHS instead of gathering
        # the (B, C, signal) result — permuting the tiny packed operand's
        # rows permutes the output rows for free, where `y[inv]` is a
        # full-output-size copy (~6 ms of the ~40 ms xla arm at the
        # BENCH_compiled geometry).  Pad rows drop out with the same
        # indexing.  Pallas lanes keep the gather: their grid needs the
        # padded, occupancy-sorted row layout.
        g = schedule.groups[0]
        if not g.sel_layers:
            rows = len(schedule.inv) if combine is None else n_real
            return jnp.zeros((rows, n_chan, n_tiles * tile), jnp.int32)
        op = (
            device_groups[0]
            if device_groups is not None
            else jnp.asarray(g.packed.view(np.int32))
        )[schedule.inv]
        y = _bank_call_xla(
            frames, op, taps, g.schedule, g.tail_shift, tile,
            combine=combine, n_real=n_real,
        )
        return y.reshape(y.shape[0], n_chan, -1)
    parts = []
    for gi, g in enumerate(schedule.groups):
        rows = g.packed.shape[0]
        if not g.sel_layers:  # all-zero tile group: no kernel at all
            parts.append(
                jnp.zeros((rows, n_chan, n_tiles * tile), jnp.int32)
            )
            continue
        op = (
            device_groups[gi]
            if device_groups is not None
            else jnp.asarray(g.packed.view(np.int32))
        )
        with span("group", group=gi):
            if lane == "xla":
                y = _bank_call_xla(
                    frames, op, taps, g.schedule, g.tail_shift, tile
                )
            else:
                y = _bank_call(
                    frames, op, taps, g.schedule, g.tail_shift, tile,
                    schedule.tile_size, interpret,
                )  # (rows, C, n_tiles, tile)
        parts.append(y.reshape(rows, n_chan, -1))
    y = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    y = y[schedule.inv]  # drop pad rows, restore caller's filter order
    if combine is not None:
        y = _combine_shared(y, combine, n_real)
    return y


# ---------------------------------------------------------------------------
# 16-bit samples: the two 8-bit planes' outputs combined into int64 words
# ---------------------------------------------------------------------------

COMBINE_ROWS = 128  # filters per combine block: the transposes' lane width
COMBINE_COLS = 256  # output samples per combine block
_SIGN = -(1 << 31)


def _plane_words(lo, hi):
    """The little-endian int32 words (low, high) of ``256 · hi + lo``,
    exactly, for any int32 ``lo`` and ``hi``: ``256 · hi`` is split at
    bit 24 of ``hi``, and the carry out of the low word is an unsigned
    compare (signed after flipping the sign bit)."""
    shifted = (hi & 0xFFFFFF) << 8  # the low 32 bits of 256 · hi
    low = shifted + lo  # wraps mod 2**32
    carry = ((low ^ _SIGN) < (shifted ^ _SIGN)).astype(jnp.int32)
    return low, (hi >> 24) + (lo >> 31) + carry


def _plane_combine_kernel(y_ref, out_ref, pairs_ref, *, channels):
    """One (filters × samples) block: channel c's outputs are the low
    plane's (row c of ``y_ref``) plus 256 × the high plane's (row
    channels + c), written as interleaved (low, high) word pairs — the
    bytes of little-endian int64.  The interleave goes through the
    transposed scratch: word rows stored at stride 2 along sublanes."""
    for c in range(channels):
        low, high = _plane_words(y_ref[:, c, :], y_ref[:, channels + c, :])
        n = low.shape[1]
        pairs_ref[pl.ds(0, n, stride=2), :] = low.T
        pairs_ref[pl.ds(1, n, stride=2), :] = high.T
        out_ref[:, c, :] = pairs_ref[...].T


@functools.partial(jax.jit, static_argnames=("n_out", "interpret"))
@jax.named_scope("blmac/plane_combine")
def plane_combine(y: jnp.ndarray, n_out: int, interpret: bool) -> jnp.ndarray:
    """(B, 2C, cols) int32 outputs of the low planes (channels 0..C-1)
    and high planes (C..2C-1) of C 16-bit channels → (B, C, 2 · n_pad)
    int32: the first ``n_out`` outputs of each channel, ``256 · hi +
    lo``, as int64 words in little-endian order, in rows padded to
    whole blocks of ``COMBINE_COLS`` outputs.  The host reads the
    words with ``np.asarray(out)[..., :2 * n_out].view(np.int64)``:
    (B, C, n_out), exact.  The padding keeps the rows whole 128-lane
    tiles: for a ragged row a TPU lays the result out column-major, and
    its host copy cannot be read as int64 words."""
    b, two_c, _ = y.shape
    c = two_c // 2
    n_pad = pl.cdiv(n_out, COMBINE_COLS) * COMBINE_COLS
    return pl.pallas_call(
        functools.partial(_plane_combine_kernel, channels=c),
        grid=(pl.cdiv(b, COMBINE_ROWS), pl.cdiv(n_out, COMBINE_COLS)),
        in_specs=[pl.BlockSpec((COMBINE_ROWS, two_c, COMBINE_COLS),
                               lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((COMBINE_ROWS, c, 2 * COMBINE_COLS),
                               lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, c, 2 * n_pad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((2 * COMBINE_COLS, COMBINE_ROWS),
                                   jnp.int32)],
        interpret=interpret,
        name="blmac_plane_combine",
    )(y)


@functools.partial(jax.jit, static_argnames=("n_real",))
@jax.named_scope("blmac/combine")
def _combine_shared(y: jnp.ndarray, combine: jnp.ndarray, n_real: int):
    """Fold shared partial-sum rows (``y[n_real:]``) back into their
    consumers: one (n_real, n_shared) int32 GEMM plus an add.  Exact in
    the mod-2**32 ring on every lane; the combined values are the parent
    program's outputs, which fit int32 by the pack-time §2.1 bound."""
    return y[:n_real] + jnp.tensordot(
        combine, y[n_real:], axes=1, preferred_element_type=jnp.int32
    )


def blmac_fir_dynamic(
    x: jnp.ndarray,
    trits: np.ndarray,  # (n_layers, M_pad) int8, layer-major, {-1,0,1}
    taps: int,
    n_layers: int,
    tile: int = 1024,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Single-filter runtime-trit entry point: a B=1 scheduled bank call.

    The trits stay a runtime operand — the kernel compile cache is keyed
    on the filter's layer-OCCUPANCY schedule, not its pulse list, so
    streaming many distinct filters through this path re-traces only when
    the set of populated layers changes (dense same-width filters share
    one program).  Use `blmac_fir_bank`'s fast path /
    `blmac_fir_specialized` when per-filter compilation is acceptable.
    The trits are wrapped as a content-addressed `BlmacProgram`
    (`repro.compiler.compile_packed`), which asserts the §2.1 int32
    accumulator bound and memoizes the B=1 superlayer schedule.
    """
    trits = np.asarray(trits)
    half = taps // 2
    packed = pack_trits(trits[None, :n_layers, : half + 1])  # (1, L, W)
    prog = compile_packed(packed, taps)  # decodes weights, asserts §2.1
    return blmac_fir_bank(
        x, prog.packed, taps, tile, interpret=interpret,
        fast_path=False, schedule=prog.schedule(bank_tile=1),
    )[0]
