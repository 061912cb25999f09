"""Streaming filter-bank engine: overlap-save BLMAC over B filters × C channels.

`FilterBankEngine` is the serving-side face of the scheduled bank kernel
(`repro.kernels.blmac_fir_bank`): feed it arbitrary-length chunks of a
multi-channel sample stream and it returns, for every filter in the bank,
the output samples that became computable — carrying the ``taps − 1``
sample tail between chunks (classical overlap-save) so consecutive pushes
produce one gapless output stream per (filter, channel) pair.

Mode selection mirrors the hardware trade-off:

  * ``"specialized"`` — per-filter pulse-baked programs from the LRU
    program cache; wins for narrow banks where per-call overhead is
    amortized and the add count is exactly the pulse count.
  * ``"packed"``      — the scheduled bank path: filters sorted into
    occupancy-homogeneous bank tiles at construction time
    (`plan_bank_schedule`), each tile group one `pallas_call` iterating
    ONLY its populated superlayers, packed uint32 trit operands resident
    on device across pushes.
  * ``"auto"``        — the default: `autotune_bank_dispatch` runs both
    candidates (and the scheduled tile/merge grid) through the
    calibrated cost model in `repro.core.costmodel` and keeps the
    winner's plan — no threshold guessing.

Since the one-program refactor the engine is a thin client of
`repro.compiler`: construction compiles (or is handed) ONE
`BlmacProgram` and reads everything off it — the packed trit operands,
the memoized superlayer schedule, the per-filter pulse schedules of
specialized mode, and the §4 cycle predictions.  Two engines built on
the same bank share one program (content-addressed), and an engine built
from a `BlmacProgram.load()`ed file starts without recompiling anything.

Arithmetic contract: int32 throughout the kernels.  The §2.1 bound
(16-bit coeffs × 8-bit samples × ≤255 taps) is asserted ONCE, inside
`compile_bank` — neither `push` nor the kernels re-check it, and
`blmac_fir_dynamic` documents the identical guarantee.  A stream's
sample width comes from its data (`stream_bits`): 8-bit streams return
int32.  A 16-bit stream (int16 input) is split on the host into two
8-bit planes, ``x = 256 · hi + lo``, run as 2C channels of the same
kernels, and combined on the device into exact int64 outputs
(`repro.kernels.blmac_fir.plane_combine`).

Bit-exactness: all modes agree with `repro.filters.fir_bit_layers_batch`
to the last bit on integer inputs (property-tested in `tests/test_bank.py`
and `tests/differential.py`).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp

from ..kernels.runtime import DEFAULT_TILE, span

# Legacy crossover (filters below → specialized) — superseded by the
# autotuner for mode="auto"; kept because external callers used it to
# pre-decide a forced mode.
SPECIALIZE_THRESHOLD = 8

__all__ = ["FilterBankEngine", "PushStats", "SPECIALIZE_THRESHOLD",
           "DEFAULT_TILE"]


@dataclasses.dataclass
class PushStats:
    """Cumulative work counters of an engine's pushes (`push_stats()`).

    ``pushes`` counts calls that returned outputs to the caller (`push`
    and `apply_lanes`); ``outputs_delivered`` the rows × channels ×
    samples they returned; ``outputs_computed`` the padded rows ×
    channels × padded columns the device produced for them, summed over
    tile groups (and shards); ``bytes_read_back`` the bytes copied from
    device to host.  Plain integers, bumped where the work happens.
    """

    pushes: int = 0
    outputs_delivered: int = 0
    outputs_computed: int = 0
    bytes_read_back: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def delivered(self, y: np.ndarray) -> None:
        """Count one call that returned ``y`` to the caller."""
        self.pushes += 1
        self.outputs_delivered += y.size


INT8_MIN, INT8_MAX = -128, 127


def stream_bits(chunk: np.ndarray) -> int:
    """The sample width a chunk states: 16 for int16 data; 8 for any
    other integer data whose values fit int8.  Raises ValueError for
    anything else, so a sample the kernels cannot serve exactly never
    comes back silently wrong."""
    if chunk.dtype == np.int16:
        return 16
    if chunk.dtype.kind not in "iu":
        raise ValueError(f"samples must be integers, got {chunk.dtype}")
    check_8bit(chunk, "an 8-bit stream (int16 data makes a 16-bit one)")
    return 8


def check_8bit(x: np.ndarray, what: str) -> None:
    """Raise ValueError unless every value of ``x`` fits int8."""
    if x.size and (x.min() < INT8_MIN or x.max() > INT8_MAX):
        raise ValueError(
            f"{what} takes 8-bit samples in [{INT8_MIN}, {INT8_MAX}], "
            f"got values in [{x.min()}, {x.max()}]"
        )


def split_planes(buf: np.ndarray) -> np.ndarray:
    """(C, n) 16-bit samples → (2C, n) int32: the low planes
    ``lo = ((x + 128) & 255) − 128`` in [−128, 127], then the high
    planes ``hi = (x − lo) >> 8`` in [−128, 128], so ``x = 256 · hi +
    lo`` and both fold (Eq. 3) within the 8-bit kernels' |u| ≤ 2**8."""
    lo = ((buf + 128) & 255) - 128
    return np.concatenate([lo, (buf - lo) >> 8])


def padded_columns(n: int, taps: int, tile: int) -> int:
    """Output columns the kernels produce for an ``n``-sample buffer
    framed in ``tile``-sample tiles (`frame_signal_batch`)."""
    return -(-(n - taps + 1) // tile) * tile


class FilterBankEngine:
    """Overlap-save streaming application of a quantized FIR filter bank.

    Parameters
    ----------
    qbank : (B, taps) or (taps,) int array, or `repro.compiler.BlmacProgram`
        Quantized odd symmetric (type-I) coefficients, one row per filter
        — compiled via `compile_bank` (content-addressed, so repeated
        constructions of the same bank share one artifact).  Passing a
        prebuilt / `load()`ed program skips compilation entirely.  A
        CSE-`OptimizedProgram` serves its PARENT's filters: the engine
        runs the shared-row layout and folds the combine matrix inside
        `_apply` (``mode="auto"`` lets the autotuner *decline* the
        optimized layout — ``dispatch_plan.cse`` records the verdict).
    channels : int
        Number of independent input channels C (all filtered by every filter).
    tile : int | None
        Output samples per kernel grid step (lane-parallel width).
        ``None`` lets the autotuner pick (falls back to ``DEFAULT_TILE``
        for forced modes).
    mode : {"auto", "packed", "scheduled", "specialized"}
        ``"scheduled"`` is an alias for ``"packed"``.
    bank_tile : int | None
        Filters per bank tile of the scheduled kernel (None = heuristic).
    merge : int | None
        CSD layers fused per superlayer matmul (None = kernel default;
        1 = paper-pure one matmul per bit layer).
    interpret : bool | None
        Pallas interpret override; None = backend default.
    chunk_hint : int
        Expected samples per push, the autotuner's amortization knob
        (streaming chunks are short; batch jobs long).
    compiled : bool | str
        Opt the ``"auto"`` sweep into the compiled execution lanes
        (``True`` = this host's `default_lane`, or a lane name);
        the engine then executes whatever lane the winning plan names.
        Default ``False`` keeps the historic interpret-only behaviour.
    lane : str | None
        Pin the execution lane for a forced (non-auto) packed mode —
        e.g. ``"xla"`` runs the schedule through the fused compiled
        lowering.  ``None`` = the legacy pallas_call + ``interpret``.

    Raises
    ------
    ValueError
        Unknown ``mode``, ``channels < 1``, or non-type-I/overflowing
        coefficients (via `compile_bank`'s §2.1 bound check).

    Examples
    --------
    >>> import numpy as np
    >>> from repro.filters import FilterBankEngine
    >>> bank = np.zeros((4, 15), np.int64)
    >>> bank[:, 7] = [64, 96, 160, 224]          # centre-tap scalers
    >>> eng = FilterBankEngine(bank, channels=1, interpret=True)
    >>> x = np.arange(40, dtype=np.int32)[None, :]
    >>> y = eng.push(x)                          # (B, C, n_out)
    >>> y.shape
    (4, 1, 26)
    >>> bool((y[1] == 96 * np.arange(7, 33)).all())
    True
    """

    def __init__(
        self,
        qbank: np.ndarray,
        channels: int = 1,
        tile: int | None = None,
        mode: str = "auto",
        bank_tile: int | None = None,
        interpret: bool | None = None,
        merge: int | None = None,
        chunk_hint: int = 2048,
        compiled: "bool | str" = False,
        lane: str | None = None,
    ):
        from ..compiler import BlmacProgram, MERGE_DEFAULT, compile_bank
        from ..kernels.runtime import autotune_bank_dispatch

        if isinstance(qbank, BlmacProgram):
            program = qbank
        else:
            # CSD encoding, trit packing and the §2.1 int32 bound all
            # happen in here — exactly once per distinct bank content,
            # however many engines are built.  The int64 cast preserves
            # this constructor's historical contract (float input is
            # truncated, not quantized — pass the bank through
            # `compile_bank` yourself for §3.2 po2 quantization).
            program = compile_bank(
                np.atleast_2d(np.asarray(qbank, np.int64))
            )
        if channels < 1:
            raise ValueError("channels must be >= 1")
        if mode == "scheduled":
            mode = "packed"
        if mode not in ("auto", "packed", "specialized"):
            raise ValueError(f"unknown mode {mode!r}")
        self.taps = program.taps
        self.channels = int(channels)
        self.interpret = interpret
        self.dispatch_plan = None
        self.lane = lane
        schedule = None
        if mode == "auto":
            self.dispatch_plan, schedule = autotune_bank_dispatch(
                program, channels=self.channels, tile=tile,
                chunk_hint=chunk_hint, interpret=interpret,
                compiled=compiled,
            )
            if self.dispatch_plan.cse == "declined":
                # a CSE-optimized program whose shared-row layout the
                # cost model rejects here: the plan (and schedule) are
                # the PARENT's — execute it, bit-identical outputs
                program = program.parent
            mode = (
                "specialized"
                if self.dispatch_plan.mode == "specialized"
                else "packed"
            )
            if self.lane is None and self.dispatch_plan.lane != "interpret":
                self.lane = self.dispatch_plan.lane
            if tile is None:
                tile = self.dispatch_plan.tile
            if bank_tile is None and schedule is not None:
                bank_tile = schedule.tile_size
            if merge is None and schedule is not None:
                merge = schedule.merge
        self.program = program
        # external face: a CSE-optimized program still SERVES the
        # parent's filters — qbank/n_filters describe the combined
        # outputs, the augmented shared-row layout stays internal
        self._combine = program.combine
        self.qbank = (
            program.qbank if program.combine is None
            else program.effective_qbank()
        )
        self.n_filters = program.out_filters
        self.tile = int(tile) if tile is not None else DEFAULT_TILE
        self.mode = mode
        self.merge = merge if merge is not None else MERGE_DEFAULT
        if mode == "packed":
            # the program memoizes one plan per (bank_tile, merge) — the
            # autotuned schedule and an explicit-override re-plan resolve
            # through the same memo; upload each tile group's packed
            # operand ONCE so push() feeds device-resident operands
            # instead of re-staging the bank every chunk
            if (
                schedule is None
                or (bank_tile is not None and bank_tile != schedule.tile_size)
                or schedule.merge != self.merge
            ):
                schedule = program.schedule(bank_tile, self.merge)
            self.bank_schedule = schedule
            self.bank_tile = schedule.tile_size
            self._group_ops = [
                jnp.asarray(g.packed.view(np.int32)) if g.sel_layers else None
                for g in schedule.groups
            ]
            self._schedules = None
        else:
            self.bank_schedule = None
            self.bank_tile = bank_tile
            self._group_ops = None
            self._schedules = program.pulse_schedules()
        # overlap-save state: the last taps-1 samples of every channel,
        # and the stream's sample width (None until the first push)
        self._tail = np.zeros((channels, 0), np.int32)
        self.samples_in = 0
        self.samples_out = 0
        self.sample_bits = None
        self.stats = PushStats()
        self.wide_pushes = 0

    # -- cost model ---------------------------------------------------------

    def predicted_machine_cycles(self, spec=None) -> np.ndarray:
        """(B,) clock cycles per output each filter would cost on the §4
        FPGA dot-product machine (one cycle per RLE code + overhead).

        ``spec`` is a `repro.core.MachineSpec` (default: the paper's
        127-tap spec parameters applied to this bank's tap count).  Reads
        `BlmacProgram.machine_cycles` — derived from the program's own
        CSD digits and memoized per spec ON THE PROGRAM, so every engine,
        benchmark and test sharing this bank shares one computation.
        Agrees exactly with both simulators — `FirBlmacVMachine` asserts
        this in `tests/differential.py`.
        """
        return self.program.machine_cycles(spec)

    def predicted_mean_cycles(self, spec=None) -> float:
        """Bank-average §4 machine cycles per output sample."""
        return float(self.predicted_machine_cycles(spec).mean())

    # -- streaming API ------------------------------------------------------

    def push(self, chunk) -> np.ndarray:
        """Feed (C, n) samples (or (n,) when C == 1); returns the newly
        computable outputs (B, C, n_out) — n_out may be 0 while the
        engine is still priming its taps−1 history.

        The first push fixes the stream's sample width (`stream_bits`):
        int16 data makes a 16-bit stream, whose outputs are exact int64;
        any other integer data whose values fit int8 makes an 8-bit
        stream, whose outputs are int32.  A later push of another width,
        or values outside the width, raise ValueError; `reset` forgets
        the width."""
        chunk = np.asarray(chunk)
        if chunk.ndim == 1:
            chunk = chunk[None, :]
        if chunk.shape[0] != self.channels:
            raise ValueError(
                f"expected {self.channels} channels, got {chunk.shape[0]}"
            )
        bits = stream_bits(chunk)
        if self.sample_bits is not None and bits != self.sample_bits:
            raise ValueError(
                f"this stream is {self.sample_bits}-bit, the chunk is "
                f"{bits}-bit: reset() starts a new stream"
            )
        self.sample_bits = bits
        with span("push", chunk=self.stats.pushes):
            self.samples_in += chunk.shape[1]
            with span("stage"):
                buf = np.concatenate(
                    [self._tail, chunk.astype(np.int32)], axis=1
                )
            n = buf.shape[1]
            if n < self.taps:  # still priming
                self._tail = buf
                y = np.zeros((self.n_filters, self.channels, 0),
                             np.int64 if bits == 16 else np.int32)
            else:
                self._tail = (
                    buf[:, n - (self.taps - 1):] if self.taps > 1
                    else buf[:, :0]
                )
                # an 8-bit push calls `_apply` as it always has
                y = (self._apply(buf, wide=True) if bits == 16
                     else self._apply(buf))
            self.samples_out += y.shape[2]
            self.stats.delivered(y)
            self.wide_pushes += bits == 16
            return y

    def __call__(self, chunk) -> np.ndarray:
        return self.push(chunk)

    def reset(self) -> None:
        """Drop all buffered history and the sample width (start a new
        stream)."""
        self._tail = np.zeros((self.channels, 0), np.int32)
        self.samples_in = 0
        self.samples_out = 0
        self.sample_bits = None

    def push_stats(self) -> dict:
        """JSON-able cumulative work counters (see `PushStats`): what the
        pushes delivered against what the device computed and the host
        read back — the engine's analogue of a server's ``serve_stats()``
        — plus ``wide_pushes``, the pushes of a 16-bit stream."""
        return dict(self.stats.as_dict(), wide_pushes=self.wide_pushes)

    @property
    def pending(self) -> int:
        """Samples buffered but not yet old enough to finish a window."""
        return self._tail.shape[1]

    # -- tail snapshot / restore (content-addressed stream state) -----------

    def snapshot_tail(self, session: str = ""):
        """Freeze the overlap-save stream state as a
        `repro.compiler.TailSnapshot` keyed to this engine's program
        digest — `save()`-able next to `BlmacProgram.save()` so a
        restarted serving process resumes the stream bit-exactly, and
        the replay point the sharded engine's fault recovery builds on.
        ``session`` stamps an optional stream identity into the snapshot
        (the multi-tenant server labels parked sessions this way)."""
        from ..compiler.state import TailSnapshot

        return TailSnapshot(
            program_key=self.program.key, channels=self.channels,
            samples_in=self.samples_in, samples_out=self.samples_out,
            tail=self._tail.copy(), session=str(session),
            sample_bits=self.sample_bits,
        )

    def restore_tail(self, snapshot) -> None:
        """Adopt a `TailSnapshot` captured on THIS program (validated by
        content key — restoring another bank's stream is a loud error,
        never a silently wrong output).  The stream keeps the
        snapshot's sample width."""
        if snapshot.program_key != self.program.key:
            raise ValueError(
                f"snapshot belongs to program {snapshot.program_key[:12]}…, "
                f"this engine runs {self.program.key[:12]}…"
            )
        if int(snapshot.channels) != self.channels:
            raise ValueError(
                f"snapshot has {snapshot.channels} channels, "
                f"engine has {self.channels}"
            )
        self._tail = np.asarray(snapshot.tail, np.int32).copy()
        self.samples_in = int(snapshot.samples_in)
        self.samples_out = int(snapshot.samples_out)
        self.sample_bits = snapshot.sample_bits

    # -- one-shot application ----------------------------------------------

    def apply_lanes(self, buf) -> np.ndarray:
        """Stateless one-shot bank application over ``channels`` lanes.

        ``buf`` is (C, n) 8-bit samples (values in int8; anything else
        raises ValueError) with ``n >= taps``; returns the full int32
        (B, C, n − taps + 1) output without touching the engine's
        overlap-save tail or stream counters.  This is the batched
        multi-select dispatch surface the session server builds on: it
        packs many tenants' ``tail + queued`` buffers into the C lanes of
        ONE shared engine, fires a single dispatch, and slices each
        tenant's `program.select()` rows / valid sample range out of the
        result — bit-exactness per lane follows from `push` and
        `apply_lanes` sharing the same `_apply` path.
        """
        buf = np.asarray(buf)
        check_8bit(buf, "apply_lanes")
        buf = buf.astype(np.int32, copy=False)
        if buf.ndim != 2 or buf.shape[0] != self.channels:
            raise ValueError(
                f"expected ({self.channels}, n) lane buffer, "
                f"got shape {buf.shape}"
            )
        if buf.shape[1] < self.taps:
            raise ValueError(
                f"lane buffer has {buf.shape[1]} samples, "
                f"need >= taps ({self.taps})"
            )
        with span("push", chunk=self.stats.pushes):
            y = self._apply(buf)
            self.stats.delivered(y)
            return y

    def _apply(self, buf: np.ndarray, wide: bool = False) -> np.ndarray:
        """The bank's outputs on the (C, n) int32 buffer: int32, or for
        ``wide`` (16-bit samples) int64 through the two 8-bit planes."""
        from ..kernels.blmac_fir import (bank_schedule_apply, blmac_fir_specialized,
                                         frame_signal_batch, plane_combine)
        from ..kernels.runtime import resolve_interpret

        n = buf.shape[1]
        n_out = n - self.taps + 1
        # Quantize the jit shape: pad the buffer to a tile multiple so a
        # stream of ragged chunk sizes hits a handful of compile-cache
        # entries instead of retracing every push; windows that reach
        # into the padding are dropped below.
        n_pad = -(-n // self.tile) * self.tile
        cols = padded_columns(n_pad, self.taps, self.tile)
        with span("stage"):
            if wide:
                with span("planes"):
                    buf = split_planes(buf)
            if n_pad != n:
                buf = np.pad(buf, ((0, 0), (0, n_pad - n)))
            x = jnp.asarray(buf, jnp.int32)
        lanes = buf.shape[0]  # channels, or the 2C planes of a wide push
        if self.mode == "packed":
            interpret = resolve_interpret(self.interpret)
            with span("dispatch"):
                frames, _ = frame_signal_batch(x, self.taps, self.tile)
                y = bank_schedule_apply(
                    frames,
                    self.bank_schedule,
                    self.taps,
                    self.tile,
                    interpret,
                    device_groups=self._group_ops,
                    lane=self.lane,
                    combine=self._combine,
                    n_real=(self.n_filters if self._combine is not None
                            else None),
                )  # (B, lanes, n_tiles * tile), caller order restored + combined
                if wide:
                    with span("plane_combine"):
                        y = plane_combine(y, n_out, interpret)
                else:
                    y = y[:, :, :n_out]
            rows = sum(g.packed.shape[0] for g in self.bank_schedule.groups)
            self.stats.outputs_computed += rows * lanes * cols
            with span("wait"):
                y.block_until_ready()
            with span("readback"):
                out = np.asarray(y)
            self.stats.bytes_read_back += out.nbytes
            # a wide push's words, less the padding of their last block
            return out[:, :, :2 * n_out].view(np.int64) if wide else out
        out = np.empty((len(self._schedules), lanes, n_out), np.int32)
        # each filter × channel is read back before the next dispatch,
        # so every readback sits inside the loop's dispatch span
        with span("dispatch"):
            for b, pulses in enumerate(self._schedules):
                for c in range(lanes):
                    yc = blmac_fir_specialized(
                        x[c], pulses, self.taps, self.tile, self.interpret
                    )
                    with span("readback"):
                        out[b, c] = np.asarray(yc)[:n_out]
                    self.stats.bytes_read_back += yc.nbytes
        self.stats.outputs_computed += out.shape[0] * lanes * cols
        if self._combine is not None:
            from ..compiler.lowering import _host_combine_i32

            out = _host_combine_i32(out, self._combine, self.n_filters)
        if wide:
            c = self.channels
            return (out[:, c:].astype(np.int64) << 8) + out[:, :c]
        return out
