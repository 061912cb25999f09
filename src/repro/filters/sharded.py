"""Device-sharded filter-bank serving: BLMAC banks over a (bank, data) mesh.

The paper scales throughput by replicating 110-LUT BLMAC machines; this
module scales the jax_pallas reproduction the same way across XLA devices.
`ShardedFilterBankEngine` partitions a (B filters × C channels) bank over
a two-axis device mesh:

  * **bank axis** — filters, assigned by
    `repro.distributed.sharding.partition_bank`: occupancy-sorted so each
    shard's `plan_bank_schedule` sees a homogeneous run (short superlayer
    programs), cost-balanced so one dense shard never straggles the mesh.
    Every shard compiles its OWN schedule and runs as its own program on
    its own mesh row — replicated machines, not one padded SPMD body.
  * **data axis** — channels when ``C`` divides the axis (no
    communication), otherwise signal time chunks with an overlap-save
    halo exchange (`repro.distributed.collectives.halo_exchange_left`,
    one `ppermute` of ``taps − 1`` samples per push) inside `shard_map`.

The plan is `repro.distributed.sharding.sharded_plan`: one bank shard
per mesh row (or the caller's count), the data axis only when the caller
names a ``data_mode``, and `repro.compiler.bank_plan` on every shard.

Output reassembly is gather-free: per-shard outputs land on their own
devices, every shard's host copy starts at dispatch, and the host
reads the shards in turn, handing each block to a worker thread that
writes it once into its caller rows (`BankPartition.assign`) of one new
output while the next shard is read — no cross-device collective
touches the results, and no bank-sized intermediate is built on the
host.

**Fault tolerance** (see `repro.distributed.faultbank` for the shared
taxonomy/injector/watchdog): every `push_async` captures a
`repro.compiler.TailSnapshot` — the pure-host overlap-save state that
makes the chunk deterministically replayable on ANY backend of the same
program.  When a shard is detected dead (a raised `ShardLost`, or the
`ShardHealth` watchdog timeout), the engine removes that mesh row,
re-partitions the bank over the survivors via the program's memoized
`partition`/`select` slices (one shard per surviving mesh row, or the
caller's count), and replays every
in-flight chunk from its snapshot — so the resumed stream is bit-exact
with an uninterrupted run.  When the mesh degrades to a single device
the engine falls back to the plain `FilterBankEngine` lowering of the
SAME `BlmacProgram`.  Corrupted shard blocks (caught by the optional
boundary integrity probe) are replayed in place and escalate to loss if
they persist; transient errors re-arm the chunk and propagate for
`repro.serving.AsyncBankServer`'s bounded retry/backoff.  Counters for
all of it surface through ``fault_stats()``.

Bit-exactness: every mesh shape agrees with
`repro.filters.fir_bit_layers_batch` to the last bit on integer inputs
(the fifth leg of `tests/differential.py`, including its chaos grid).
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor, wait

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.collectives import halo_exchange_left
from ..distributed.faultbank import (FaultStats, PendingInvalidated,
                                     ShardCorruption, ShardError, ShardHealth,
                                     ShardLost, ShardTimeout,
                                     TransientShardError)
from ..distributed.sharding import (DATA_AXIS, BankPartition,
                                    ShardedBankPlan, bank_mesh,
                                    mesh_bank_shape, sharded_plan)
from ..kernels.runtime import span
from .bank import PushStats, check_8bit, padded_columns

# what a push of wider samples is told (`check_8bit`)
WIDE_REFUSAL = ("ShardedFilterBankEngine (16-bit streams go through "
                "FilterBankEngine, as int16 data)")

__all__ = ["ShardedFilterBankEngine", "PendingChunk"]


class PendingChunk:
    """In-flight outputs of one `push_async`: per-shard device arrays plus
    the reassembly recipe (the partition's caller rows per shard) and
    the chunk's replay material (tail snapshot + raw samples).
    `result()` materializes on the host — each shard's block is read off
    its own devices and scattered once, on a worker thread, into its
    caller rows of one new output while the next shard is read (no
    device-side gather, no concatenated intermediate) — and is
    where faults are detected and recovered: a lost shard triggers the
    engine's re-partition + replay, a corrupted block is replayed in
    place, a transient error re-arms the chunk and propagates for the
    server's retry loop."""

    def __init__(self, engine, shard_outs, assign, n_out, offsets,
                 n_filters, channels, snapshot=None, chunk=None,
                 chunk_idx=0):
        self._engine = engine
        self._shard_outs = shard_outs
        self._assign = assign
        self._offsets = offsets
        self.n_out = int(n_out)
        self._shape = (n_filters, channels)
        self._resolved = None
        self._invalid = False
        self.snapshot = snapshot
        self.chunk = chunk
        self.chunk_idx = int(chunk_idx)
        self._heals = 0  # corruption replays consumed on this chunk

    def _rearm(self, shard_outs, offsets, assign) -> None:
        """Swap in a replay's fresh dispatch (possibly from a different
        partition after a recovery re-partition)."""
        self._shard_outs = shard_outs
        self._offsets = offsets
        self._assign = assign

    def invalidate(self) -> None:
        """Mark the chunk unusable (engine reset / terminal failure):
        `result()` will raise `PendingInvalidated`, and the engine stops
        tracking it for replay."""
        self._invalid = True
        self._shard_outs = None
        self.snapshot = None
        self.chunk = None
        eng = self._engine
        if eng is not None and self in eng._inflight:
            eng._inflight.remove(self)

    def result(self) -> np.ndarray:
        """Block until the chunk's outputs are ready → int32 (B, C, n_out).

        Raises `PendingInvalidated` if the engine's stream state moved
        on (``reset()`` while this push was outstanding), re-raises
        `TransientShardError` after re-arming the chunk (the server
        retries), and raises `ShardLost` only when recovery found no
        surviving devices."""
        if self._resolved is not None:
            return self._resolved
        if self._invalid:
            raise PendingInvalidated(
                "engine stream state moved on before this chunk resolved "
                "(reset() or a terminal failure) — its shard outputs are "
                "stale and will not be reassembled"
            )
        b, c = self._shape
        eng = self._engine
        if self.n_out <= 0:
            self._resolved = np.zeros((b, c, 0), np.int32)
            eng.stats.delivered(self._resolved)
            return self._resolved
        while True:
            try:
                out = eng._materialize(self)
                break
            except ShardCorruption as e:
                eng.fault.detections += 1
                eng.fault.corruptions += 1
                self._heals += 1
                if self._heals > eng.max_heals:
                    # persistent corruption == a lying shard: treat as lost
                    eng._recover(ShardLost(
                        e.shard,
                        f"shard {e.shard}: corruption persisted after "
                        f"{eng.max_heals} replays",
                    ))
                else:
                    eng._replay_one(self)
            except TransientShardError:
                eng.fault.detections += 1
                eng.fault.transients += 1
                eng._replay_one(self)  # re-arm so the next attempt is fresh
                raise
            except ShardLost as e:
                eng._recover(e)  # re-partitions + replays, or re-raises
        self._resolved = out
        eng.stats.delivered(out)
        self._shard_outs = None  # free device references + replay material
        self.snapshot = None
        self.chunk = None
        if eng is not None and self in eng._inflight:
            eng._inflight.remove(self)
        return self._resolved


class ShardedFilterBankEngine:
    """Overlap-save streaming FIR bank sharded over a (bank, data) mesh.

    Parameters
    ----------
    qbank : (B, taps) or (taps,) int array, or `repro.compiler.BlmacProgram`
        Quantized odd symmetric (type-I) coefficients, one row per filter
        — compiled once via `compile_bank` (content-addressed); passing a
        prebuilt / `load()`ed program warm-starts without recompiling.
        Shard subprograms are the program's memoized `select()` slices.
    channels : int
        Independent input channels C (all filtered by every filter).
    mesh : jax.sharding.Mesh | None
        A mesh with a ``bank`` axis and optionally a ``data`` axis
        (see `repro.distributed.sharding.bank_mesh`).  ``None`` builds a
        (n_devices, 1) mesh over every visible device.  A 1×1 mesh is
        valid: one shard on one device.
    n_bank_shards : int | None
        The filter-shard count (clamped to the mesh's bank axis and the
        bank size); ``None`` = one shard per bank row.
    data_mode : {"none", "channels", "time"} | None
        How the data axis is used; ``None`` = ``"none"``, the axis idle.
    tile, merge, chunk_hint, interpret
        As `repro.filters.FilterBankEngine` (``chunk_hint`` is ignored);
        ``tile`` and ``merge`` apply to every shard.
    fault_injector : repro.distributed.faultbank.FaultInjector | None
        Deterministic chaos hooks (tests/benchmarks only): consulted on
        every shard dispatch and materialize.
    shard_timeout : float | None
        Hard per-shard materialize deadline in seconds; expiry is
        escalated to `ShardTimeout` → shard loss.  ``None`` disables
        the watchdog timeout (heartbeats are still recorded).
    integrity_check : bool
        Recompute boundary output positions of every shard block on the
        host and raise `ShardCorruption` on mismatch (cost: a handful
        of taps-length dot products per shard per push).
    straggler_factor : float
        `ShardHealth` slow-shard multiple over the running median.
    """

    def __init__(
        self,
        qbank: np.ndarray,
        channels: int = 1,
        mesh: Mesh | None = None,
        n_bank_shards: int | None = None,
        data_mode: str | None = None,
        tile: int | None = None,
        merge: int | None = None,
        chunk_hint: int = 2048,
        interpret: bool | None = None,
        fault_injector=None,
        shard_timeout: float | None = None,
        integrity_check: bool = False,
        straggler_factor: float = 3.0,
    ):
        from ..compiler import BlmacProgram, compile_bank
        from ..kernels.runtime import resolve_interpret

        if isinstance(qbank, BlmacProgram):
            program = qbank
        else:
            # CSD, packing and the §2.1 int32 bound — once, content-
            # addressed, shared with every other client.  int64 cast as
            # in `FilterBankEngine`: float input keeps its historical
            # truncation semantics; quantize via `compile_bank` directly.
            program = compile_bank(
                np.atleast_2d(np.asarray(qbank, np.int64))
            )
        if channels < 1:
            raise ValueError("channels must be >= 1")
        if mesh is None:
            mesh = bank_mesh()
        self.program = program
        self.qbank = program.qbank
        self.n_filters = program.n_filters
        self.taps = program.taps
        self.channels = int(channels)
        self.interpret = resolve_interpret(interpret)
        self._halo = self.taps - 1
        # construction preferences, reused verbatim by every recovery
        # re-configure so a rebuilt mesh honors the caller's pins
        self._force_bank = n_bank_shards
        self._force_data = data_mode
        self._tile_arg = tile
        self._merge_arg = merge
        self._interpret_arg = interpret
        self.injector = fault_injector
        self.shard_timeout = shard_timeout
        self.integrity_check = bool(integrity_check)
        self._straggler_factor = float(straggler_factor)
        self.max_heals = 2  # corruption replays per chunk before loss
        self.fault = FaultStats()
        self._plain = None  # set when degraded to the unsharded engine
        self._inflight: list[PendingChunk] = []
        self._chunk_idx = 0
        self.stats = PushStats()
        self._reads = {"prefetched_reads": 0, "overlapped_scatters": 0}
        self._scatter_pool = None  # created at the first multi-shard read
        self._configure(mesh)
        # overlap-save state: the last taps-1 samples of every channel
        self._tail = np.zeros((channels, 0), np.int32)
        self.samples_in = 0
        self.samples_out = 0

    # -- construction helpers ----------------------------------------------

    def _configure(self, mesh: Mesh) -> None:
        """(Re)build the mesh-dependent half of the engine: plan,
        partition, per-shard dispatch closures, chunk quantum and the
        `ShardHealth` watchdog.  Called at construction and again by
        `_recover` with the surviving-device mesh."""
        n_bank, n_data = mesh_bank_shape(mesh)
        if n_bank * n_data != mesh.size:
            raise ValueError(
                f"mesh must be ({'bank'}, {'data'})-shaped, got {mesh.shape}"
            )
        self.plan, self.partition, schedules = sharded_plan(
            self.program, (n_bank, n_data), channels=self.channels,
            n_bank_shards=self._force_bank, data_mode=self._force_data,
            tile=self._tile_arg, merge=self._merge_arg,
        )
        self.mesh = mesh
        self.n_bank_shards = self.plan.n_bank_shards
        self.n_data = self.plan.n_data
        self.data_mode = self.plan.data_mode
        # chunk lengths are quantized to a multiple of every shard's tile
        # so ragged pushes hit a handful of jit-cache entries; only TIME
        # sharding additionally needs the ×n_data factor (each device's
        # slice must itself be tile-aligned and cover the halo it sends
        # rightwards) — channel sharding splits C, not time
        self._quantum = max(p.tile for p in self.plan.shard_plans)
        if self.data_mode == "time":
            self._quantum *= self.n_data
            while self._quantum // self.n_data < self._halo:
                self._quantum *= 2

        devices = np.asarray(mesh.devices).reshape(n_bank, n_data)
        self._device_rows = [devices[r] for r in range(n_bank)]
        self._shards = []
        # rows each shard's kernels produce, tile-group padding included
        self._shard_rows = [
            sum(g.packed.shape[0] for g in sched.groups)
            for sched in schedules
        ]
        for s, plan in enumerate(self.plan.shard_plans):
            self._shards.append(
                self._build_shard(plan, schedules[s], devices[s % n_bank])
            )
        self.health = ShardHealth(
            len(self._shards), timeout=self.shard_timeout,
            straggler_factor=self._straggler_factor,
        )

    def _configure_degraded(self, device) -> None:
        """Last-resort recovery target: one surviving device.  The SAME
        `BlmacProgram` is lowered through the plain single-device
        `FilterBankEngine`, and the shard list collapses to one
        host-side closure.  ``device`` is the survivor: the engine's
        operands and every dispatch are placed on it, never on the
        default device (which may be the one that was lost)."""
        from .bank import FilterBankEngine

        with jax.default_device(device):
            plain = FilterBankEngine(
                self.program, channels=self.channels, tile=self._tile_arg,
                merge=self._merge_arg, interpret=self._interpret_arg,
            )
        self._plain = plain
        self.plan = ShardedBankPlan(1, 1, "none", (plain.dispatch_plan,))
        self.n_bank_shards, self.n_data, self.data_mode = 1, 1, "none"
        b = self.n_filters
        self.partition = BankPartition(
            assign=(np.arange(b),), inv=np.arange(b),
            cost=np.asarray([float(self.program.filter_costs.sum())]),
        )
        self._quantum = plain.tile
        self._device_rows = None
        self.mesh = None

        def run_plain(buf, n):
            with jax.default_device(device):
                return plain._apply(buf[:, :n])

        self._shards = [(run_plain, 0)]
        self._shard_rows = [0]  # the plain engine counts its own work
        self.health = ShardHealth(
            1, timeout=self.shard_timeout,
            straggler_factor=self._straggler_factor,
        )
        self.fault.degraded_since = time.perf_counter()

    def _build_shard(self, plan, schedule, dev_row):
        """One bank shard = (dispatch closure, device row).  Returns a
        callable ``fn(buf_np, n) -> device output`` where ``buf_np`` is
        the padded (C, n_pad) int32 buffer and ``n`` the valid length."""
        fn = self._make_scheduled_fn(schedule, plan.tile)
        if self.n_data == 1:
            dev = dev_row[0]
            ops = tuple(
                jax.device_put(jnp.asarray(g.packed.view(np.int32)), dev)
                for g in schedule.groups if g.sel_layers
            )

            def run_single(buf, n):
                x = jax.device_put(jnp.asarray(buf, jnp.int32), dev)
                return fn(x, *ops)

            return run_single, 0

        row_mesh = Mesh(dev_row, (DATA_AXIS,))
        repl = NamedSharding(row_mesh, P())
        ops = tuple(
            jax.device_put(jnp.asarray(g.packed.view(np.int32)), repl)
            for g in schedule.groups if g.sel_layers
        )
        if self.data_mode == "channels":
            in_specs = (P(DATA_AXIS, None),) + (P(),) * len(ops)
            out_specs = P(None, DATA_AXIS, None)

            def body(buf, *op):
                return fn(buf, *op)

            offset = 0
        else:  # time: halo exchange, then each slice is self-contained
            in_specs = (P(None, DATA_AXIS),) + (P(),) * len(ops)
            out_specs = P(None, None, DATA_AXIS)
            n_data, halo = self.n_data, self._halo

            def body(buf, *op):
                chunk_local = buf.shape[-1]
                xl = halo_exchange_left(buf, DATA_AXIS, n_data, halo)
                return fn(xl, *op)[:, :, :chunk_local]

            # shard 0's halo is ppermute zero-fill: the first taps-1
            # concatenated outputs are warm-up, trimmed at reassembly
            offset = self._halo

        mapped = jax.shard_map(
            body, mesh=row_mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        jitted = jax.jit(mapped)
        x_sharding = NamedSharding(row_mesh, in_specs[0])

        def run_mapped(buf, n):
            x = jax.device_put(jnp.asarray(buf, jnp.int32), x_sharding)
            return jitted(x, *ops)

        return run_mapped, offset

    def _make_scheduled_fn(self, schedule, tile):
        """Jitted scheduled-bank program for one shard: frame, then the
        shared `bank_schedule_apply` group loop (zeros for empty groups,
        one `_bank_call` per tile group, shard-order restoration).  The
        schedule is static (closed over); jit caches per input shape ×
        device.  ``ops`` carries only the NON-empty groups' operands
        (shard_map in_specs must match real arrays), re-slotted to the
        full per-group list here."""
        from ..kernels.blmac_fir import bank_schedule_apply, frame_signal_batch

        taps, interpret = self.taps, self.interpret
        has_layers = [bool(g.sel_layers) for g in schedule.groups]

        @jax.jit
        def fn(x, *ops):
            frames, _ = frame_signal_batch(x, taps, tile)
            it = iter(ops)
            full = [next(it) if h else None for h in has_layers]
            return bank_schedule_apply(
                frames, schedule, taps, tile, interpret,
                device_groups=full,
            )

        return fn

    # -- streaming API ------------------------------------------------------

    def push_async(self, chunk) -> PendingChunk:
        """Feed (C, n) samples (or (n,) when C == 1); dispatches every
        bank shard onto its mesh row and returns WITHOUT blocking on the
        device work — the double-buffered serving path overlaps the next
        chunk's host framing with this chunk's kernels.  The returned
        `PendingChunk` carries a `TailSnapshot` of the pre-push stream
        state, so the chunk can be replayed bit-exactly through a
        recovered mesh if a shard dies before it resolves."""
        chunk = np.asarray(chunk)
        if chunk.ndim == 1:
            chunk = chunk[None, :]
        if chunk.shape[0] != self.channels:
            raise ValueError(
                f"expected {self.channels} channels, got {chunk.shape[0]}"
            )
        check_8bit(chunk, WIDE_REFUSAL)
        idx = self._chunk_idx
        self._chunk_idx += 1
        with span("stage", chunk=idx):
            snap = self.snapshot_tail()
            chunk_i = chunk.astype(np.int32)
            buf = np.concatenate([self._tail, chunk_i], axis=1)
        self.samples_in += chunk.shape[1]
        n = buf.shape[1]
        if n < self.taps:  # still priming
            self._tail = buf
            return PendingChunk(
                self, [], self.partition.assign, 0, [],
                self.n_filters, self.channels,
                snapshot=snap, chunk=chunk_i, chunk_idx=idx,
            )
        self._tail = (
            buf[:, n - self._halo:] if self._halo else buf[:, :0]
        )
        n_out = n - self.taps + 1
        outs, offsets = self._dispatch_shards(buf, n, idx)
        self.samples_out += n_out
        p = PendingChunk(
            self, outs, self.partition.assign, n_out, offsets,
            self.n_filters, self.channels,
            snapshot=snap, chunk=chunk_i, chunk_idx=idx,
        )
        self._inflight.append(p)
        return p

    def _dispatch_shards(self, buf, n, chunk_idx):
        """Pad ``buf`` to the chunk quantum and dispatch every shard.
        A dispatch-time `ShardError` (injected or real) is STORED in the
        shard's output slot instead of raised — detection and recovery
        happen at `result()`, preserving push_async's non-blocking
        contract."""
        n_pad = -(-n // self._quantum) * self._quantum
        if n_pad != buf.shape[1]:
            with span("stage", chunk=chunk_idx):
                buf = np.pad(buf, ((0, 0), (0, n_pad - buf.shape[1])))
        outs, offsets = [], []
        for s, (fn, offset) in enumerate(self._shards):
            try:
                with span("shard_dispatch", chunk=chunk_idx, shard=s):
                    if self.injector is not None:
                        self.injector.on_dispatch(s, chunk_idx)
                    y = fn(buf, n)
                    if isinstance(y, jax.Array):  # start the host copy now
                        y.copy_to_host_async()
                self.stats.outputs_computed += (
                    self._shard_rows[s] * self.channels
                    * self._shard_columns(s, n_pad)
                )
            except ShardError as e:
                if e.shard is None:
                    e.shard = s
                y = e
            outs.append(y)
            offsets.append(offset)
        return outs, offsets

    def _shard_columns(self, s: int, n_pad: int) -> int:
        """Output columns shard ``s`` produces for an ``n_pad``-sample
        buffer: time sharding frames each data slice with its halo, which
        tiles the slice exactly."""
        if self.data_mode == "time":
            return n_pad
        return padded_columns(n_pad, self.taps, self.plan.shard_plans[s].tile)

    def push(self, chunk) -> np.ndarray:
        """Synchronous `push_async` → int32 (B, C, n_out)."""
        with span("push", chunk=self._chunk_idx):
            return self.push_async(chunk).result()

    def __call__(self, chunk) -> np.ndarray:
        return self.push(chunk)

    def apply_lanes(self, buf) -> np.ndarray:
        """Stateless one-shot bank application over ``channels`` lanes —
        the sharded twin of `FilterBankEngine.apply_lanes`, which is the
        dispatch surface `repro.serving.BankSessionServer` batches
        tenants through.  ``buf`` is (C, n) int samples with
        ``n >= taps``; returns the full (B, C, n − taps + 1) output
        without touching the engine's overlap-save tail or stream
        counters.

        The dispatch goes through the SAME fault path as `push`: each
        lane buffer rides a `PendingChunk` whose replay material is the
        buffer itself (an empty tail snapshot — the call is stateless),
        so a shard lost / timed out / corrupted mid-call triggers the
        normal re-partition + bit-exact replay and the call returns the
        recovered result.  A `TransientShardError` propagates to the
        caller (the session server's bounded retry), after invalidating
        the pending so no stale dispatch leaks into ``_inflight``."""
        from ..compiler.state import TailSnapshot

        buf = np.asarray(buf)
        check_8bit(buf, WIDE_REFUSAL)
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
        idx = self._chunk_idx
        self._chunk_idx += 1
        # empty-tail snapshot + the raw buffer == complete replay
        # material: `_replay_one` rebuilds concat(tail, chunk) == buf
        snap = TailSnapshot(
            program_key=self.program.key, channels=self.channels,
            samples_in=0, samples_out=0,
            tail=np.zeros((self.channels, 0), np.int32),
        )
        n = buf.shape[1]
        n_out = n - self.taps + 1
        with span("push", chunk=idx):
            outs, offsets = self._dispatch_shards(buf, n, idx)
            p = PendingChunk(
                self, outs, self.partition.assign, n_out, offsets,
                self.n_filters, self.channels,
                snapshot=snap, chunk=buf, chunk_idx=idx,
            )
            self._inflight.append(p)
            try:
                return p.result()
            except Exception:
                p.invalidate()
                raise

    def reset(self) -> None:
        """Drop all buffered history (start a new stream).  Outstanding
        `PendingChunk`s are INVALIDATED — their ``result()`` raises
        `PendingInvalidated` instead of silently reassembling shard
        outputs that belong to the abandoned stream."""
        for p in list(self._inflight):
            p.invalidate()
        self._inflight = []
        self._tail = np.zeros((self.channels, 0), np.int32)
        self.samples_in = 0
        self.samples_out = 0
        self._chunk_idx = 0

    @property
    def pending(self) -> int:
        """Samples buffered but not yet old enough to finish a window."""
        return self._tail.shape[1]

    def push_stats(self) -> dict:
        """JSON-able cumulative work counters, as
        `FilterBankEngine.push_stats`: outputs computed are summed over
        shards (replays included), and a degraded engine adds its plain
        engine's device work."""
        d = self.stats.as_dict()
        if self._plain is not None:
            plain = self._plain.stats
            d["outputs_computed"] += plain.outputs_computed
            d["bytes_read_back"] += plain.bytes_read_back
        return d

    def read_stats(self) -> dict:
        """JSON-able cumulative counters of the overlapped readback:
        ``prefetched_reads``, shard reads whose host copy was started at
        dispatch, and ``overlapped_scatters``, shard scatters handed to
        a worker while a later shard of the chunk was still unread."""
        return dict(self._reads)

    # -- tail snapshot / restore (content-addressed stream state) -----------

    def snapshot_tail(self):
        """Freeze the overlap-save stream state as a
        `repro.compiler.TailSnapshot` keyed to this engine's program
        digest — the deterministic replay point behind fault recovery,
        and `save()`-able next to `BlmacProgram.save()` for cross-
        process stream resume."""
        from ..compiler.state import TailSnapshot

        return TailSnapshot(
            program_key=self.program.key, channels=self.channels,
            samples_in=self.samples_in, samples_out=self.samples_out,
            tail=self._tail.copy(),
        )

    def restore_tail(self, snapshot) -> None:
        """Adopt a `TailSnapshot` captured on THIS program (validated by
        content key — restoring another bank's stream is a loud error).
        Outstanding pendings are invalidated first (`reset` semantics)."""
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
        if snapshot.sample_bits == 16:
            raise ValueError(f"a 16-bit stream's snapshot: {WIDE_REFUSAL} "
                             "takes 8-bit samples")
        self.reset()
        self._tail = np.asarray(snapshot.tail, np.int32).copy()
        self.samples_in = int(snapshot.samples_in)
        self.samples_out = int(snapshot.samples_out)

    # -- fault detection / recovery -----------------------------------------

    def _materialize(self, p: PendingChunk) -> np.ndarray:
        """Assemble one pending chunk on the host; raises the first
        shard fault it detects (stored dispatch errors, watchdog
        timeout, integrity-probe corruption).  Shards are read in turn
        on this thread; each checked block is then copied once, straight
        to its caller rows, on a worker while the next shard is read.  A
        fault waits for the copies already handed out, so no output
        escapes half written; a worker's own error is raised in its
        place, with the fault as its context."""
        out = np.empty(p._shape + (p.n_out,), np.int32)
        last = len(p._shard_outs) - 1
        if last > 0 and self._scatter_pool is None:
            self._scatter_pool = ThreadPoolExecutor(
                max_workers=len(self._shards),
                thread_name_prefix="blmac-scatter")
        scatters = []
        try:
            for s, (rows, y, off) in enumerate(
                zip(p._assign, p._shard_outs, p._offsets)
            ):
                if isinstance(y, ShardError):
                    raise y
                part = self._materialize_shard(s, p, y, off)
                if last == 0:
                    _scatter(out, rows, part)
                    continue
                scatters.append(
                    self._scatter_pool.submit(_scatter, out, rows, part))
                if s < last:
                    self._reads["overlapped_scatters"] += 1
        except BaseException:
            _finish(scatters)
            raise
        with span("reassemble", chunk=p.chunk_idx):
            _finish(scatters)
        return out

    def _materialize_shard(self, s, p, y, off):
        inj = self.injector
        n_out = p.n_out

        def read():
            if inj is not None:
                inj.on_materialize(s, p.chunk_idx)
            host = np.asarray(y)
            if isinstance(y, jax.Array):  # not the degraded engine's
                self.stats.bytes_read_back += host.nbytes
                self._reads["prefetched_reads"] += 1  # started at dispatch
            return host[:, :, off: off + n_out]

        t0 = time.perf_counter()
        with span("shard_read", chunk=p.chunk_idx, shard=s):
            if self.health.timeout is not None:
                part = self._with_timeout(read, s)
            else:
                part = read()
        if self.health.record(s, time.perf_counter() - t0):
            self.fault.stragglers += 1
        if inj is not None:
            part = inj.corrupt(s, p.chunk_idx, part)
        if self.integrity_check:
            with span("verify", chunk=p.chunk_idx, shard=s):
                self._verify_part(s, part, p)
        return part

    def _with_timeout(self, fn, s):
        """Run one shard materialize under the `ShardHealth` hard
        deadline; expiry escalates to `ShardTimeout` (→ loss).  The
        worker thread is abandoned, not joined — a wedged device read
        must not wedge the recovery path too."""
        from concurrent.futures import TimeoutError as FuturesTimeout

        ex = ThreadPoolExecutor(max_workers=1)
        try:
            fut = ex.submit(fn)
            try:
                return fut.result(timeout=self.health.timeout)
            except FuturesTimeout:
                raise ShardTimeout(
                    s, f"shard {s} exceeded the {self.health.timeout:.3f}s "
                       f"watchdog timeout"
                ) from None
        finally:
            ex.shutdown(wait=False)

    def _verify_part(self, s, part, p):
        """Boundary integrity probe: recompute a handful of this shard's
        output positions on the host (int64 dot products over the
        snapshot tail + raw chunk) and compare bit-for-bit.  Probed
        positions are t = 0, the final output, and every data-axis
        slice boundary — where halo-exchange or reassembly corruption
        shows up first."""
        rows = self.partition.assign[s]
        full = np.concatenate(
            [np.asarray(p.snapshot.tail, np.int64),
             np.asarray(p.chunk, np.int64)], axis=1,
        )
        n_out = p.n_out
        pos = {0, n_out - 1}
        for j in range(1, self.n_data):
            pos.add(min(max(j * n_out // self.n_data, 0), n_out - 1))
        pos = sorted(pos)
        wins = np.stack([full[:, t: t + self.taps] for t in pos])  # (P,C,taps)
        expect = np.einsum("rj,pcj->rpc", self.qbank[rows], wins)
        got = np.asarray(part, np.int64)[:, :, pos].transpose(0, 2, 1)
        if not np.array_equal(got, expect):
            raise ShardCorruption(
                s, f"shard {s} failed the boundary integrity probe on "
                   f"chunk {p.chunk_idx}"
            )

    def _recover(self, err: ShardLost) -> None:
        """Handle a detected shard loss: drop the dead mesh row,
        re-partition the bank over the survivors (one shard per
        surviving row, or the caller's count), rebuild the dispatch
        closures, and
        replay every in-flight chunk from its tail snapshot.  Raises
        `ShardLost` when no surviving device remains."""
        with span("recover"):
            self._recover_from(err)

    def _recover_from(self, err: ShardLost) -> None:
        self.fault.detections += 1
        if isinstance(err, ShardTimeout):
            self.fault.timeouts += 1
        s = err.shard
        rows = self._device_rows
        if self._plain is not None or rows is None or len(rows) <= 1:
            raise ShardLost(
                s, f"shard {s} lost with no surviving devices to "
                   f"re-partition onto: {err}"
            ) from err
        t0 = time.perf_counter()
        self.fault.lost_shards += 1
        if self.injector is not None:
            self.injector.on_shard_removed(s)
        del rows[s]
        n_bank = len(rows)
        n_data = int(np.asarray(rows[0]).size)
        if n_bank == 1 and n_data == 1:
            self._configure_degraded(np.asarray(rows[0]).reshape(-1)[0])
        else:
            devices = [d for row in rows
                       for d in np.asarray(row).reshape(-1)]
            self._configure(bank_mesh(n_bank, n_data, devices=devices))
        self._replay_inflight()
        self.fault.recoveries += 1
        self.fault.last_recovery_s = time.perf_counter() - t0

    def _replay_inflight(self) -> None:
        """Re-dispatch every unresolved chunk through the recovered
        mesh, oldest first — each from its own tail snapshot, so the
        replayed stream is bit-exact with the uninterrupted one."""
        for p in list(self._inflight):
            self._replay_one(p)

    def _replay_one(self, p: PendingChunk) -> None:
        """Re-dispatch ONE pending chunk from its tail snapshot and
        swap the fresh shard outputs (and the current partition's
        reassembly recipe) into the pending."""
        with span("recover", chunk=p.chunk_idx):
            buf = np.concatenate(
                [np.asarray(p.snapshot.tail, np.int32), p.chunk], axis=1
            )
            outs, offsets = self._dispatch_shards(
                buf, buf.shape[1], p.chunk_idx
            )
        p._rearm(outs, offsets, self.partition.assign)
        self.fault.replayed_chunks += 1
        self.fault.replayed_samples += p.n_out

    # -- introspection ------------------------------------------------------

    def fault_stats(self) -> dict:
        """JSON-ready fault/recovery counters (see
        `repro.distributed.faultbank.FaultStats`) plus the live mesh
        shape, in-flight depth, injected-fault counts (when a
        `FaultInjector` is attached) and the `ShardHealth` heartbeat
        summary — the observability surface next to
        `repro.compiler.cache_stats()`."""
        d = self.fault.as_dict()
        d.update(
            n_bank_shards=self.n_bank_shards,
            n_data=self.n_data,
            data_mode=self.data_mode,
            inflight=len(self._inflight),
            injected=(
                self.injector.faults_injected()
                if self.injector is not None else None
            ),
            health=self.health.summary(),
        )
        return d

    def time_shards(self, chunk, repeats: int = 3) -> np.ndarray:
        """(n_shards,) best-of-``repeats`` isolated wall seconds per bank
        shard for one ``chunk``, without disturbing the stream state.

        Forced host-platform devices share the host's cores, so timing
        shards CONCURRENTLY measures core contention, not mesh scaling;
        this probe times each shard's dispatch alone (dispatch → block),
        which is the per-machine number the paper's replicated-instance
        throughput model aggregates.  `benchmarks/bank_sharded.py` builds
        its critical-path scaling row from exactly this.
        """
        chunk = np.atleast_2d(np.asarray(chunk)).astype(np.int32)
        n = chunk.shape[1]
        if n < self.taps:
            raise ValueError("chunk shorter than the filter")
        n_pad = -(-n // self._quantum) * self._quantum
        buf = np.pad(chunk, ((0, 0), (0, n_pad - n)))
        for fn, _ in self._shards:  # warm-up: compile
            jax.block_until_ready(fn(buf, n))
        # round-robin the repeats so one transient host hiccup cannot
        # poison every sample of a single shard (min-per-shard is only
        # robust when a shard's samples are spread over the run)
        times = np.full(len(self._shards), np.inf)
        for _ in range(repeats):
            for s, (fn, _) in enumerate(self._shards):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(buf, n))
                times[s] = min(times[s], time.perf_counter() - t0)
        return times

    def describe(self) -> str:
        """One line for logs: mesh, shard modes and balance."""
        modes = ",".join(p.mode[:4] for p in self.plan.shard_plans)
        degraded = " DEGRADED" if self._plain is not None else ""
        return (
            f"sharded-bank B={self.n_filters} C={self.channels} "
            f"mesh=({self.n_bank_shards}x{self.n_data}){degraded} "
            f"data={self.data_mode} modes=[{modes}] "
            f"imbalance={self.partition.imbalance:.2f}"
        )


def _scatter(out, rows, part) -> None:
    """Write one shard's block to its caller rows of the output."""
    out[rows] = part


def _finish(futures) -> None:
    """Wait for every scatter, then raise the first one's error."""
    wait(futures)
    for f in futures:
        f.result()
