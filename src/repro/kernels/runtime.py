"""Backend selection and autotuned dispatch shared by the Pallas kernels.

Kernels take ``interpret: bool | None`` and resolve ``None`` through
:func:`default_interpret` at trace time: on a TPU backend the
``pallas_call`` lowers to Mosaic; everywhere else (this container is
CPU-only) the kernel body runs under the Pallas interpreter, which is the
bit-exact validation mode the tests rely on.

`autotune_bank_dispatch` is the FIR bank dispatch planner: it sweeps a
small candidate grid of ``(mode, tile, bank_tile, merge)`` configurations
through the cost model in `repro.core.costmodel` (constants fitted on the
reference machine) and returns the winner together with its compiled
`BankSchedule`.  Since the one-program refactor both autotuners are thin
clients of `repro.compiler`: the bank argument may be a `BlmacProgram`
(preferred — the engines pass theirs) or a raw packed operand (wrapped
via `compile_packed`), every candidate schedule comes from the program's
memoized `schedule()` and every cost estimate from its
``predict_*_us`` readers — nothing here re-derives CSD, occupancy or
trit statistics.  The dispatch cache keys on the program's content
digest; hits/misses are reported by `repro.compiler.cache_stats()`.

Lives in its own leaf module so both ``ops.py`` (the public entry points)
and the kernel modules it imports can share it without a cycle (the
planner imports ``blmac_fir`` lazily for the same reason).
"""
from __future__ import annotations

import collections
import os
import pathlib

import jax
import numpy as np

from ..compiler.cache import STATS as _COMPILER_STATS

__all__ = [
    "default_interpret",
    "resolve_interpret",
    "default_lane",
    "resolve_lane",
    "use_compilation_cache",
    "span",
    "autotune_bank_dispatch",
    "autotune_sharded_dispatch",
    "SPECIALIZE_BANK_MAX",
    "MERGE_CANDIDATES",
    "COMPILED_MERGE_CANDIDATES",
]

# Specialized programs compile once per filter (~0.3 s each under the
# interpreter): banks wider than this never dispatch per-filter, whatever
# the steady-state model says, so the compile bill stays bounded.
SPECIALIZE_BANK_MAX = 32
MERGE_CANDIDATES = (1, 4, 8)
# Compiled lanes re-open the merge question: a superlayer matmul on a
# wide vector/matrix unit amortizes its pass over the window matrix far
# better than the interpreter did, so FEWER, FATTER superlayers win —
# 32 exceeds any 16-bit bank's layer count, i.e. full fusion into one
# dense (bank_tile, M) @ (M, signal) contraction.  Measured on the
# reference container (B=256, taps=63): full merge on the XLA lane is
# ~2× merge=8 on the same lane, inverting the interpret-era heuristic.
COMPILED_MERGE_CANDIDATES = (8, 16, 32)
DEFAULT_TILE = 512
# Tile is a measured lookup, not a model output: the analytic cost model
# is linear in tile and cannot capture the cache-residency cliff that
# actually decides it (a (bank_tile, tile) int32 accumulator past ~256 KiB
# goes memory-bound on the reference machine).  Measured optimum: 512
# everywhere except wide scheduled tiles, where 256 wins ~15%.  The
# cliff is a property of the interpreter's blocked accumulate; compiled
# lanes keep DEFAULT_TILE.
WIDE_BANK_TILE = 128


def _default_tile(mode: str, bank_tile: int) -> int:
    return 256 if mode == "scheduled" and bank_tile >= WIDE_BANK_TILE \
        else DEFAULT_TILE


CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
    itself; no other directory is set here).  Otherwise the cache is the
    fixed ``<checkout>/.jax_cache`` — the path is part of what makes a
    later process find the entries again.  Every compile is cached: the
    bank kernels compile in about a second, under JAX's default
    threshold.  Call it before the first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def span(name: str, **ids):
    """The profiler span ``blmac.<name>`` around one step of the serving
    path, with small integer ``ids`` (``chunk``, ``group``, ``shard``,
    ``step``) that tie the spans of one push together.

    A `jax.profiler.TraceAnnotation`: it lands on the profiler's host
    plane, on the same clock as the device planes, so a trace reader can
    name what the host was doing while a chip sat idle.  With no
    profiler running it costs about a microsecond, so the serving path
    keeps its spans on at all times.
    """
    return jax.profiler.TraceAnnotation("blmac." + name, **ids)


def default_interpret() -> bool:
    """True when no TPU backend is present (interpret mode required)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve an ``interpret=None`` kernel argument to the backend default."""
    return default_interpret() if interpret is None else bool(interpret)


def default_lane() -> str:
    """The compiled execution lane this host can actually run: Mosaic on
    a TPU backend, Triton on a GPU backend, the plain-XLA lowering
    everywhere else (the CPU-compiled CI target)."""
    backend = jax.default_backend()
    if backend == "tpu":
        return "mosaic"
    if backend == "gpu":
        return "triton"
    return "xla"


def resolve_lane(lane: "str | bool | None") -> str:
    """Resolve a ``compiled=`` argument to a lane name: ``True``/``None``
    → this host's `default_lane`, a string → itself (validated)."""
    from .blmac_fir import LANES

    if lane is None or lane is True:
        return default_lane()
    if lane in LANES:
        return str(lane)
    raise ValueError(f"unknown lane {lane!r}; expected one of {LANES}")


def _resolve_program(bank, taps):
    """Accept a `BlmacProgram` (preferred) or a packed operand + taps."""
    from ..compiler import BlmacProgram, compile_packed

    if isinstance(bank, BlmacProgram):
        if taps is not None and int(taps) != bank.taps:
            raise ValueError(f"program is {bank.taps}-tap, got taps={taps}")
        return bank
    if taps is None:
        raise ValueError("taps is required with a packed-operand bank")
    return compile_packed(np.ascontiguousarray(bank), int(taps))


def autotune_bank_dispatch(
    bank,  # BlmacProgram, or (B, n_layers, n_words) uint32 packed operand
    taps: int | None = None,
    channels: int = 1,
    tile: int | None = None,
    chunk_hint: int = 2048,
    interpret: bool | None = None,
    compiled: "bool | str" = False,
):
    """Pick ``(mode, tile, bank_tile, merge)`` for a compiled bank.

    Evaluates the cost model over the candidate grid — the specialized
    per-filter loop (narrow banks only, see `SPECIALIZE_BANK_MAX`) versus
    occupancy-grouped scheduled tiles at each ``(bank_tile, merge)`` —
    and returns ``(plan, schedule)``: the winning
    `repro.core.costmodel.BankDispatchPlan` plus, for scheduled mode, the
    `BankSchedule` it was costed with (so callers never re-plan).

    ``bank`` is a `repro.compiler.BlmacProgram` or a raw `pack_bank_trits`
    operand (then ``taps`` is required; the operand is wrapped content-
    addressed via `compile_packed`).  Candidate schedules come from the
    program's memo, so an engine autotuning then serving the same bank
    plans each geometry once.  ``chunk_hint`` is the expected samples per
    dispatch, the autotuner's amortization knob (streaming engines push
    small chunks → dispatch overhead matters more; one-shot batch jobs
    amortize it).  ``tile`` defaults to the measured per-mode lookup
    (see `_default_tile`).

    ``compiled`` opts the sweep into the compiled execution lanes:
    ``True`` adds this host's `default_lane` (a lane name string pins
    one explicitly), costed at the wider `COMPILED_MERGE_CANDIDATES`
    with that lane's `BackendCalibration` — fitted at first use via
    `repro.core.costmodel.ensure_calibration`.  The interpret candidates
    stay in the sweep, so the winning ``plan.lane`` answers "does the
    compiled lowering pay here?".  The default (``False``) keeps the
    historic interpret-only sweep byte-for-byte.

    An `repro.compiler.OptimizedProgram` (CSE pass output) is swept over
    its shared-row layout — `predict_scheduled_us` prices the combine
    stage — AND compared against autotuning its parent: when the parent
    wins, the returned plan carries ``cse="declined"`` with the PARENT's
    schedule, and the engine executes the parent (bit-identical
    outputs); otherwise ``cse="optimized"``.
    """
    program = _resolve_program(bank, taps)
    lanes: "tuple[str, ...]" = ("interpret",)
    if compiled:
        lanes = ("interpret", resolve_lane(compiled))
    key = (
        program.key, channels, tile, chunk_hint, resolve_interpret(interpret),
        lanes,
    )
    if key in _AUTOTUNE_CACHE:
        _AUTOTUNE_CACHE.move_to_end(key)
        _COMPILER_STATS["autotune"].hit()
        return _AUTOTUNE_CACHE[key]
    _COMPILER_STATS["autotune"].miss()
    result = _autotune(program, channels, tile, chunk_hint, lanes=lanes)
    if program.combine is not None:
        import dataclasses

        parent_plan, parent_sched = autotune_bank_dispatch(
            program.parent, channels=channels, tile=tile,
            chunk_hint=chunk_hint, interpret=interpret, compiled=compiled,
        )
        opt_plan, opt_sched = result
        if parent_plan.predicted_us < opt_plan.predicted_us:
            result = (
                dataclasses.replace(parent_plan, cse="declined"),
                parent_sched,
            )
        else:
            result = (
                dataclasses.replace(opt_plan, cse="optimized"), opt_sched
            )
    _AUTOTUNE_CACHE[key] = result
    while len(_AUTOTUNE_CACHE) > _AUTOTUNE_CACHE_MAX:
        _AUTOTUNE_CACHE.popitem(last=False)
    return result


_AUTOTUNE_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_AUTOTUNE_CACHE_MAX = 16  # schedules hold compacted bank copies: keep few


def _autotune(program, channels, tile, chunk_hint, allow_specialized=True,
              lanes=("interpret",)):
    from ..compiler import default_bank_tile
    from ..core.costmodel import BankDispatchPlan, ensure_calibration
    from .blmac_fir import BF16_MERGE_MAX

    n_filters = program.n_filters

    def n_tiles(t):
        return max(1, -(-chunk_hint // t))

    best = None  # (plan, schedule)
    if allow_specialized and n_filters <= SPECIALIZE_BANK_MAX:
        t = tile or _default_tile("specialized", 1)
        us = program.predict_specialized_us(channels, n_tiles(t))
        best = (BankDispatchPlan("specialized", t, 1, 1, us), None)
    bank_tiles = {default_bank_tile(n_filters)}
    if n_filters > 8:
        bank_tiles.add(min(default_bank_tile(n_filters), 32))
    for lane in lanes:
        if lane == "interpret":
            # the historic sweep: reference constants, blocked-tile lookup
            cal, merges = None, MERGE_CANDIDATES
        else:
            cal = ensure_calibration(lane)  # fit-at-first-use, persisted
            merges = COMPILED_MERGE_CANDIDATES
            if lane != "xla":  # Pallas: only the exact bf16 contraction
                merges = tuple(m for m in merges if m <= BF16_MERGE_MAX)
        for bt in sorted(bank_tiles):
            for merge in merges:
                schedule = program.schedule(bt, merge)
                t = tile or (
                    _default_tile("scheduled", bt)
                    if lane == "interpret" else DEFAULT_TILE
                )
                us = program.predict_scheduled_us(
                    channels, n_tiles(t), t, bt, merge, cal=cal
                )
                plan = BankDispatchPlan("scheduled", t, bt, merge, us, lane)
                if best is None or us < best[0].predicted_us:
                    best = (plan, schedule)
    return best


# ---------------------------------------------------------------------------
# mesh-aware sharded dispatch planning
# ---------------------------------------------------------------------------


def autotune_sharded_dispatch(
    bank,  # BlmacProgram, or (B, n_layers, n_words) uint32 packed operand
    taps: int | None = None,
    channels: int = 1,
    mesh_shape: "tuple[int, int]" = (1, 1),
    tile: int | None = None,
    chunk_hint: int = 2048,
    interpret: bool | None = None,
    force_shards: int | None = None,
    force_data: str | None = None,
    compiled: "bool | str" = False,
):
    """Plan a bank dispatch over an (n_bank, n_data) device mesh.

    Sweeps the bank-shard count over {1, 2, 4, …, n_bank} (occupancy-
    balanced contiguous partitions from
    `repro.distributed.sharding.partition_bank`), runs the single-device
    autotuner on EVERY candidate shard (per-shard mode/tile/merge picks,
    with the data-axis slice of the chunk folded into its amortization
    knob), and scores candidates with the critical-path model
    `repro.core.costmodel.predict_sharded_us`.  The unsharded plan
    competes in the same sweep, so the winner answers "does sharding pay
    at all?" — `ShardedBankPlan.n_bank_shards == 1` means it does not.

    Returns ``(plan, partition, schedules)``: the winning
    `ShardedBankPlan`, its `BankPartition`, and one `BankSchedule` (or
    ``None`` for specialized shards) per bank shard, so callers never
    re-plan.  ``bank`` is a `BlmacProgram` or a raw packed operand (then
    ``taps`` is required); per-shard candidates are the program's
    memoized `select()` subprograms — the exact objects the sharded
    engine then executes, so autotuning and serving share one compiled
    artifact per shard.  LRU-cached on the program digest like
    `autotune_bank_dispatch`.  ``force_shards`` pins the bank-shard count
    (the sweep collapses to that single candidate — mode/tile per shard
    are still autotuned); ``force_data`` pins the data-axis usage to
    ``"none"``, ``"channels"`` or ``"time"`` instead of letting the
    sweep decline the axis.  ``compiled`` adds the compiled execution
    lanes to every per-shard sweep, exactly as in
    `autotune_bank_dispatch` — per-shard plans then carry the winning
    ``lane`` and the host-dispatch costs are priced with that lane's
    calibration.

    An `OptimizedProgram` plans its augmented shared-row bank (via
    ``.bank``; the host folds ``combine`` after the gather, priced with
    `predict_combine_us`) and competes against planning its parent —
    the winner's plan carries ``cse="optimized"`` or ``cse="declined"``
    so callers know which program's rows the partition/schedules
    describe.
    """
    program = _resolve_program(bank, taps)
    n_bank, n_data = int(mesh_shape[0]), int(mesh_shape[1])
    lanes: "tuple[str, ...]" = ("interpret",)
    if compiled:
        lanes = ("interpret", resolve_lane(compiled))
    key = (
        "sharded", program.key, channels, n_bank, n_data, tile, chunk_hint,
        resolve_interpret(interpret), force_shards, force_data, lanes,
    )
    if key in _AUTOTUNE_CACHE:
        _AUTOTUNE_CACHE.move_to_end(key)
        _COMPILER_STATS["autotune"].hit()
        return _AUTOTUNE_CACHE[key]
    _COMPILER_STATS["autotune"].miss()
    if program.combine is not None:
        result = _sharded_cse_compare(
            program, channels, n_bank, n_data, tile, chunk_hint,
            force_shards, force_data, lanes,
        )
    else:
        result = _autotune_sharded(
            program, channels, n_bank, n_data, tile, chunk_hint,
            force_shards, force_data, lanes=lanes,
        )
    _AUTOTUNE_CACHE[key] = result
    while len(_AUTOTUNE_CACHE) > _AUTOTUNE_CACHE_MAX:
        _AUTOTUNE_CACHE.popitem(last=False)
    return result


def _sharded_cse_compare(program, channels, n_bank, n_data, tile,
                         chunk_hint, force_shards, force_data, lanes):
    """Sharded plan for an `OptimizedProgram`: plan the augmented bank
    (+ the host-side combine fold after the gather) against planning
    the parent outright, and tag the winner's ``cse`` field."""
    import dataclasses

    from ..core.costmodel import predict_combine_us

    opt_plan, opt_part, opt_scheds = _autotune_sharded(
        program.bank, channels, n_bank, n_data, tile, chunk_hint,
        force_shards, force_data, lanes=lanes,
    )
    # the fold is host numpy on the gathered result — reference-constant
    # pricing, like the host dispatch terms above
    t = opt_plan.shard_plans[0].tile
    combine_us = predict_combine_us(
        program.n_real, program.n_shared, channels,
        max(1, -(-chunk_hint // t)), t,
    )
    opt_plan = dataclasses.replace(
        opt_plan, predicted_us=opt_plan.predicted_us + combine_us,
        cse="optimized",
    )
    parent_plan, parent_part, parent_scheds = _autotune_sharded(
        program.parent, channels, n_bank, n_data, tile, chunk_hint,
        force_shards, force_data, lanes=lanes,
    )
    if parent_plan.predicted_us < opt_plan.predicted_us:
        return (
            dataclasses.replace(parent_plan, cse="declined"),
            parent_part, parent_scheds,
        )
    return opt_plan, opt_part, opt_scheds


def _shard_candidates(n_bank: int, n_filters: int) -> "list[int]":
    """Bank-shard counts to sweep: powers of two up to the axis, the axis
    itself, all clamped to the bank size."""
    cands = {1}
    c = 2
    while c < n_bank:
        cands.add(c)
        c *= 2
    cands.add(n_bank)
    return sorted({min(c, n_filters) for c in cands})


def _autotune_sharded(program, channels, n_bank, n_data, tile,
                      chunk_hint, force_shards=None, force_data=None,
                      lanes=("interpret",)):
    from ..core.costmodel import (PALLAS_CALL_US, SPEC_CALL_US,
                                  ShardedBankPlan, get_calibration,
                                  predict_sharded_us)

    taps = program.taps
    n_filters = program.n_filters
    # data-axis candidates: using the axis (channels when divisible, else
    # time chunks with a halo exchange) AND leaving it idle — the sweep
    # may decline EITHER mesh axis; the engine degrades per-shard to a
    # single-device row when nd == 1 wins
    data_cands = [(1, "none", channels, chunk_hint)]
    if n_data > 1:
        if channels % n_data == 0:
            data_cands.append(
                (n_data, "channels", channels // n_data, chunk_hint)
            )
        else:
            data_cands.append(
                (n_data, "time", channels,
                 max(taps, -(-chunk_hint // n_data)))
            )
    if force_data is not None:
        data_cands = [c for c in data_cands if c[1] == force_data]
        if not data_cands:
            raise ValueError(
                f"data mode {force_data!r} is not available on a "
                f"({n_bank}, {n_data}) mesh with {channels} channel(s)"
            )

    if force_shards is not None:
        candidates = [max(1, min(int(force_shards), n_bank, n_filters))]
    else:
        candidates = _shard_candidates(n_bank, n_filters)
    best = None  # (ShardedBankPlan, partition, schedules)
    for nd, data_mode, chan_local, chunk_local in data_cands:
        for n_shards in candidates:
            part = program.partition(n_shards)
            # two mode policies per shard count: each shard's free pick,
            # and all-scheduled — the per-shard optimum is chosen in
            # isolation, but specialized shards pay one HOST dispatch
            # per filter, and the host is serial across the mesh; only
            # the sharded objective can see that, so it must get both
            # variants to rank
            policies = (
                (True, False) if data_mode == "none" else (False,)
            )
            for allow_spec in policies:
                plans, schedules, costs, host = [], [], [], []
                for rows in part.assign:
                    sub = program.select(rows)  # memoized shard subprogram
                    plan, schedule = _autotune(
                        sub, chan_local, tile, chunk_local,
                        allow_specialized=allow_spec, lanes=lanes,
                    )
                    plans.append(plan)
                    schedules.append(schedule)
                    costs.append(plan.predicted_us)
                    # host dispatch is priced with the winning lane's
                    # constants (interpret keeps the reference values)
                    if plan.lane == "interpret":
                        call_us, spec_us = PALLAS_CALL_US, SPEC_CALL_US
                    else:
                        c = get_calibration(plan.lane)
                        call_us, spec_us = c.call_us, c.spec_call_us
                    if plan.mode == "specialized":
                        host.append(len(rows) * chan_local * spec_us)
                    else:
                        host.append(
                            sum(1 for g in schedule.groups if g.sel_layers)
                            * call_us
                        )
                if allow_spec and not any(
                    p.mode == "specialized" for p in plans
                ):
                    continue  # identical to the all-scheduled variant
                us = predict_sharded_us(costs, nd, data_mode, host_us=host)
                if n_shards == 1 and nd == 1:
                    us = plans[0].predicted_us  # true unsharded baseline
                cand = (
                    ShardedBankPlan(
                        n_bank_shards=n_shards,
                        n_data=nd,
                        data_mode=data_mode,
                        shard_plans=tuple(plans),
                        predicted_us=us,
                    ),
                    part,
                    tuple(schedules),
                )
                if best is None or us < best[0].predicted_us:
                    best = cand
    return best
