"""Logical-axis → mesh-axis rules: the single place the parallelism layout
is decided.

Parallelism map (DESIGN.md §5):
  DP    batch over (pod, data)
  FSDP  the d_model side of every weight over data  (ZeRO-3-style; XLA
        inserts the per-layer all-gathers inside the scan)
  TP    heads / ff / vocab / experts over model (Megatron-style)
  EP    experts over model
  SP    decode KV/latent caches over model (flash-decoding style), and
        over (data, model) when the decode batch cannot fill the data axis

FIR bank partitioning (the BLMAC serving side):
  BANK  filters over the `bank` mesh axis — `partition_bank` assigns
        filters to shards occupancy-sorted AND cost-balanced, so one
        dense shard does not straggle the mesh (the paper scales by
        replicating 110-LUT machines; we scale by replicating per-shard
        bank programs)
  DATA  channels, or signal time chunks with an overlap-save halo
        exchange, over the `data` mesh axis, when asked for
`sharded_plan` applies these rules, and `repro.compiler.bank_plan` to
each shard.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_size(mesh: Mesh) -> int:
    return math.prod(mesh.shape[a] for a in data_axes(mesh))


def make_rules(mesh: Mesh, kind: str = "train",
               global_batch: int | None = None) -> dict[str, Any]:
    """Logical-axis rules for one execution cell."""
    daxes: Any = data_axes(mesh)
    if len(daxes) == 1:
        daxes = daxes[0]
    rules: dict[str, Any] = {
        "batch": daxes,
        "seq": None,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "heads_flat": "model",
        "head_dim": None,
        "ff": "model",
        "experts": "model",
        "expert_ff": None,
        "d_model": "data",  # FSDP
        "state": None,
        "layers": None,
        "cache_seq": None,
    }
    if kind == "decode":
        rules["cache_seq"] = "model"
        if global_batch is not None and global_batch < data_size(mesh):
            # batch can't fill the data axis (long-context, batch=1):
            # shard the cache sequence across everything instead
            rules["batch"] = None
            rules["cache_seq"] = (
                ("pod", "data", "model") if "pod" in mesh.axis_names
                else ("data", "model")
            )
    if kind in ("prefill", "decode"):
        # FSDP is a *training* memory trick: at inference, weights are
        # read-only — replicating them over `data` removes a full-model
        # all-gather per step (§Perf iteration A1: 133 GiB/step on
        # deepseek-coder-33b decode_32k)
        rules["d_model"] = None
    return rules


def named_sharding(mesh: Mesh, spec: PartitionSpec) -> NamedSharding:
    return NamedSharding(mesh, spec)


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        return math.prod(mesh.shape[a] for a in entry)
    return mesh.shape[entry]


def sanitize_spec(mesh: Mesh, spec: PartitionSpec, shape: tuple[int, ...]) -> PartitionSpec:
    """Drop mesh axes that do not divide their dim: jit *input* shardings
    must be even (GSPMD pads only intermediates).  E.g. kv_heads=2 cannot
    shard over model=16 → replicated (the realistic TP choice anyway)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is not None and dim % _axis_size(mesh, entry) != 0:
            entry = None
        out.append(entry)
    return PartitionSpec(*out)


def sanitized_shardings(mesh: Mesh, pspec_tree, shape_tree,
                        tp_fallback_axis: str | None = None):
    """NamedShardings with divisibility enforcement, leaf-wise.

    ``tp_fallback_axis``: when a weight ends up with NO use of that mesh
    axis (its TP dim wasn't divisible — e.g. 56 heads on a 16-way axis),
    shard its largest divisible dim instead.  For inference this is the
    row-parallel layout: the contraction dim is sharded, each device reads
    1/TP of the weight and contributes a partial sum (§Perf iteration A2).
    """
    flat_s, treedef = jax.tree_util.tree_flatten(
        pspec_tree, is_leaf=lambda x: isinstance(x, PartitionSpec))
    flat_shapes = treedef.flatten_up_to(shape_tree)
    out = []
    for s, sh in zip(flat_s, flat_shapes):
        shape = tuple(sh.shape)
        spec = sanitize_spec(mesh, s, shape)
        if tp_fallback_axis is not None:
            used = {a for e in spec if e
                    for a in (e if isinstance(e, tuple) else (e,))}
            if tp_fallback_axis not in used and len(shape) >= 2:
                size = mesh.shape[tp_fallback_axis]
                cands = [(dim, i) for i, (dim, e) in
                         enumerate(zip(shape, spec))
                         if e is None and dim % size == 0 and dim >= size]
                if cands:
                    _, idx = max(cands)
                    entries = list(spec)
                    entries[idx] = tp_fallback_axis
                    spec = PartitionSpec(*entries)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_shardings(mesh: Mesh, pspecs) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def batch_pspec(mesh: Mesh, rules: dict, ndim: int) -> PartitionSpec:
    """Sharding for a (B, S, ...) input batch leaf."""
    return PartitionSpec(rules.get("batch"), *([None] * (ndim - 1)))


def batch_shardings(mesh: Mesh, rules: dict, batch_tree) -> Any:
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(
            mesh, batch_pspec(mesh, rules, len(leaf.shape))
        ),
        batch_tree,
    )


# ---------------------------------------------------------------------------
# FIR filter-bank partition specs (the BLMAC serving mesh)
# ---------------------------------------------------------------------------

BANK_AXIS = "bank"
DATA_AXIS = "data"


def bank_mesh(
    n_bank: int | None = None,
    n_data: int = 1,
    devices=None,
) -> Mesh:
    """(bank, data) device mesh for sharded filter-bank serving.

    ``n_bank`` defaults to every available device divided by ``n_data``.
    A 1×1 mesh is valid — `ShardedFilterBankEngine` degrades to the
    single-device scheduled path on it.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if n_bank is None:
        n_bank = max(1, len(devices) // n_data)
    need = n_bank * n_data
    if need > len(devices):
        raise ValueError(
            f"bank_mesh needs {need} devices ({n_bank}×{n_data}), "
            f"have {len(devices)}"
        )
    return jax.make_mesh(
        (n_bank, n_data), (BANK_AXIS, DATA_AXIS), devices=devices[:need]
    )


def mesh_bank_shape(mesh: Mesh) -> tuple[int, int]:
    """(n_bank, n_data) of a bank mesh; axes it lacks count as size 1."""
    return (
        mesh.shape.get(BANK_AXIS, 1),
        mesh.shape.get(DATA_AXIS, 1),
    )


@dataclass(frozen=True)
class BankPartition:
    """Filter → bank-shard assignment with caller-order restoration baked in.

    ``assign[s]`` holds the ORIGINAL indices of the filters served by
    shard ``s`` (occupancy-sorted within the shard, so each shard's
    `plan_bank_schedule` sees a homogeneous run).  ``inv`` maps an
    original filter index to its row in the shard-major concatenation of
    per-shard outputs — reassembly is one host-side index permutation,
    never a cross-device gather.  ``cost[s]`` is the predicted per-shard
    work the balancer equalized.
    """

    assign: tuple
    inv: np.ndarray
    cost: np.ndarray

    @property
    def n_shards(self) -> int:
        return len(self.assign)

    @property
    def imbalance(self) -> float:
        """max/mean per-shard predicted cost — 1.0 is a perfect balance."""
        mean = float(self.cost.mean())
        return float(self.cost.max()) / mean if mean > 0 else 1.0


def bank_filter_costs(packed: np.ndarray, taps: int) -> np.ndarray:
    """(B,) predicted per-filter work: BLMAC pulses + the symmetric folds.

    The pulse count is exactly the paper's §3.3 add count, read straight
    off the packed trit words (each populated 2-bit code is one add in
    every kernel mode, `core.csd.packed_pulse_counts` — the same popcount
    `BlmacProgram.pulse_counts` stores), so the balancer and the cost
    model agree on what "one filter's work" means.
    """
    from ..core.csd import packed_pulse_counts

    return packed_pulse_counts(packed).astype(np.float64) + taps // 2


def partition_bank(
    packed: np.ndarray,
    n_shards: int,
    taps: int,
    cost: np.ndarray | None = None,
    sig: np.ndarray | None = None,
) -> BankPartition:
    """Occupancy-balanced contiguous partition of a packed bank.

    Filters are first sorted by layer-occupancy signature (the same
    ordering `plan_bank_schedule` uses), then the sorted run is cut into
    ``n_shards`` CONTIGUOUS spans with balanced cumulative cost.
    Contiguity in signature order keeps every shard occupancy-
    homogeneous (its tile schedules stay short); the weighted cut keeps
    a dense shard from straggling the mesh.  Shards may carry unequal
    filter counts — per-shard programs are compiled per shard, so no
    SPMD padding is needed.  ``n_shards`` is clamped to the bank size.

    ``cost``/``sig`` let a `repro.compiler.BlmacProgram` supply its
    precomputed per-filter costs and occupancy signatures (the
    `BlmacProgram.partition` hook does) instead of re-deriving them from
    the packed words here.
    """
    from ..core.csd import occupancy_signatures

    packed = np.asarray(packed)
    n_filters = packed.shape[0]
    if n_filters == 0:
        raise ValueError("cannot partition an empty bank")
    n_shards = max(1, min(int(n_shards), n_filters))
    if cost is None:
        cost = bank_filter_costs(packed, taps)
    cost = np.asarray(cost, np.float64)
    if sig is None:
        sig = occupancy_signatures(packed.any(axis=-1))
    order = np.argsort(sig, kind="stable")
    csum = np.cumsum(cost[order])
    total = csum[-1]
    if total <= 0:  # all-zero bank: fall back to equal counts
        bounds = [round(n_filters * s / n_shards) for s in range(n_shards + 1)]
    else:
        bounds = [0]
        for s in range(1, n_shards):
            target = total * s / n_shards
            cut = int(np.searchsorted(csum, target))
            # every shard keeps >= 1 filter and cuts stay monotonic
            cut = min(max(cut, bounds[-1] + 1), n_filters - (n_shards - s))
            bounds.append(cut)
        bounds.append(n_filters)
    assign = tuple(
        order[bounds[s]: bounds[s + 1]] for s in range(n_shards)
    )
    inv = np.empty(n_filters, np.int64)
    inv[np.concatenate(assign)] = np.arange(n_filters)
    shard_cost = np.array([cost[a].sum() for a in assign])
    return BankPartition(assign=assign, inv=inv, cost=shard_cost)


DATA_MODES = ("none", "channels", "time")


@dataclass(frozen=True)
class ShardedBankPlan:
    """How a bank runs on a (bank, data) mesh.

    ``n_bank_shards`` bank shards, one per mesh row; ``data_mode`` says
    how the second mesh axis is used: ``"none"`` (one device per shard),
    ``"channels"`` (C split over the axis, no communication) or
    ``"time"`` (signal chunks with an overlap-save halo exchange), over
    ``n_data`` devices.  ``shard_plans`` holds each shard's
    `repro.compiler.BankDispatchPlan`.
    """

    n_bank_shards: int
    n_data: int
    data_mode: str
    shard_plans: tuple

    @property
    def sharded(self) -> bool:
        return self.n_bank_shards > 1 or self.n_data > 1


def sharded_plan(
    program,
    mesh_shape: "tuple[int, int]",
    channels: int = 1,
    n_bank_shards: int | None = None,
    data_mode: str | None = None,
    tile: int | None = None,
    merge: int | None = None,
):
    """Plan a `repro.compiler.BlmacProgram` over an ``(n_bank, n_data)``
    mesh → ``(ShardedBankPlan, BankPartition, schedules)``.

    The bank is cut into ``n_bank_shards`` shards (default: every bank
    row of the mesh), at most one per filter and per bank row, by the
    program's memoized `partition`.  The data axis is used only when
    ``data_mode`` names ``"channels"`` (needs C divisible by the axis)
    or ``"time"``; the default is ``"none"``, and a mode the mesh cannot
    give raises ValueError.  Each shard runs `repro.compiler.bank_plan`
    of its memoized `select` subprogram, at ``tile`` and ``merge`` when
    given.

    >>> import numpy as np
    >>> from repro.compiler import compile_bank
    >>> bank = np.zeros((6, 15), np.int64)
    >>> bank[:, 7] = np.arange(1, 7) * 32
    >>> plan, part, _ = sharded_plan(compile_bank(bank), (4, 1))
    >>> plan.n_bank_shards, plan.data_mode, part.n_shards
    (4, 'none', 4)
    """
    from ..compiler import bank_plan

    n_bank, n_data = int(mesh_shape[0]), int(mesh_shape[1])
    mode = "none" if data_mode is None else data_mode
    if mode not in DATA_MODES:
        raise ValueError(f"unknown data mode {mode!r}; expected one of "
                         f"{DATA_MODES}")
    if mode != "none" and (
        n_data < 2 or (mode == "channels" and channels % n_data)
    ):
        raise ValueError(
            f"data mode {mode!r} is not available on a ({n_bank}, "
            f"{n_data}) mesh with {channels} channel(s)"
        )
    shards = n_bank if n_bank_shards is None else int(n_bank_shards)
    shards = max(1, min(shards, n_bank, program.n_filters))
    part = program.partition(shards)
    plans, schedules = [], []
    for rows in part.assign:
        plan, schedule = bank_plan(program.select(rows), tile, merge=merge)
        plans.append(plan)
        schedules.append(schedule)
    plan = ShardedBankPlan(
        n_bank_shards=shards,
        n_data=1 if mode == "none" else n_data,
        data_mode=mode,
        shard_plans=tuple(plans),
    )
    return plan, part, tuple(schedules)
