"""One BLMAC program, five backends: the unified compile pipeline.

The paper's pipeline — quantized taps → CSD bit layers → a pulse /
superlayer schedule a tiny machine executes — is compiled ONCE into a
`BlmacProgram` and every execution engine is a *backend* of that
artifact::

             coefficients (float or already-quantized int)
                          │  compile_bank(coeffs, spec)
                          ▼
                    BlmacProgram  ──  save() / load()  (npz + JSON header)
        quantized taps · packed CSD trits · occupancy signatures
        memoized superlayer schedules · partitions · cycle counts
                          │  lower(program, backend=...)
        ┌───────────┬─────┴─────┬───────────┬─────────────┐
        ▼           ▼           ▼           ▼             ▼
     oracle    specialized  scheduled    vmachine      sharded
     (numpy     (Pallas,    (Pallas      (§4 machine   (mesh of
      Eq. 2)    pulse-baked) bank tiles)  simulator)    bank shards)

Public surface:

  * `compile_bank` / `compile_packed` — content-addressed compilation,
  * `BlmacProgram` — the artifact (schedules, partitions and cycle
    predictions all memoized on it),
  * `lower` — executables for the five backends,
  * `cse_pass` / `OptimizedProgram` — the cross-filter CSE optimizing
    pass: shared partial-sum rows mined across the bank, bit-exact on
    every backend, memoized on ``(parent.key, "cse", level)`` (see
    ``docs/architecture.md`` "Optimization passes"),
  * `plan_bank_schedule` / `BankSchedule` / `superlayer_schedule` — the
    pack-time scheduler (moved here from ``kernels/blmac_fir.py``),
  * `bank_plan` / `BankDispatchPlan` — the one rule that picks a bank's
    kernel geometry (tile, bank tile, merge),
  * `cache_stats` / `clear_caches` — one observability point for every
    compile-pipeline cache,
  * `TailSnapshot` — overlap-save stream state frozen as an artifact,
    content-addressed to its program (the replay point behind the
    sharded engine's bit-exact fault recovery).

`repro.filters.FilterBankEngine`, `ShardedFilterBankEngine`,
and `repro.serving.AsyncBankServer` are thin clients of this package.
"""
from .cache import cache_stats, clear_caches
from .lowering import BACKENDS, Lowered, lower
from .optimize import OptimizedProgram, cse_pass
from .program import (BlmacProgram, CompileSpec, PROGRAM_FORMAT_VERSION,
                      ProgramFormatError, compile_bank, compile_packed,
                      pack_bank_trits)
from .schedule import (BankDispatchPlan, BankSchedule, DEFAULT_TILE,
                       MERGE_DEFAULT, TileGroup, bank_plan, default_bank_tile,
                       plan_bank_schedule, superlayer_schedule)
from .state import STATE_FORMAT_VERSION, SnapshotFormatError, TailSnapshot

__all__ = [
    "BACKENDS",
    "BankDispatchPlan",
    "BankSchedule",
    "BlmacProgram",
    "CompileSpec",
    "DEFAULT_TILE",
    "Lowered",
    "MERGE_DEFAULT",
    "OptimizedProgram",
    "PROGRAM_FORMAT_VERSION",
    "ProgramFormatError",
    "STATE_FORMAT_VERSION",
    "SnapshotFormatError",
    "TailSnapshot",
    "TileGroup",
    "bank_plan",
    "cache_stats",
    "clear_caches",
    "compile_bank",
    "compile_packed",
    "cse_pass",
    "default_bank_tile",
    "lower",
    "pack_bank_trits",
    "plan_bank_schedule",
    "superlayer_schedule",
]
