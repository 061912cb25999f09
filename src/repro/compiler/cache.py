"""Bounded, instrumented caches for the compile pipeline.

One content-addressed program cache replaces the four scattered LRUs that
PRs 1–4 grew (`specialized_program`, `_bank_call`, the two autotuner
caches): a `BlmacProgram` is compiled at most once per distinct bank
content, and every downstream cache keys on the program's digest instead
of re-hashing (or worse, re-deriving) the bank.

`cache_stats()` is the single observability point: hit/miss/size for
every cache in the pipeline plus event counters for the expensive
recomputations the refactor is meant to eliminate (CSD packings,
schedule plans, machine-cycle derivations).  `tests/test_compiler.py`
asserts through it that CSD/occupancy is computed exactly once when one
bank is shared by the engine, the autotuner and the cycle predictor.
"""
from __future__ import annotations

import collections
import importlib
from dataclasses import dataclass

__all__ = ["CacheStat", "ProgramCache", "cache_stats", "clear_caches",
           "PROGRAM_CACHE", "STATS", "COUNTERS"]


@dataclass
class CacheStat:
    """Hit/miss counters for one cache domain."""

    hits: int = 0
    misses: int = 0

    def hit(self) -> None:
        self.hits += 1

    def miss(self) -> None:
        self.misses += 1

    def reset(self) -> None:
        self.hits = self.misses = 0


class ProgramCache:
    """LRU cache of compiled `BlmacProgram`s, content-addressed.

    One program object may be registered under SEVERAL keys (its
    quantized-coefficient digest and its packed-trit digest point at the
    same artifact), so a bank compiled from coefficients is found again
    by a caller holding only the packed operand, and vice versa.
    Bounded: past ``max_entries`` keys the least recently used entry is
    dropped — programs hold the packed bank, so the bound is the memory
    bound.
    """

    def __init__(self, max_entries: int = 64):
        self.max_entries = int(max_entries)
        self._entries: "collections.OrderedDict" = collections.OrderedDict()
        self.stat = CacheStat()

    def get(self, key):
        prog = self._entries.get(key)
        if prog is None:
            self.stat.miss()
            return None
        self._entries.move_to_end(key)
        self.stat.hit()
        return prog

    def put(self, prog, *keys) -> None:
        for key in keys:
            self._entries[key] = prog
            self._entries.move_to_end(key)
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        self._entries.clear()
        self.stat.reset()


PROGRAM_CACHE = ProgramCache()

# hit/miss stats for caches that live OUTSIDE this module but belong to
# the one pipeline (the autotuners key on program digests; their cache
# object stays in kernels/runtime.py to keep that module a leaf)
STATS: "dict[str, CacheStat]" = {
    "autotune": CacheStat(),
    # the optimized-program memo (compiler/optimize.py `_CSE_MEMO`):
    # hits are cse_pass calls answered without re-mining
    "cse": CacheStat(),
}

# event counters for the expensive derivations `compile_bank` is meant to
# centralize — each key counts actual recomputation events, not lookups
COUNTERS = collections.Counter()


def _bump(event: str, n: int = 1) -> None:
    COUNTERS[event] += n


def cache_stats() -> dict:
    """Hits/misses/size across every compile-pipeline cache.

    Returns a plain dict (JSON-ready)::

        {
          "program":     {"hits": ..., "misses": ..., "size": ...},
          "autotune":    {"hits": ..., "misses": ..., "size": ...},
          "cse":         {"hits": ..., "misses": ..., "size": ...},
          "specialized": {"hits": ..., "misses": ..., "size": ...},
          "bank_call":   {"size": ...},          # jit cache: size only
          "counters":    {"csd_packings": ..., "schedule_plans": ...,
                          "machine_cycle_computes": ..., ...},
        }

    ``counters`` are recomputation EVENTS (how many times the expensive
    step actually ran), the quantity the one-program refactor bounds.
    """
    # the submodule, NOT the same-named function re-exported by the
    # kernels package (`import ... as` would resolve the shadowing attr)
    _bf = importlib.import_module("repro.kernels.blmac_fir")
    _rt = importlib.import_module("repro.kernels.runtime")
    _opt = importlib.import_module("repro.compiler.optimize")

    out: dict = {
        "program": {
            "hits": PROGRAM_CACHE.stat.hits,
            "misses": PROGRAM_CACHE.stat.misses,
            "size": len(PROGRAM_CACHE),
        },
        "autotune": {
            "hits": STATS["autotune"].hits,
            "misses": STATS["autotune"].misses,
            "size": len(_rt._AUTOTUNE_CACHE),
        },
        "cse": {
            "hits": STATS["cse"].hits,
            "misses": STATS["cse"].misses,
            "size": len(_opt._CSE_MEMO),
        },
    }
    info = _bf.specialized_program.cache_info()
    out["specialized"] = {
        "hits": info.hits, "misses": info.misses, "size": info.currsize,
    }
    # jax.jit exposes only a size
    out["bank_call"] = {"size": _bf._bank_call._cache_size()}
    out["counters"] = dict(COUNTERS)
    return out


def clear_caches() -> None:
    """Empty every compile-pipeline cache and zero the counters.

    Test isolation hook; serving processes never need it (the caches are
    bounded).  The `_bank_call` jit cache is cleared too.
    """
    _bf = importlib.import_module("repro.kernels.blmac_fir")
    _rt = importlib.import_module("repro.kernels.runtime")
    _opt = importlib.import_module("repro.compiler.optimize")

    PROGRAM_CACHE.clear()
    _rt._AUTOTUNE_CACHE.clear()
    STATS["autotune"].reset()
    _opt._CSE_MEMO.clear()
    STATS["cse"].reset()
    _bf.specialized_program.cache_clear()
    _bf._bank_call.clear_cache()
    COUNTERS.clear()
