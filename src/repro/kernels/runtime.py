"""Backend selection, profiler spans and the compilation cache shared by
the Pallas kernels and the engines.

Kernels take ``interpret: bool | None`` and resolve ``None`` through
:func:`default_interpret` at trace time: on a TPU backend the
``pallas_call`` lowers to Mosaic; everywhere else (this container is
CPU-only) the kernel body runs under the Pallas interpreter, which is the
bit-exact validation mode the tests rely on.

Lives in its own leaf module so both ``ops.py`` (the public entry points)
and the kernel modules it imports can share it without a cycle.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = [
    "default_interpret",
    "resolve_interpret",
    "use_compilation_cache",
    "span",
]


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
