"""Executables JAX built inside the window (compiled or read from the
persistent cache): lane lengths the warm-up did not cover."""


def read(ctx):
    return ctx.counters.get("compiles")
