"""Lanes carrying a tenant over lanes dispatched, in the window, in %
(the server's own serve_stats() occupancy counter)."""


def read(ctx):
    occ = ctx.counters.get("occupancy")
    return None if occ is None else 100.0 * occ
