"""Busy time of the busiest chip over the mean chip's (1.0 = even)."""


def read(ctx):
    if not ctx.trace:
        return None
    busy = [d["busy_s"] for d in ctx.trace["devices"]]
    mean = sum(busy) / len(busy)
    return max(busy) / mean if mean > 0 else None
