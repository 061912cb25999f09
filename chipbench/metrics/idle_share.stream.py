"""Share of the window in which chip 0 ran no operation, in %."""


def read(ctx):
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    dev = ctx.trace["devices"][0]
    return 100.0 * (1.0 - dev["busy_s"] / ctx.trace["window_s"])
