"""Share of the window in which a chip ran no operation, in %, averaged
over the chips the cell holds."""


def read(ctx):
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    devs = ctx.trace["devices"]
    busy = sum(d["busy_s"] for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / ctx.trace["window_s"])
