"""Device busy time per push outside the Mosaic kernel, in ms (chip 0):
framing, the tile-group concatenation, the y[inv] restore, slicing."""


def read(ctx):
    pushes = ctx.counters.get("pushes")
    if not ctx.trace or not pushes:
        return None
    dev = ctx.trace["devices"][0]
    if dev["busy_s"] <= 0:
        return None
    return 1e3 * (dev["busy_s"] - dev["kernel_s"]) / pushes
