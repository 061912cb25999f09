"""Device time per push of the plane-combine kernel, in ms (chip 0): the
operations named ``blmac_plane_combine``, which turn the int32 outputs of
a 16-bit stream's two 8-bit planes into exact int64 outputs.  Nothing
where no operation of that name ran."""
NAME = "blmac_plane_combine"


def read(ctx):
    pushes = ctx.counters.get("pushes")
    if not ctx.trace or not pushes:
        return None
    ops = ctx.trace["devices"][0]["ops_s"]
    seconds = sum(v for k, v in ops.items() if NAME in k)
    return 1e3 * seconds / pushes if seconds > 0 else None
