"""Device time of the Mosaic bank kernel per push, in ms (chip 0).

A cell whose ``why`` names the kernel runs it on every push.  If chip 0
was busy in the window but no operation there is marked a kernel, the
trace names the kernel otherwise than `tracing.extract` expects, and
this reader raises rather than report nothing: the kernel's time would
otherwise go silently into ``other_device_ms.stream``."""


def read(ctx):
    pushes = ctx.counters.get("pushes")
    if not ctx.trace or not pushes:
        return None
    dev = ctx.trace["devices"][0]
    if dev["kernel_s"] > 0:
        return 1e3 * dev["kernel_s"] / pushes
    if dev["busy_s"] > 0 and "kernel" in ctx.why:
        raise RuntimeError(
            "chip 0 ran operations in the window, but none is marked as the "
            "bank kernel (tracing.KERNEL_MARK); read the trace's names with "
            "--trace-dir before trusting kernel_ms.stream")
    return None
