"""Least time of a 16-bit push over the device's busy time per push, in %.

The work comes from the push's shapes alone (`work`), the same whatever
code does it: B filters, C channels, n_out outputs, taps; 8 bytes per
int64 output, 2 per 16-bit input sample, 2 per 16-bit coefficient, and
2 · B · C · n_out · (taps // 2 + 1) integer operations.  The least time
is the larger of bytes over peak HBM bandwidth and operations over the
peak int8 rate; the busy time is every device operation, kernel or not."""
from chipbench import roofline


def work(b: int, c: int, n_out: int, taps: int) -> tuple[float, float]:
    """(operations, bytes) one 16-bit push needs at least."""
    ops = 2.0 * b * c * n_out * (taps // 2 + 1)
    nbytes = 8.0 * b * c * n_out + 2.0 * c * n_out + 2.0 * b * taps
    return ops, nbytes


def read(ctx):
    c = ctx.counters
    pushes = c.get("pushes")
    if not ctx.trace or not pushes:
        return None
    busy = ctx.trace["devices"][0]["busy_s"]
    if busy <= 0:
        return None
    ops, nbytes = work(ctx.filters, c["channels"], c["n_out"], ctx.taps)
    least = roofline.least_seconds(ops, nbytes, ctx.peak)
    return 100.0 * least * pushes / busy
