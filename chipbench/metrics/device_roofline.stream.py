"""Least time of a push over the device's busy time per push, in %.

The least time comes from the push's shapes alone (`roofline.push_work`:
B filters, C channels, n_out outputs, taps) and the chip's published
peaks; the busy time is every device operation, kernel or not, so the
share reads the same work whichever code does it."""
from chipbench import roofline


def read(ctx):
    c = ctx.counters
    pushes = c.get("pushes")
    if not ctx.trace or not pushes:
        return None
    busy = ctx.trace["devices"][0]["busy_s"]
    if busy <= 0:
        return None
    ops, nbytes = roofline.push_work(
        ctx.filters, c["channels"], c["n_out"], ctx.taps,
        ctx.config["sample_bits"], ctx.config["coeff_bits"])
    least = roofline.least_seconds(ops, nbytes, ctx.peak)
    return 100.0 * least * pushes / busy
