"""95th percentile of how late the load generator pushed a chunk after
it was due, in ms: a starved generator is not a fast server."""
import numpy as np


def read(ctx):
    lag = ctx.counters.get("gen_lag_s")
    if not lag:
        return None
    return 1e3 * float(np.percentile(np.asarray(lag), 95))
