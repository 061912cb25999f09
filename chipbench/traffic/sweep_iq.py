"""The engine of ``sweep_iq``: the mix's own, refused when it cannot serve
the configuration.

A 16-bit configuration states exact int64 outputs.  The engine is asked
for them with an empty int16 push, which runs nothing on the device.  A
program that answers with another dtype cannot run the cell: it exits
non-zero during set-up, before any timed push, in place of reporting a
window of outputs that are known to be wrong."""
import numpy as np

from chipbench import drivers


def make_engine(program, mix, channels, chunk_hint):
    engine = drivers.build_engine(program, mix, channels, chunk_hint)
    probe = engine.push(np.zeros((channels, 0), np.int16))
    if probe.dtype != np.int64:
        raise SystemExit(
            f"chipbench: the program returns {probe.dtype} outputs for "
            f"16-bit samples; this configuration needs exact int64 outputs")
    return engine
