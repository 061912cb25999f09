#!/usr/bin/env python3
"""The control: the plain reference in float32, put in the program's place.

The configurations state exact integer outputs.  The nearest precision
below is float32: its products of 16-bit coefficients and 8-bit samples
are exact, but its sums round once they pass 2**24.  `Float32Engine`
computes the bank that way on the chip (a float32 matrix product at
``HIGHEST`` precision, so the MXU does not round its operands further)
and stands in for `FilterBankEngine` / `ShardedFilterBankEngine` in a
closed loop and for the session server's lane engine in an open loop.
Everything else of a run (the traffic, the window, the server's row
slicing, the check) is the benchmark's own.  A sound check must find it
not correct.

    python3 chipbench/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

runs the cell once per seed in one process and prints one JSON line per
seed with the compared numbers.  The benchmark's own runs never use it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROW_BLOCK = 2048  # filters per device matrix product


class Float32Engine:
    """``push`` / ``apply_lanes`` of the program's engines, computed as
    ``rint(float32(q) @ float32(window))`` on the default device."""

    def __init__(self, program, channels: int = 1, **_):
        import jax
        import jax.numpy as jnp

        self.program = program
        self.taps = program.taps
        self.channels = int(channels)
        self.n_filters = program.n_filters
        q = np.asarray(program.qbank, np.float32)
        self._q = [jnp.asarray(q[r:r + ROW_BLOCK])
                   for r in range(0, q.shape[0], ROW_BLOCK)]
        self._tail = np.zeros((self.channels, 0), np.int32)

        @jax.jit
        def rows(qb, x):  # qb (R, taps), x (C, n) -> (R, C, n - taps + 1)
            n_out = x.shape[1] - self.taps + 1
            idx = np.arange(n_out)[None, :] + np.arange(self.taps)[:, None]
            w = x.astype(jnp.float32)[:, idx]  # (C, taps, n_out)
            y = jnp.einsum("rk,ckn->rcn", qb, w,
                           precision=jax.lax.Precision.HIGHEST)
            return jnp.rint(y).astype(jnp.int32)

        self._rows = rows

    def apply_lanes(self, buf) -> np.ndarray:
        import jax.numpy as jnp

        x = jnp.asarray(np.asarray(buf, np.int32))
        return np.concatenate([np.asarray(self._rows(qb, x))
                               for qb in self._q], axis=0)

    def push(self, chunk) -> np.ndarray:
        chunk = np.asarray(chunk, np.int32)
        if chunk.ndim == 1:
            chunk = chunk[None, :]
        buf = np.concatenate([self._tail, chunk], axis=1)
        if buf.shape[1] < self.taps:
            self._tail = buf
            return np.zeros((self.n_filters, self.channels, 0), np.int32)
        self._tail = buf[:, buf.shape[1] - (self.taps - 1):]
        return self.apply_lanes(buf)

    def describe(self) -> str:
        return f"control: float32 reference, {self.n_filters} filters"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])
    from chipbench import roofline, run

    bench = run.load_benchmark()
    w, _, _ = run.cell(bench, args.workload)
    devices = run.check_device(int(w["chips"]))
    peak = roofline.peaks(devices[0].device_kind)
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.kernels.runtime import use_compilation_cache

    use_compilation_cache()
    for seed in args.seeds:
        out, checks = run.run_cell(bench, args.workload, seed, args.seconds,
                                   False, devices, peak,
                                   make_engine=Float32Engine)
        print(json.dumps({"control": "float32", "workload": args.workload,
                          "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
