#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate whose backlog
does not grow.

    python3 chipbench/knee.py --workload <name> --seed <n> --seconds <s> \
        --rates <chunks/s> [<chunks/s> ...]

Runs the cell's traffic at each rate in turn, in one process on the
chip, and prints one JSON line per rate: the chunks offered, how long
after the window's close the last answer came (``drain_s``: it grows
with the window once the backlog grows), latency percentiles from due
time, mean step time and compiles in the window.  The cell's rate in
``traffic/<mix>.json`` is then set, once, to about four fifths of the
knee.  The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])
    from chipbench import design, drivers, generator, run

    bench = run.load_benchmark()
    w, cfg, traffic = run.cell(bench, args.workload)
    run.check_device(int(w["chips"]))
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.compiler import compile_bank
    from repro.kernels.runtime import use_compilation_cache

    use_compilation_cache()
    program = compile_bank(design.load_bank(cfg))
    for rate in args.rates:
        d = drivers.OpenLoop(cfg, dict(traffic, rate_chunks_per_s=rate),
                             args.seed, args.seconds,
                             generator.hooks(run.BENCH, w["traffic"]))
        d.setup(program)
        with drivers.CompileCounter() as compiles:
            rec = d.window(args.seconds, drivers.Spans(False))
        d.close()
        lat = np.asarray(rec["latencies_s"]) * 1e3
        print(json.dumps({
            "rate": rate, "offered": rec["attempted"],
            "unanswered": rec["missing_chunks"],
            "drain_s": rec["window_s"] - args.seconds,
            "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
            "p95_ms": float(np.percentile(lat, 95)) if lat.size else None,
            "step_ms": 1e3 * float(np.mean(rec["step_s"]))
            if rec["step_s"] else None,
            "occupancy": rec["occupancy"], "compiles": compiles.count,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
