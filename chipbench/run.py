#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip this process finds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's configuration and traffic (``chipbench/configs``,
``chipbench/traffic``, found by the names in BENCHMARK.json), sets up the
served path, drives it for ``--seconds``, then compares what callers
received with the plain reference (``reference.py``).  With ``--trace 0``
the last stdout line carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics (one reader each in ``chipbench/metrics``), device
busy time and a breakdown.  The numbers compared, each with its limit,
come last on stderr and last in the JSON line.

Exits non-zero, printing no result, off a TPU or on fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":
    sys.path[0] = str(ROOT)  # import this directory as the package

import numpy as np  # noqa: E402

from chipbench import design, drivers, generator, roofline  # noqa: E402
from chipbench import reference, tracing  # noqa: E402


def process_age() -> float:
    """Seconds since this process started (from /proc where there is
    one, else since this module was loaded)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _T0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, workload: str, root: pathlib.Path = ROOT,
         bench_dir: pathlib.Path = BENCH) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic mix), found by name."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"chipbench: no workload {workload!r}; known: "
                         f"{sorted(by_name)}")
    w = by_name[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / entry["file"]) as f:
        cfg = json.load(f)
    return w, cfg, generator.load(bench_dir, w["traffic"])


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def metric_reader(bench_dir: pathlib.Path, name: str):
    """``read(ctx)`` of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int):
    """The chips, or exit non-zero naming what was found instead."""
    import jax

    devices = jax.devices()
    d = devices[0]
    found = (f"platform {d.platform!r}, kind {d.device_kind!r}, "
             f"count {len(devices)}")
    if d.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, found {found}")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, "
                         f"found {found}")
    return devices


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def traced_window(driver, seconds, log_dir):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    spans = drivers.Spans(True)
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with spans("window"):
            rec = driver.window(seconds, spans)
    return rec


def read_trace(log_dir: str, chips: int, keep: bool) -> tuple[dict, dict]:
    """The reduced trace of the chips a cell uses, and its breakdown.
    ``keep`` also writes what was read (``trace.json``) and a listing of
    the planes (``describe.json``) beside the trace."""
    path = tracing.find_xplane(log_dir)
    raw = tracing.extract(path)
    if keep:
        with open(os.path.join(log_dir, "describe.json"), "w") as f:
            json.dump(tracing.describe(path), f)
        with open(os.path.join(log_dir, "trace.json"), "w") as f:
            json.dump(raw, f)
    reduced = tracing.reduce(raw)
    reduced["devices"] = reduced["devices"][:chips]
    return reduced, tracing.breakdown(reduced, raw)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, devices, peak: dict, *, started=process_age,
             root: pathlib.Path = ROOT, bench_dir: pathlib.Path = BENCH,
             trace_dir: str | None = None,
             make_engine=None) -> tuple[dict, list]:
    """Set up, drive and check one cell.  Returns the result line (a
    dict) and the compared numbers as (name, value, limit).
    ``make_engine`` replaces the program's engine (the control)."""
    from repro.compiler import compile_bank

    w, cfg, traffic = cell(bench, workload, root, bench_dir)
    t = time.perf_counter()
    q = design.load_bank(cfg, bench_dir / "cache")
    log(f"[setup] {cfg['name']}: {q.shape[0]} filters x {q.shape[1]} taps "
        f"in {time.perf_counter() - t:.2f} s")
    hooks = generator.hooks(bench_dir, w["traffic"])
    if traffic["loop"] == "closed":
        driver = drivers.ClosedLoop(cfg, traffic, seed, hooks)
    else:
        driver = drivers.OpenLoop(cfg, traffic, seed, seconds, hooks)
    t = time.perf_counter()
    program = compile_bank(q)
    driver.setup(program, make_engine)
    log(f"[setup] compile_bank, engine and warm-up "
        f"{time.perf_counter() - t:.2f} s: {driver.describe()}")
    setup_s = started()

    log_dir = (trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-")
               if trace else None)
    try:
        with drivers.CompileCounter() as compiles:
            if trace:
                rec = traced_window(driver, seconds, log_dir)
            else:
                rec = driver.window(seconds, drivers.Spans(False))
        used = devices[:int(w["chips"])]
        mem = memory_peak(used)
        driver.close()
        del program
        gc.collect()
        if trace:
            reduced, brk = read_trace(log_dir, len(used), keep=bool(trace_dir))
    finally:
        if trace and not trace_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    rec["compiles"] = compiles.count
    log(f"[window] {rec['attempted']} attempted in {rec['window_s']:.3f} s, "
        f"{compiles.count} compiles inside the window")

    t = time.perf_counter()
    bad = missing = expected = failed = 0
    for name, got, x, rows in driver.checks():
        qr = q if rows is None else q[rows]
        b, m, e = reference.compare(got, x, qr)
        bad, missing, expected = bad + b, missing + m, expected + e
        failed += bool(b or m)
        if b or m:
            log(f"[check] {name}: {b} outputs differ, {m} missing of {e}")
    unanswered = int(rec.get("missing_chunks", 0))
    failed += unanswered
    log(f"[check] {expected} outputs compared with the reference in "
        f"{time.perf_counter() - t:.2f} s")
    checks = [("mismatched_outputs", bad, 0), ("missing_outputs", missing, 0),
              ("unanswered_chunks", unanswered, 0)]
    correct = expected > 0 and all(v <= lim for _, v, lim in checks)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": int(failed), "metrics": {}, "device": device}
    if not trace:
        lat = np.asarray(rec["latencies_s"], np.float64)
        values = {
            "setup_s": setup_s,
            "out_samples_per_s": rec["outputs"] / rec["window_s"],
            "latency_p95_ms": (float(np.percentile(lat, 95)) * 1e3
                               if lat.size else None),
        }
        for m in bench["end_to_end"]:
            if applies(m, workload):
                out["metrics"][m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
    else:
        devs = reduced["devices"]
        ctx = types.SimpleNamespace(trace=reduced if devs else None,
                                    counters=rec, peak=peak,
                                    config=cfg, taps=q.shape[1],
                                    filters=q.shape[0], why=w["why"])
        for m in bench["per_layer"]:
            if applies(m, workload):
                v = metric_reader(bench_dir, m["name"])(ctx)
                if v is not None:
                    out["metrics"][m["name"]] = {"value": float(v),
                                                 "unit": m["unit"]}
        device["busy_s"] = (float(np.mean([d["busy_s"] for d in devs]))
                            if devs else 0.0)
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = brk
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace, and what was read from "
                         "it, in this directory")
    args = ap.parse_args(argv)

    bench = load_benchmark()
    w, _, _ = cell(bench, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chipbench: no program under {ROOT / 'src'}")
    devices = check_device(int(w["chips"]))
    peak = roofline.peaks(devices[0].device_kind)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.runtime import use_compilation_cache

    log(f"[setup] compilation cache {use_compilation_cache()}")
    out, checks = run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), devices, peak,
                           trace_dir=args.trace_dir)
    for name, value, limit in checks:
        log(f"check {name} {value} limit {limit}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
