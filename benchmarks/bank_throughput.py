"""Filter-bank throughput: the engines' BLMAC bank plan vs per-filter loop.

For each bank size B the benchmark designs B lowpass filters with spread
cutoffs, quantizes them to 16 bits, and measures samples/s/filter for

  * ``batched``    — the plan every engine runs (`repro.compiler.bank_plan`:
    tile 256, bank tile `default_bank_tile(B)`, merge 8) on the
    scheduled bank kernel.  The plan is recorded in the row.
  * ``dense``      — the scheduled kernel forced to merge=1 and one
    default bank tile: one matmul per bit layer, i.e. the PR-1 kernel —
    kept so the schedule's contribution is measurable on its own.
  * ``per_filter`` — a Python loop issuing one dense B=1 bank-kernel call
    per filter, trits packed and schedules planned outside the timer (the
    per-filter serving pattern the bank replaces: compiled once,
    dispatched/framed B times).

All arms are cross-checked bit-exactly against
`repro.filters.fir_bit_layers_batch` before timing.  A full run writes
``BENCH_fir.json`` at the repo root, and every run writes the per-arm
breakdown to ``benchmarks/out/bank_throughput_breakdown.json``.  The
numbers are wall times of the backend the run is on (on a CPU, the
Pallas interpreter): a record, not a gate.

Usage:
  python benchmarks/bank_throughput.py                 # full: B ∈ {1,16,256}
  python benchmarks/bank_throughput.py --quick         # short signal, no JSON
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

BANK_SIZES = (1, 16, 256)
TAPS = 63
TILE = 512  # tile of the dense and per-filter arms
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_fir.json")
BREAKDOWN_PATH = os.path.join(
    os.path.dirname(__file__), "out", "bank_throughput_breakdown.json"
)


def _design_qbank(n_filters: int, taps: int) -> np.ndarray:
    from repro.filters import spread_lowpass_qbank

    return spread_lowpass_qbank(n_filters, taps)


def _time(fn, repeats: int) -> float:
    fn()  # warm-up: compile + cache
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_bank(
    n_filters: int,
    n_samples: int,
    taps: int = TAPS,
    tile: int = TILE,
    repeats: int = 3,
    verbose: bool = True,
    baseline: bool = True,
) -> dict:
    import jax.numpy as jnp

    from repro.compiler import bank_plan, compile_bank
    from repro.filters import fir_bit_layers_batch
    from repro.kernels.blmac_fir import blmac_fir_bank, plan_bank_schedule

    qbank = _design_qbank(n_filters, taps)
    rng = np.random.default_rng(42)
    x = rng.integers(-128, 128, n_samples).astype(np.int32)
    xj = jnp.asarray(x)
    n_out = n_samples - taps + 1

    # every arm reads the ONE compiled program — trit encoding, packing
    # AND schedule planning are compile-time work hoisted out of the
    # timed region, like reloading the FPGA weight memory
    program = compile_bank(qbank)
    packed = program.packed
    plan, schedule = bank_plan(program)
    dense_schedule = program.schedule(bank_tile=None, merge=1)
    singles = [
        (packed[b : b + 1], plan_bank_schedule(packed[b : b + 1], 1, merge=1))
        for b in range(n_filters)
    ]

    ref = fir_bit_layers_batch(x, qbank)[:, 0, :]

    def run_batched():
        blmac_fir_bank(
            xj, packed, taps, tile=plan.tile, schedule=schedule
        ).block_until_ready()

    y_plan = np.asarray(
        blmac_fir_bank(xj, packed, taps, tile=plan.tile, schedule=schedule)
    )

    def run_dense():
        blmac_fir_bank(
            xj, packed, taps, tile=tile, schedule=dense_schedule
        ).block_until_ready()

    # bit-exact check of every arm before any timing
    if not np.array_equal(y_plan, ref):
        raise AssertionError(f"batched arm mismatch at B={n_filters}")
    y_dense = np.asarray(
        blmac_fir_bank(xj, packed, taps, tile=tile, schedule=dense_schedule)
    )
    if not np.array_equal(y_dense, ref):
        raise AssertionError(f"dense arm mismatch at B={n_filters}")

    t_batched = _time(run_batched, repeats)
    t_dense = _time(run_dense, repeats)
    row = {
        "bank_size": n_filters,
        "n_samples": n_samples,
        "taps": taps,
        "tile": plan.tile,
        "mode": plan.mode,
        "bank_tile": plan.bank_tile,
        "merge": plan.merge,
        "outputs_per_filter": n_out,
        "batched_s": t_batched,
        "batched_samples_per_s_per_filter": n_out / t_batched,
        "dense_s": t_dense,
        "dense_samples_per_s_per_filter": n_out / t_dense,
        "speedup_vs_dense": t_dense / t_batched,
    }
    if baseline:

        def run_per_filter():
            ys = [
                blmac_fir_bank(
                    xj, p, taps, tile, bank_tile=1, schedule=s, fast_path=False
                )
                for p, s in singles
            ]
            ys[-1].block_until_ready()

        t_loop = _time(run_per_filter, repeats)
        row["per_filter_s"] = t_loop
        row["per_filter_samples_per_s_per_filter"] = n_out / t_loop
        row["speedup"] = t_loop / t_batched
    if verbose:
        per = (f"  per-filter {row['per_filter_samples_per_s_per_filter']:12.0f}"
               f"  samples/s/filter  speedup {row['speedup']:.2f}x"
               if baseline else "  samples/s/filter")
        print(f"B={n_filters:4d} [{row['mode']:11s} tile={row['tile']:4d} "
              f"bank_tile={row['bank_tile']:3d} merge={row['merge']}] batched "
              f"{row['batched_samples_per_s_per_filter']:12.0f}{per} "
              f"(vs dense {row['speedup_vs_dense']:.2f}x)")
    return row


def run(
    bank_sizes=BANK_SIZES,
    n_samples: int = 8192,
    repeats: int = 3,
    verbose: bool = True,
    baseline: bool = True,
) -> dict:
    import jax

    from repro.kernels.runtime import default_interpret

    rows = [
        bench_bank(b, n_samples, repeats=repeats, verbose=verbose,
                   baseline=baseline)
        for b in bank_sizes
    ]
    return {
        "benchmark": "bank_throughput",
        "backend": jax.default_backend(),
        "interpret": default_interpret(),
        "taps": TAPS,
        "tile": TILE,
        "rows": rows,
    }


def write_breakdown(result: dict, path: str = BREAKDOWN_PATH) -> None:
    """Per-mode rows for the CI artifact: one entry per (bank, arm)."""
    rows = []
    for r in result["rows"]:
        for arm in ("batched", "dense", "per_filter"):
            key = f"{arm}_s"
            if key not in r:
                continue
            rows.append({
                "bank_size": r["bank_size"],
                "arm": arm,
                "mode": r["mode"] if arm == "batched" else
                        ("scheduled/merge=1" if arm == "dense"
                         else "dense/bank_tile=1 loop"),
                "seconds": r[key],
                "samples_per_s_per_filter":
                    r["outputs_per_filter"] / r[key],
            })
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"benchmark": "bank_throughput_breakdown",
                   "taps": result["taps"], "rows": rows}, f, indent=2)
        f.write("\n")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="short signal, one repeat (no JSON rewrite)")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    n_samples = 2048 if args.quick else 8192
    repeats = 1 if args.quick else 3
    result = run(n_samples=n_samples, repeats=repeats)
    write_breakdown(result)
    if not args.quick:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(f"wrote {os.path.normpath(args.out)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
