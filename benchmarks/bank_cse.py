"""Cross-filter CSE pass: the adds-per-filter reduction on the sweep grid.

One arm, one committed artifact (``BENCH_cse.json``): the paper's
§3.3/Table-4 accounting metric on the full 9,900-filter 127-tap Hamming
sweep grid (`table4_machine`'s bank).  The grid is compiled once,
`repro.compiler.cse_pass`-optimized, and the §3.3 adds-per-filter and
§4 machine-cycle predictions of parent vs optimized program are
compared.  Pure accounting — no timing — so the reduction is exact,
deterministic and machine-independent (the optimized cycle column
amortizes each shared virtual row once per bank and charges one cycle
per combine use; it is priced at the widened ``coeff_bits = n_layers +
1`` spec the augmented rows need).

The CI gate (``--check``) enforces the acceptance floor: mean
adds-per-filter reduction ``>= --floor-adds`` (default 10%) on the
sweep grid, plus a tolerance band against the committed reduction.

Usage:
  python benchmarks/bank_cse.py                  # full run, writes JSON
  python benchmarks/bank_cse.py --fast           # CI smoke sizes
  python benchmarks/bank_cse.py --fast --check BENCH_cse.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SWEEP_TAPS = 127
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_cse.json")
BREAKDOWN_PATH = os.path.join(
    os.path.dirname(__file__), "out", "bank_cse_breakdown.json"
)


def run_sweep(n_div: int = 100, verbose: bool = True) -> dict:
    """The accounting arm: §3.3 adds and §4 cycles, parent vs optimized,
    over the Table-4 sweep grid."""
    from benchmarks.table4_machine import design_quantized_bank
    from repro.compiler import compile_bank, cse_pass
    from repro.core import MachineSpec

    qbank = design_quantized_bank(n_div)
    parent = compile_bank(qbank)
    n_filters = parent.n_filters
    t0 = time.perf_counter()
    opt = cse_pass(parent)
    mine_s = time.perf_counter() - t0

    adds_parent = parent.total_adds()
    adds_opt = opt.total_adds()
    pulses_parent = int(parent.pulse_counts.sum())
    pulses_opt = int(opt.pulse_counts.sum())
    cyc_parent = float(
        parent.machine_cycles(MachineSpec(taps=SWEEP_TAPS)).mean()
    )
    if opt is parent:  # the pass declined entirely (degenerate grid)
        n_shared, cyc_opt = 0, cyc_parent
    else:
        assert np.array_equal(opt.effective_qbank(), parent.qbank), \
            "sweep arm: optimized program is not bit-equivalent"
        n_shared = opt.n_shared
        # real-row cycles (incl. one per combine use) + each shared
        # virtual row amortized ONCE per bank per output sample
        cyc_opt = float(
            (opt.machine_cycles().sum() + opt.shared_cycles().sum())
            / opt.n_real
        )
    sweep = {
        "n_filters": n_filters,
        "taps": SWEEP_TAPS,
        "n_div": n_div,
        "n_shared": n_shared,
        "mine_seconds": mine_s,
        "total_adds_parent": adds_parent,
        "total_adds_optimized": adds_opt,
        "total_pulses_parent": pulses_parent,
        "total_pulses_optimized": pulses_opt,
        "mean_cycles_parent": cyc_parent,
        "mean_cycles_optimized": cyc_opt,
        **derive_sweep(adds_parent, adds_opt, n_filters,
                       pulses_parent, pulses_opt, cyc_parent, cyc_opt),
    }
    if verbose:
        print(f"sweep B={n_filters} taps={SWEEP_TAPS}: "
              f"adds/filter {sweep['adds_per_filter_parent']:.1f} -> "
              f"{sweep['adds_per_filter_optimized']:.1f} "
              f"({100 * sweep['adds_reduction']:.1f}% saved, "
              f"{n_shared} shared rows, mined in {mine_s:.2f}s); "
              f"cycles {cyc_parent:.1f} -> {cyc_opt:.1f} "
              f"({100 * sweep['cycle_reduction']:.1f}%)")
    return sweep


def derive_sweep(adds_parent, adds_opt, n_filters, pulses_parent,
                 pulses_opt, cyc_parent, cyc_opt) -> dict:
    """Derived reduction columns from the raw totals (shared with
    `benchmarks.reanalyze.reanalyze_cse`)."""
    return {
        "adds_per_filter_parent": adds_parent / n_filters,
        "adds_per_filter_optimized": adds_opt / n_filters,
        "adds_reduction": 1.0 - adds_opt / adds_parent,
        "pulse_reduction": 1.0 - pulses_opt / pulses_parent,
        "cycle_reduction": 1.0 - cyc_opt / cyc_parent,
    }


def run(n_div: int = 100, verbose: bool = True) -> dict:
    import jax

    return {
        "benchmark": "bank_cse",
        "backend": jax.default_backend(),
        "sweep": run_sweep(n_div, verbose=verbose),
    }


def write_breakdown(result: dict, path: str = BREAKDOWN_PATH) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")


def check(result: dict, committed_path: str, tolerance: float,
          floor_adds: float) -> int:
    """Fail (non-zero) unless the adds-per-filter reduction clears the
    acceptance floor and stays within ``tolerance`` of the committed
    value."""
    with open(committed_path) as f:
        committed = json.load(f)
    status = 0

    red = result["sweep"]["adds_reduction"]
    flag = "OK" if red >= floor_adds else "REGRESSION"
    print(f"check adds reduction: {100 * red:.1f}% >= "
          f"{100 * floor_adds:.1f}% required  {flag}")
    if flag != "OK":
        status = 1
    old = committed["sweep"]["adds_reduction"]
    flag = "OK" if red >= old - tolerance else "REGRESSION"
    print(f"check adds reduction vs committed: {100 * red:.1f}% vs "
          f"{100 * old:.1f}% (tolerance {100 * tolerance:.1f}pt)  {flag}")
    if flag != "OK":
        status = 1
    return status


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke sizes (no JSON rewrite)")
    ap.add_argument("--check", metavar="JSON",
                    help="compare against a committed BENCH_cse.json")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="allowed adds-reduction drop vs committed "
                         "(absolute percentage points / 100)")
    ap.add_argument("--floor-adds", type=float, default=0.10,
                    help="minimum mean adds-per-filter reduction on the "
                         "sweep grid (the PR acceptance bar)")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    if args.check and not os.path.exists(args.check):
        ap.error(f"baseline not found: {args.check}")
    result = run(n_div=20 if args.fast else 100)
    write_breakdown(result)
    if args.check:
        return check(result, args.check, args.tolerance, args.floor_adds)
    if not args.fast:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(f"wrote {os.path.normpath(args.out)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
