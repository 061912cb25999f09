"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * us_per_call — wall time of the measured operation on this host,
  * derived     — the paper-comparable metric (additions, cycles, rates…).

Full-fidelity modes (paper's exact grids) are available on each module's
CLI (e.g. ``python benchmarks/fig34_fir_sweep.py --full``); this harness
uses reduced grids so the whole suite runs in ~2 minutes.
"""
from __future__ import annotations

import sys
import os
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


def bench_table3_pulses() -> None:
    """Tab. 3: avg/max pulse counts (exact, 1..16 bits here; --full: 24)."""
    from benchmarks import table3_pulses

    t0 = time.time()
    rows, ok = table3_pulses.run(max_bits=16, verbose=False)
    us = (time.time() - t0) * 1e6
    n7 = next(r for r in rows if r[0] == 7)
    _row("table3_pulses", us / len(rows),
         f"exact={ok};avg7={n7[1]:.2f};max7={n7[2]}")


def bench_fig34_sweep() -> None:
    """Figs. 3-4: B_N over the sweep (fast grid; --full = 1.98M filters)."""
    from benchmarks import fig34_fir_sweep

    t0 = time.time()
    rows, checks = fig34_fir_sweep.run("fast", verbose=False)
    us = (time.time() - t0) * 1e6
    h255 = next(r for r in rows if r["window"] == "hamming" and r["taps"] == 255)
    _row("fig34_fir_sweep", us / max(len(rows), 1),
         f"B255_hamming={h255['mean']:.1f};adds_per_tap={h255['adds_per_tap']:.2f};"
         f"vs_classical={h255['classical_equiv']/h255['mean']:.2f}x")


def bench_table4_machine() -> None:
    """§4/Tab. 4: vectorized machine over the whole bank — cycles,
    memory-fit rate, Msample/s, full-bank bit-exactness."""
    from benchmarks import table4_machine

    t0 = time.time()
    stats = table4_machine.run(n_div=40, verbose=False)
    us = (time.time() - t0) * 1e6
    _row("table4_machine", us,
         f"mean_cycles={stats['mean_cycles_all']:.1f};"
         f"fused={stats['fused_mean_cycles_all']:.1f};"
         f"pct_overflow={stats['pct_not_fitting']:.1f};"
         f"rate_artix7={316.8/stats['mean_cycles_all']:.2f}Msps;"
         f"bit_exact_bank={stats['n_filters'] - stats['bit_exact_mismatches']}"
         f"/{stats['n_filters']}")


def bench_kernel_blmac_fir() -> None:
    """Pallas FIR kernel (interpret mode on CPU): adds == pulse count."""
    import jax.numpy as jnp

    from repro.core import fir_blmac_additions, po2_quantize
    from repro.filters import design_bank
    from repro.kernels import blmac_fir

    h = design_bank(127, [("lowpass", 0.31)])[0]
    q, _ = po2_quantize(h, 16)
    x = jnp.asarray(np.random.default_rng(0).integers(-128, 128, 8192),
                    jnp.int32)
    y = blmac_fir(x, q)  # compile once
    t0 = time.time()
    for _ in range(3):
        y = blmac_fir(x, q)
    y.block_until_ready()
    us = (time.time() - t0) / 3 * 1e6
    _row("kernel_blmac_fir", us,
         f"outputs={y.shape[0]};adds_per_output={fir_blmac_additions(q)}")


def bench_kernel_bank() -> None:
    """Batched filter-bank kernel: samples/s/filter and speedup vs the
    per-filter loop (full grid + BENCH_fir.json: benchmarks/bank_throughput.py)."""
    from benchmarks import bank_throughput

    rows = bank_throughput.run(bank_sizes=(16,), n_samples=2048,
                               repeats=1, verbose=False)["rows"]
    r = rows[0]
    _row("kernel_bank_fir", r["batched_s"] * 1e6,
         f"B={r['bank_size']};"
         f"samples_per_s_per_filter={r['batched_samples_per_s_per_filter']:.0f};"
         f"vs_per_filter={r['speedup']:.2f}x")


def bench_bank_cse() -> None:
    """Cross-filter CSE pass: adds-per-filter reduction on the sweep
    grid, timed by the pass's mining (full grid + BENCH_cse.json:
    benchmarks/bank_cse.py)."""
    from benchmarks import bank_cse

    sweep = bank_cse.run(n_div=10, verbose=False)["sweep"]
    derived = (f"adds_reduction={100 * sweep['adds_reduction']:.1f}%;"
               f"cycle_reduction={100 * sweep['cycle_reduction']:.1f}%;"
               f"n_shared={sweep['n_shared']}")
    _row("bank_cse", sweep["mine_seconds"] * 1e6, derived)


def bench_kernel_pulse_matmul() -> None:
    """CSD-P pulse-code matmul vs quantization error / storage."""
    import jax.numpy as jnp

    from repro.kernels import pulse_dequantize, pulse_matmul_op, pulse_quantize

    rng = np.random.default_rng(0)
    w = rng.standard_normal((512, 256))
    x = rng.standard_normal((16, 512)).astype(np.float32)
    derived = []
    us = 0.0
    for p in (1, 2, 4):
        codes, ge = pulse_quantize(w, p)
        err = np.abs(pulse_dequantize(codes, ge) - w).mean() / np.abs(w).mean()
        t0 = time.time()
        y = pulse_matmul_op(jnp.asarray(x), jnp.asarray(codes),
                            jnp.asarray(ge), p)
        y.block_until_ready()
        us = (time.time() - t0) * 1e6
        derived.append(f"P{p}:relerr={err:.4f}")
    _row("kernel_pulse_matmul", us, ";".join(derived) + ";bits=6P(packed)")


def bench_roofline_summary() -> None:
    """§Roofline headline from the dry-run artifacts (if present)."""
    from benchmarks import roofline_table

    rows = roofline_table.load("baseline")
    if not rows:
        _row("roofline", 0.0, "no dryrun artifacts (run repro.launch.dryrun)")
        return
    n_fit = sum(r["fits_hbm"] for r in rows)
    dom = {}
    for r in rows:
        dom[r["dominant"]] = dom.get(r["dominant"], 0) + 1
    best = max(
        (r for r in rows if r["kind"] == "train"),
        key=lambda r: r["model_flops_per_dev"] / 197e12
        / max(r["compute_term_s"], r["memory_term_s"], r["collective_term_s"]),
    )
    frac = (best["model_flops_per_dev"] / 197e12 /
            max(best["compute_term_s"], best["memory_term_s"],
                best["collective_term_s"]))
    dom_s = ":".join(f"{k}{v}" for k, v in sorted(dom.items()))
    _row("roofline", 0.0,
         f"cells={len(rows)};fits_hbm={n_fit};dominant={dom_s};"
         f"best_train={best['arch']}@{frac:.3f}")


def main() -> None:
    print("name,us_per_call,derived")
    bench_table3_pulses()
    bench_fig34_sweep()
    bench_table4_machine()
    bench_kernel_blmac_fir()
    bench_kernel_bank()
    bench_bank_cse()
    bench_kernel_pulse_matmul()
    bench_roofline_summary()


if __name__ == "__main__":
    main()
