"""Paper Figs. 3–4 + §3.3 analysis: BLMAC additions over the FIR sweep.

For each tap count (55..255 odd) × window (Hamming / Kaiser β=8.0 — β
calibrated against the paper's reported B_N, see EXPERIMENTS.md):
design the N(N−1)-filter bank, quantize to int16 (po2 scale + convergent
rounding), count BLMAC additions (Eq. 3 + ntrits), and report
mean/std/min/max — the quantities plotted in the paper's figures.

Default is the paper's full n_div=100 grid but a thinned tap sweep; pass
``--full`` for all 101 tap counts (≈7 CPU-minutes serially, 1.98M
filters) or ``--fast`` for a n_div=40 grid.  ``--jobs N`` fans the
(window, tap-count) grid across a process pool — each cell designs,
quantizes and counts its bank independently, so this scales to however
many cores the machine has (window vectors are memoized per process).
"""
from __future__ import annotations

import argparse
import csv
import multiprocessing
import os
import pathlib
import time
from concurrent.futures import ProcessPoolExecutor


from repro.core import (
    adds_per_coeff,
    adds_per_tap,
    classical_equivalent_adds,
    fir_blmac_additions_batch,
    po2_quantize_batch,
)
from repro.filters import sweep_bank, sweep_specs

KAISER_BETA = 8.0  # calibrated: B_55=123.4 vs paper 123.3; B_255=475.3 vs 474.7

# Paper §3.3 reference points for validation.
PAPER = {
    ("hamming", 55): 132.5,
    ("hamming", 255): 513.6,
    ("kaiser", 55): 123.3,
    ("kaiser", 255): 474.7,
}

OUT = pathlib.Path(__file__).resolve().parent / "out"


def _grid_row(args) -> dict:
    """One (window, tap count) cell of the sweep grid — a self-contained
    unit of work, picklable for the ``--jobs`` process pool."""
    window_name, taps, n_div = args
    window = "hamming" if window_name == "hamming" else ("kaiser", KAISER_BETA)
    specs = sweep_specs(n_div)
    bank = sweep_bank(taps, n_div, window, specs)
    q, _ = po2_quantize_batch(bank, bits=16)
    adds = fir_blmac_additions_batch(q)
    return dict(
        window=window_name, taps=taps, n_filters=len(specs),
        mean=float(adds.mean()), std=float(adds.std()),
        min=int(adds.min()), max=int(adds.max()),
        adds_per_coeff=float(adds_per_coeff(adds, taps).mean()),
        adds_per_tap=float(adds_per_tap(adds, taps).mean()),
        classical_equiv=classical_equivalent_adds(taps),
    )


def _print_row(r: dict) -> None:
    print(f"  {r['window']:7s} N={r['taps']:3d}  B_N={r['mean']:6.1f}±{r['std']:5.1f} "
          f"[{r['min']},{r['max']}]  adds/coeff={r['adds_per_coeff']:.2f} "
          f"adds/tap={r['adds_per_tap']:.2f}  vs classical {r['classical_equiv']} "
          f"({r['classical_equiv']/r['mean']:.2f}x)")


def run_window(window_name: str, taps_list, n_div: int, verbose=True):
    rows = []
    for taps in taps_list:
        rows.append(_grid_row((window_name, taps, n_div)))
        if verbose:
            _print_row(rows[-1])
    return rows


def run(mode: str = "default", verbose: bool = True, jobs: int = 1):
    if mode == "full":
        taps_list, n_div = list(range(55, 256, 2)), 100
    elif mode == "fast":
        taps_list, n_div = [55, 127, 255], 40
    else:
        taps_list, n_div = [55, 75, 95, 127, 155, 191, 255], 100
    grid = [(w, t, n_div) for w in ("hamming", "kaiser") for t in taps_list]
    if jobs > 1:
        # spawn: a forked child must not inherit a TPU-holding parent
        with ProcessPoolExecutor(
            max_workers=jobs, mp_context=multiprocessing.get_context("spawn")
        ) as pool:
            all_rows = list(pool.map(_grid_row, grid, chunksize=1))
        if verbose:
            for r in all_rows:
                _print_row(r)
    else:
        all_rows = [r for w in ("hamming", "kaiser")
                    for r in run_window(w, taps_list, n_div, verbose)]
    OUT.mkdir(exist_ok=True)
    with open(OUT / f"fig34_sweep_{mode}.csv", "w", newline="") as f:
        wtr = csv.DictWriter(f, fieldnames=list(all_rows[0].keys()))
        wtr.writeheader()
        wtr.writerows(all_rows)
    # validation against the paper's reported end points — only strict on
    # the paper's own n_div=100 grid (coarser grids sample a different
    # filter population and sit ~2% off; that is grid choice, not error)
    checks = []
    strict = n_div == 100
    for (w, taps), want in PAPER.items():
        got = next((r["mean"] for r in all_rows
                    if r["window"] == w and r["taps"] == taps), None)
        if got is not None:
            rel = abs(got - want) / want
            checks.append((w, taps, got, want, rel, strict))
            if verbose:
                verdict = ("OK" if rel < 0.01 else "MISMATCH") if strict \
                    else f"(informational, n_div={n_div})"
                print(f"  check {w} N={taps}: B_N={got:.1f} paper={want}  "
                      f"rel.err={rel*100:.2f}% {verdict}")
    try:
        _plot(all_rows, mode)
    except Exception as e:  # matplotlib optional at runtime
        print("  (plot skipped:", e, ")")
    return all_rows, checks


def _plot(rows, mode):
    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, axes = plt.subplots(1, 2, figsize=(12, 4.5), sharey=True)
    for ax, w in zip(axes, ("hamming", "kaiser")):
        rs = [r for r in rows if r["window"] == w]
        taps = [r["taps"] for r in rs]
        ax.errorbar(taps, [r["mean"] for r in rs], yerr=[r["std"] for r in rs],
                    fmt="b.-", label="mean ± std")
        ax.plot(taps, [r["max"] for r in rs], "r.", label="max")
        ax.plot(taps, [r["min"] for r in rs], "g.", label="min")
        ax.set_title(f"BLMAC additions, {w} window (paper Fig. {3 if w=='hamming' else 4})")
        ax.set_xlabel("taps"); ax.grid(True); ax.legend()
    axes[0].set_ylabel("additions per filter application")
    fig.tight_layout()
    fig.savefig(OUT / f"fig34_{mode}.png", dpi=110)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="all 101 tap counts, n_div=100")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--jobs", type=int, default=0, metavar="N",
                    help="process-pool width for the (window, taps) grid; "
                         "0 = all cores, 1 = serial")
    a = ap.parse_args()
    jobs = a.jobs if a.jobs else (os.cpu_count() or 1)
    t0 = time.time()
    run("full" if a.full else "fast" if a.fast else "default", jobs=jobs)
    print(f"done in {time.time()-t0:.1f}s ({jobs} job{'s'[:jobs!=1]})")
