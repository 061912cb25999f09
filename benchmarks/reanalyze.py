"""Re-derive analysis columns of committed artifacts without re-measuring.

Two sections:

* **dryrun** — re-derive the §Roofline JSONs from the saved (gzipped)
  HLO — lets analyzer improvements update the table without recompiling
  68 cells.
* **cse** — re-derive `BENCH_cse.json`'s reduction columns
  (`adds_per_filter_*`, `adds_reduction`, `pulse_reduction`,
  `cycle_reduction`) from the stored raw totals (same formulas as
  `bank_cse.derive_sweep`).
"""
import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
from repro.roofline.hlo_analysis import analyze_hlo  # noqa: E402

PEAK, HBM_BW, ICI_BW = 197e12, 819e9, 50e9
OUT = os.path.join(os.path.dirname(__file__), "out", "dryrun")
BENCH_CSE = os.path.join(os.path.dirname(__file__), "..", "BENCH_cse.json")


def reanalyze_dryrun() -> None:
    for jf in sorted(glob.glob(os.path.join(OUT, "*.json"))):
        hf = jf[:-5] + ".hlo.txt.gz"
        if not os.path.exists(hf):
            continue
        r = json.load(open(jf))
        cost = analyze_hlo(gzip.open(hf, "rt").read())
        r["hlo_flops_per_dev"] = cost.flops
        r["hlo_hbm_bytes_per_dev"] = cost.hbm_bytes
        r["collective_bytes_per_dev"] = cost.total_coll_bytes
        r["collectives"] = cost.coll_bytes
        r["collective_counts"] = cost.coll_counts
        r["hbm_by_op"] = dict(
            sorted(cost.hbm_by_op.items(), key=lambda kv: -kv[1])[:12]
        )
        r["compute_term_s"] = cost.flops / PEAK
        r["memory_term_s"] = cost.hbm_bytes / HBM_BW
        r["collective_term_s"] = cost.total_coll_bytes / ICI_BW
        terms = {"compute": r["compute_term_s"], "memory": r["memory_term_s"],
                 "collective": r["collective_term_s"]}
        r["dominant"] = max(terms, key=terms.get)
        r["useful_flops_ratio"] = (r["model_flops_per_dev"] / cost.flops
                                   if cost.flops else 0.0)
        json.dump(r, open(jf, "w"), indent=1)
        print(os.path.basename(jf), "->", r["dominant"],
              f"c={r['compute_term_s']:.3f} m={r['memory_term_s']:.3f} "
              f"x={r['collective_term_s']:.3f}")


def reanalyze_cse(path: str = BENCH_CSE) -> None:
    """Recompute BENCH_cse.json's derived reduction columns from its
    stored raw totals (same formulas as bank_cse.run)."""
    if not os.path.exists(path):
        return
    from benchmarks.bank_cse import derive_sweep

    r = json.load(open(path))
    s = r["sweep"]
    s.update(derive_sweep(
        s["total_adds_parent"], s["total_adds_optimized"], s["n_filters"],
        s["total_pulses_parent"], s["total_pulses_optimized"],
        s["mean_cycles_parent"], s["mean_cycles_optimized"],
    ))
    with open(path, "w") as f:
        json.dump(r, f, indent=2)
        f.write("\n")
    print(os.path.basename(path), "->",
          f"adds_reduction={100 * s['adds_reduction']:.1f}%")


if __name__ == "__main__":
    reanalyze_dryrun()
    reanalyze_cse()
