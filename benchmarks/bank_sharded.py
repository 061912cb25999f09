"""Sharded filter-bank scaling: BLMAC bank over 1→N forced host devices.

A B=256 lowpass bank (the BENCH_fir.json workload) is served through
`repro.filters.ShardedFilterBankEngine` at increasing bank-shard counts
on a (n, 1) device mesh.  Every arm is verified bit-exact against the
numpy oracle before any timing.

Methodology (critical-path rule): forced host-platform devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) all share the
host's physical cores, so concurrent wall-clock on them measures core
CONTENTION, not mesh scaling.  The scaling row therefore times each
shard's dispatch IN ISOLATION (`ShardedFilterBankEngine.time_shards`)
and reports the mesh critical path — the slowest shard — which is the
number a real N-device mesh is bounded by, exactly how the paper scales
Msamples/s by replicating independent 110-LUT machines.  The concurrent
wall-clock is also recorded per row (``concurrent_s``) for reference,
but is not the gated metric on a shared-core host.

Because the gated metric is a RATIO of arms, the arms are sampled
interleaved (every repeat touches all arms back-to-back, min per shard
across repeats): a co-tenant slowdown then degrades every arm alike
instead of skewing whichever arm it happened to land on.

The committed ``BENCH_sharded.json`` is the baseline CI regresses
against: the gate compares the SAME-RUN scaling ratio (8-device
aggregate over the 1-device arm), which transfers across runner
hardware, and additionally enforces the absolute acceptance floor
``scaling >= 3.0`` at 8 devices.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/bank_sharded.py                  # full run, writes JSON
  ... bank_sharded.py --fast --check BENCH_sharded.json  # CI gate
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

BANK_SIZE = 256
TAPS = 63
DEVICE_ARMS = (1, 2, 4, 8)
SCALING_FLOOR = 3.0  # acceptance: >= 3x aggregate at 8 devices vs 1
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_sharded.json")
ARTIFACT_PATH = os.path.join(
    os.path.dirname(__file__), "out", "bank_sharded_scaling.json"
)


def _design_qbank(n_filters: int, taps: int) -> np.ndarray:
    from repro.filters import spread_lowpass_qbank

    return spread_lowpass_qbank(n_filters, taps)


def _setup_arm(qbank, x, ndev, devices, n_samples):
    from repro.distributed import bank_mesh
    from repro.filters import ShardedFilterBankEngine, fir_bit_layers_batch

    mesh = bank_mesh(ndev, 1, devices[:ndev])
    eng = ShardedFilterBankEngine(
        qbank, mesh=mesh, n_bank_shards=ndev, chunk_hint=n_samples
    )
    # bit-exact before any timing (the five-way differential runs the
    # full harness in tests; the benchmark still refuses to time a wrong
    # kernel)
    ref = fir_bit_layers_batch(x, qbank)[:, 0, :]
    y = eng.push(x)[:, 0, :]
    if not np.array_equal(y, ref):
        raise AssertionError(f"sharded arm mismatch at {ndev} devices")
    eng.reset()
    return eng


def run(n_samples: int = 8192, repeats: int = 3, arms=DEVICE_ARMS,
        verbose: bool = True) -> dict:
    import time

    import jax

    from repro.kernels.runtime import default_interpret

    devices = jax.devices()
    usable = [n for n in arms if n <= len(devices)]
    dropped = [n for n in arms if n > len(devices)]
    if dropped:
        print(f"NOTE: only {len(devices)} device(s) visible — skipping "
              f"arms {dropped} (run under XLA_FLAGS="
              f"--xla_force_host_platform_device_count={max(arms)})")
    qbank = _design_qbank(BANK_SIZE, TAPS)
    rng = np.random.default_rng(42)
    x = rng.integers(-128, 128, n_samples).astype(np.int32)
    n_out = n_samples - TAPS + 1
    engines = [_setup_arm(qbank, x, n, devices, n_samples) for n in usable]
    # INTERLEAVED timing: the gated metric is a ratio of arms, so every
    # repeat samples all arms back-to-back — a host slowdown then hits
    # every arm alike instead of skewing whichever arm it landed on
    # (sequential arms made the ratio track co-tenant noise, not code)
    shard_s = [None] * len(engines)
    for _ in range(repeats):
        for i, eng in enumerate(engines):
            t = eng.time_shards(x, repeats=1)
            shard_s[i] = t if shard_s[i] is None else np.minimum(shard_s[i], t)
    rows = []
    for eng, ndev, t in zip(engines, usable, shard_s):
        critical = float(t.max())
        # concurrent wall-clock for reference (shared-core contention)
        def run_concurrent():
            p = eng.push_async(x)
            jax.block_until_ready(p._shard_outs)
            eng.reset()

        run_concurrent()
        conc = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            run_concurrent()
            conc = min(conc, time.perf_counter() - t0)
        rows.append({
            "devices": ndev,
            "n_bank_shards": eng.n_bank_shards,
            "bank_size": qbank.shape[0],
            "n_samples": n_samples,
            "outputs_per_filter": n_out,
            "shard_modes": [p.mode for p in eng.plan.shard_plans],
            "imbalance": round(eng.partition.imbalance, 4),
            "critical_path_s": critical,
            "aggregate_samples_per_s_per_filter": n_out / critical,
            "concurrent_s": conc,
        })
        if verbose:
            print(f"devices={ndev:2d} shards={eng.n_bank_shards:2d} "
                  f"critical {critical * 1e3:8.1f} ms  aggregate "
                  f"{n_out / critical:12.0f} samples/s/filter  "
                  f"(concurrent {conc * 1e3:8.1f} ms, "
                  f"imbalance {eng.partition.imbalance:.2f})")
    base = rows[0]["aggregate_samples_per_s_per_filter"]
    for r in rows:
        r["scaling_vs_1dev"] = r["aggregate_samples_per_s_per_filter"] / base
    return {
        "benchmark": "bank_sharded",
        "backend": jax.default_backend(),
        "interpret": default_interpret(),
        "bank_size": BANK_SIZE,
        "taps": TAPS,
        "n_samples": n_samples,
        "scaling_floor": SCALING_FLOOR,
        "rows": rows,
        "note": (
            "critical-path methodology: forced host devices share cores, so "
            "each shard is timed in isolation and the row reports the mesh "
            "critical path (slowest shard) — the paper's replicated-machine "
            "scaling model; arms are sampled interleaved so the gated "
            "scaling ratio cancels host-speed drift; concurrent_s is the "
            "shared-core wall-clock, reported but not gated; rows are the "
            "conservative floor (lowest scaling) over repeated runs on the "
            "reference machine"
        ),
    }


def write_artifact(result: dict, path: str = ARTIFACT_PATH) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")


def check(result: dict, committed_path: str, tolerance: float) -> int:
    """Gate: the max-device arm must (a) clear the absolute >= 3x scaling
    floor and (b) not regress > tolerance vs the committed same-run
    scaling ratio.  Ratios are measured within one run, so the gate
    transfers across runner hardware."""
    with open(committed_path) as f:
        committed = json.load(f)
    rows = {r["devices"]: r for r in result["rows"]}
    top = max(rows)
    if top < max(DEVICE_ARMS):
        print(f"check FAILED: need the {max(DEVICE_ARMS)}-device arm, "
              f"largest measured was {top} (set XLA_FLAGS)")
        return 1
    status = 0
    scaling = rows[top]["scaling_vs_1dev"]
    flag = "OK" if scaling >= SCALING_FLOOR else "REGRESSION"
    print(f"check devices={top} scaling floor: {scaling:.2f}x >= "
          f"{SCALING_FLOOR:.1f}x required  {flag}")
    if flag != "OK":
        status = 1
    base = {r["devices"]: r for r in committed["rows"]}
    for n, row in sorted(rows.items()):
        if n not in base or n == 1:
            continue
        old = base[n]["scaling_vs_1dev"]
        new = row["scaling_vs_1dev"]
        ratio = new / old
        flag = "OK" if ratio >= 1.0 - tolerance else "REGRESSION"
        print(f"check devices={n} scaling: {new:.2f}x vs committed "
              f"{old:.2f}x ({ratio:.2f}x)  {flag}")
        if flag != "OK":
            status = 1
    return status


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="short signal + 1-vs-8 arms only (CI; no JSON "
                         "rewrite)")
    ap.add_argument("--check", metavar="JSON",
                    help="compare against a committed BENCH_sharded.json")
    ap.add_argument("--tolerance", type=float, default=0.3)
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    if args.check and not os.path.exists(args.check):
        ap.error(f"baseline not found: {args.check}")
    n_samples = 4096 if args.fast else 8192
    if args.check:
        # scaling ratios are only comparable at the committed signal
        # length
        with open(args.check) as f:
            n_samples = json.load(f)["n_samples"]
    repeats = 5 if args.fast else 7
    arms = (1, max(DEVICE_ARMS)) if args.fast else DEVICE_ARMS
    result = run(n_samples=n_samples, repeats=repeats, arms=arms)
    write_artifact(result)
    if args.check:
        return check(result, args.check, args.tolerance)
    if not args.fast:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(f"wrote {os.path.normpath(args.out)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
