"""Fault-recovery latency of the sharded filter-bank service.

Each row streams a lowpass bank through `ShardedFilterBankEngine` behind
`AsyncBankServer` on an (n, 1) forced-host-device mesh, kills one bank
shard mid-stream with a deterministic
`repro.distributed.faultbank.FaultInjector`, and measures what the
recovery path costs:

  * ``recovery_s``     — detection → recovered-mesh wall time (the
    engine's ``last_recovery_s``: drop the dead row, cost-model the
    re-partition, rebuild the dispatch closures, replay every in-flight
    chunk from its tail snapshot),
  * ``stall_s``        — the worst single ``submit``/``drain`` step of
    the faulted stream (the one that absorbed detection + recovery),
    next to the median step as the no-fault reference,
  * ``replayed_chunks`` / ``replayed_samples`` — the deterministic
    replay volume behind bit-exactness.

Every row is verified bit-exact against the numpy oracle BEFORE its
numbers are reported: a recovery that loses or corrupts samples is an
assertion failure, not a slow row.

The committed ``BENCH_fault.json`` is the smoke baseline CI gates
against.  Wall-clock recovery latency is host-speed dependent (it
re-plans the mesh and recompiles shard kernels), so the gate is
deliberately loose: every
row must (a) recover bit-exactly with the expected counters, (b) keep
``recovery_s`` under the absolute smoke ceiling, and (c) stay within
``--tolerance`` (a multiple, default 4x) of the committed latency.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/bank_fault.py                # full run, writes JSON
  ... bank_fault.py --fast --check BENCH_fault.json  # CI gate
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

TAPS = 63
KILL_SHARD = 1
KILL_CHUNK = 3
RECOVERY_CEILING_S = 30.0  # absolute smoke ceiling per recovery
# (bank_size, n_bank_shards) grid; the 8-shard arm is the BENCH_sharded
# workload losing one of its machines
GRID = ((64, 4), (256, 4), (256, 8))
FAST_GRID = ((64, 4), (256, 8))
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_fault.json")
ARTIFACT_PATH = os.path.join(
    os.path.dirname(__file__), "out", "bank_fault_recovery.json"
)


def _run_row(bank_size: int, n_shards: int, n_chunks: int,
             chunk: int) -> dict:
    from repro.distributed import bank_mesh
    from repro.distributed.faultbank import FaultInjector
    from repro.filters import (ShardedFilterBankEngine, fir_bit_layers_batch,
                               spread_lowpass_qbank)
    from repro.serving import AsyncBankServer

    qbank = spread_lowpass_qbank(bank_size, TAPS)
    rng = np.random.default_rng(bank_size + n_shards)
    x = rng.integers(-128, 128, n_chunks * chunk).astype(np.int32)
    ref = fir_bit_layers_batch(x, qbank)[:, 0, :]

    injector = FaultInjector().kill_shard(KILL_SHARD, at_chunk=KILL_CHUNK)
    eng = ShardedFilterBankEngine(
        qbank, mesh=bank_mesh(n_shards, 1), n_bank_shards=n_shards,
        chunk_hint=chunk, fault_injector=injector,
    )
    server = AsyncBankServer(eng, depth=2)
    # warm the jit caches so the recovery row does not bill compilation
    # of the HEALTHY mesh to the fault path
    eng.push(x[:chunk])
    eng.reset()

    got, step_s = [], []
    for k in range(n_chunks):
        t0 = time.perf_counter()
        got += server.submit(x[k * chunk:(k + 1) * chunk])
        step_s.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    got += server.drain()
    step_s.append(time.perf_counter() - t0)

    y = np.concatenate([g for g in got if g.shape[2]], axis=2)[:, 0, :]
    if not np.array_equal(y, ref):
        raise AssertionError(
            f"recovered stream != oracle (B={bank_size}, shards={n_shards})"
        )
    st = eng.fault_stats()
    if not (st["recoveries"] == 1 and st["lost_shards"] == 1
            and server.failed_chunks == 0):
        raise AssertionError(f"unexpected fault counters: {st}")
    return {
        "bank_size": bank_size,
        "n_bank_shards": n_shards,
        "recovered_shards": eng.n_bank_shards,
        "taps": TAPS,
        "n_chunks": n_chunks,
        "chunk_samples": chunk,
        "kill": [KILL_SHARD, KILL_CHUNK],
        "recovery_s": st["last_recovery_s"],
        "stall_s": max(step_s),
        "median_step_s": float(np.median(step_s)),
        "replayed_chunks": st["replayed_chunks"],
        "replayed_samples": st["replayed_samples"],
        "detections": st["detections"],
    }


def run(grid=GRID, n_chunks: int = 8, chunk: int = 4096,
        verbose: bool = True) -> dict:
    import jax

    from repro.kernels.runtime import default_interpret

    n_dev = len(jax.devices())
    rows = []
    for bank_size, n_shards in grid:
        if n_shards > n_dev:
            print(f"NOTE: only {n_dev} device(s) visible — skipping "
                  f"(B={bank_size}, shards={n_shards}) (run under XLA_FLAGS="
                  f"--xla_force_host_platform_device_count={n_shards})")
            continue
        row = _run_row(bank_size, n_shards, n_chunks, chunk)
        rows.append(row)
        if verbose:
            print(f"B={bank_size:4d} shards={n_shards} -> "
                  f"{row['recovered_shards']}  recovery "
                  f"{row['recovery_s'] * 1e3:8.1f} ms  stall "
                  f"{row['stall_s'] * 1e3:8.1f} ms (median step "
                  f"{row['median_step_s'] * 1e3:6.1f} ms)  replayed "
                  f"{row['replayed_chunks']} chunks")
    return {
        "benchmark": "bank_fault",
        "backend": jax.default_backend(),
        "interpret": default_interpret(),
        "taps": TAPS,
        "recovery_ceiling_s": RECOVERY_CEILING_S,
        "rows": rows,
        "note": (
            "recovery_s is detection -> recovered mesh (re-partition over the "
            "survivors, rebuilt dispatch closures, bit-exact replay of every "
            "in-flight chunk from its tail snapshot); every row is verified "
            "bit-exact against the numpy oracle before it is reported; "
            "latency re-plans the mesh so the CI gate is a loose "
            "smoke bound (absolute ceiling + a generous multiple of the "
            "committed row), not a tight regression ratio"
        ),
    }


def write_artifact(result: dict, path: str = ARTIFACT_PATH) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")


def check(result: dict, committed_path: str, tolerance: float) -> int:
    """Gate: every measured row recovered (bit-exactness and counters are
    asserted inside the run), under the absolute smoke ceiling, and within
    ``tolerance`` x the committed recovery latency for the same row."""
    with open(committed_path) as f:
        committed = json.load(f)
    if not result["rows"]:
        print("check FAILED: no rows ran (set XLA_FLAGS to force devices)")
        return 1
    base = {
        (r["bank_size"], r["n_bank_shards"]): r for r in committed["rows"]
    }
    status = 0
    for row in result["rows"]:
        key = (row["bank_size"], row["n_bank_shards"])
        rec = row["recovery_s"]
        flag = "OK" if 0.0 < rec <= RECOVERY_CEILING_S else "REGRESSION"
        print(f"check B={key[0]} shards={key[1]} recovery "
              f"{rec * 1e3:.1f} ms <= ceiling "
              f"{RECOVERY_CEILING_S:.0f} s  {flag}")
        if flag != "OK":
            status = 1
        if key in base:
            old = base[key]["recovery_s"]
            ratio = rec / old if old > 0 else float("inf")
            flag = "OK" if ratio <= 1.0 + tolerance else "REGRESSION"
            print(f"check B={key[0]} shards={key[1]} vs committed "
                  f"{old * 1e3:.1f} ms ({ratio:.2f}x, "
                  f"allowed {1.0 + tolerance:.1f}x)  {flag}")
            if flag != "OK":
                status = 1
    return status


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="reduced grid + shorter stream (CI; no JSON "
                         "rewrite)")
    ap.add_argument("--check", metavar="JSON",
                    help="compare against a committed BENCH_fault.json")
    ap.add_argument("--tolerance", type=float, default=4.0,
                    help="allowed recovery-latency multiple vs committed")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    if args.check and not os.path.exists(args.check):
        ap.error(f"baseline not found: {args.check}")
    grid = FAST_GRID if args.fast else GRID
    n_chunks = 6 if args.fast else 8
    chunk = 2048 if args.fast else 4096
    result = run(grid=grid, n_chunks=n_chunks, chunk=chunk)
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
