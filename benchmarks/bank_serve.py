"""Multi-tenant session serving vs dedicated per-session engines.

Each row serves ``n_sessions`` concurrent tenant streams — every session
selecting its own 4-filter slice of one compiled 256-filter lowpass bank
— two ways:

  * **shared**    — ONE `repro.serving.BankSessionServer` over the bank:
    all sessions' chunks are continuously batched into ``n_slots``
    shared channel lanes, ceil(n_sessions / n_slots) dispatches per
    round instead of one per tenant.
  * **dedicated** — the PR 6 shape scaled naively: one
    `FilterBankEngine` per session over the SAME `BlmacProgram`
    (construction is a content-addressed cache hit), one dispatch per
    tenant per chunk.

Both arms run identical kernel arithmetic per stream (the full bank per
lane, sliced to the session's rows), so the measured gap is pure
dispatch amortization — the thing the session layer exists to buy.
Every session's shared-arm stream is verified bit-exact against its
dedicated-arm stream BEFORE the row is reported: a fast-but-wrong
batcher is an assertion failure, not a good number.

Reported per row: aggregate output samples/s across all sessions, and
p50/p99 per-chunk latency (shared: push-to-resolved queue latency from
`serve_stats()`; dedicated: per-push wall time).

The committed ``BENCH_serve.json`` records the shared/dedicated speedup;
the CI gate (`--fast --check`) enforces BOTH floors on the same-run
ratio — shared must beat dedicated (> ``MIN_SPEEDUP``x), and must stay
within ``--tolerance`` of the committed speedup.  Same-run ratios cancel
host drift, so the gate is meaningful on any runner.

Usage:
  python benchmarks/bank_serve.py                    # full run, writes JSON
  python benchmarks/bank_serve.py --fast --check BENCH_serve.json  # CI gate
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

BANK_SIZE = 256
TAPS = 63
ROWS_PER_SESSION = 4
MIN_SPEEDUP = 1.0  # hard floor: shared-slot serving must beat dedicated
# (n_sessions, n_slots) grid — 64 tenants is the committed headline row
GRID = ((64, 8), (64, 16))
FAST_GRID = ((64, 16),)
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")
ARTIFACT_PATH = os.path.join(
    os.path.dirname(__file__), "out", "bank_serve_sessions.json"
)


def _pct(samples, q) -> float:
    return float(np.percentile(np.asarray(samples), q)) * 1e3


def _run_row(n_sessions: int, n_slots: int, n_chunks: int,
             chunk: int) -> dict:
    from repro.filters import FilterBankEngine, spread_lowpass_qbank
    from repro.compiler import compile_bank
    from repro.serving import BankSessionServer

    program = compile_bank(spread_lowpass_qbank(BANK_SIZE, TAPS))
    rng = np.random.default_rng(n_sessions * 1000 + n_slots)
    sels = [
        np.arange(i * ROWS_PER_SESSION, (i + 1) * ROWS_PER_SESSION)
        % BANK_SIZE
        for i in range(n_sessions)
    ]
    streams = [
        rng.integers(-128, 128, (n_chunks + 1) * chunk).astype(np.int32)
        for _ in range(n_sessions)
    ]

    # -- shared arm: one server, n_slots lanes, batched steps ---------------
    srv = BankSessionServer(
        program, n_slots=n_slots, chunk_hint=chunk, auto_step=False
    )
    sessions = [srv.open_session(sel) for sel in sels]
    shared_out = [[] for _ in range(n_sessions)]

    def shared_round(k: int) -> None:
        for i, s in enumerate(sessions):
            s.push(streams[i][k * chunk: (k + 1) * chunk])
        srv.step()
        for i, s in enumerate(sessions):
            shared_out[i].append(s.pull())

    shared_round(0)  # warm the jit caches off the clock
    warm_samples = srv.samples_out
    t0 = time.perf_counter()
    for k in range(1, n_chunks + 1):
        shared_round(k)
    shared_s = time.perf_counter() - t0
    shared_samples = srv.samples_out - warm_samples
    stats = srv.serve_stats()

    # -- dedicated arm: one engine per session over the same program --------
    engines = [
        FilterBankEngine(program, channels=1, chunk_hint=chunk)
        for _ in range(n_sessions)
    ]
    ded_out = [[] for _ in range(n_sessions)]
    push_s = []

    def dedicated_round(k: int, timed: bool) -> int:
        produced = 0
        for i, eng in enumerate(engines):
            t = time.perf_counter()
            y = eng.push(streams[i][None, k * chunk: (k + 1) * chunk])
            if timed:
                push_s.append(time.perf_counter() - t)
            out = y[sels[i], 0]
            produced += out.shape[1]
            ded_out[i].append(out)
        return produced

    dedicated_round(0, timed=False)  # same off-the-clock warm-up
    t0 = time.perf_counter()
    ded_samples = 0
    for k in range(1, n_chunks + 1):
        ded_samples += dedicated_round(k, timed=True)
    ded_s = time.perf_counter() - t0

    # -- bit-exactness gate BEFORE any number is reported -------------------
    for i in range(n_sessions):
        got = np.concatenate(shared_out[i], axis=1)
        want = np.concatenate(ded_out[i], axis=1)
        if not np.array_equal(got, want):
            raise AssertionError(
                f"shared session {i} != dedicated engine "
                f"(sessions={n_sessions}, slots={n_slots})"
            )
    if shared_samples != ded_samples:
        raise AssertionError("arms produced different sample counts")

    shared_rate = shared_samples / shared_s
    ded_rate = ded_samples / ded_s
    return {
        "n_sessions": n_sessions,
        "n_slots": n_slots,
        "bank_size": BANK_SIZE,
        "rows_per_session": ROWS_PER_SESSION,
        "taps": TAPS,
        "chunk_samples": chunk,
        "n_chunks": n_chunks,
        "occupancy": stats["occupancy"],
        "shared": {
            "samples_per_s": shared_rate,
            "latency_p50_ms": stats["latency_p50_ms"],
            "latency_p99_ms": stats["latency_p99_ms"],
            "dispatch_rounds": stats["rounds"],
        },
        "dedicated": {
            "samples_per_s": ded_rate,
            "latency_p50_ms": _pct(push_s, 50),
            "latency_p99_ms": _pct(push_s, 99),
            "dispatches": n_sessions * n_chunks,
        },
        "speedup": shared_rate / ded_rate,
    }


def run(grid=GRID, n_chunks: int = 6, chunk: int = 512,
        verbose: bool = True) -> dict:
    import jax

    from repro.kernels.runtime import default_interpret

    rows = []
    for n_sessions, n_slots in grid:
        row = _run_row(n_sessions, n_slots, n_chunks, chunk)
        rows.append(row)
        if verbose:
            print(f"sessions={n_sessions:3d} slots={n_slots:3d}  shared "
                  f"{row['shared']['samples_per_s']:10.0f} samp/s "
                  f"(p50 {row['shared']['latency_p50_ms']:6.1f} ms, p99 "
                  f"{row['shared']['latency_p99_ms']:6.1f} ms)  dedicated "
                  f"{row['dedicated']['samples_per_s']:10.0f} samp/s  "
                  f"speedup {row['speedup']:.2f}x")
    return {
        "benchmark": "bank_serve",
        "backend": jax.default_backend(),
        "interpret": default_interpret(),
        "min_speedup": MIN_SPEEDUP,
        "rows": rows,
        "note": (
            "shared = one BankSessionServer batching all sessions into "
            "n_slots lanes; dedicated = one FilterBankEngine per session "
            "over the same BlmacProgram; both arms run identical kernel "
            "arithmetic and every session is verified bit-exact "
            "shared-vs-dedicated before the row is reported, so speedup "
            "is pure dispatch amortization; the CI gate is same-run "
            "(shared vs dedicated measured in one process), so host "
            "speed cancels"
        ),
    }


def write_artifact(result: dict, path: str = ARTIFACT_PATH) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")


def check(result: dict, committed_path: str, tolerance: float) -> int:
    """Gate: every measured row's shared arm beats its dedicated arm
    (> MIN_SPEEDUP, the acceptance floor), and stays within ``tolerance``
    of the committed speedup for the same (sessions, slots) row."""
    with open(committed_path) as f:
        committed = json.load(f)
    if not result["rows"]:
        print("check FAILED: no rows ran")
        return 1
    base = {(r["n_sessions"], r["n_slots"]): r for r in committed["rows"]}
    status = 0
    for row in result["rows"]:
        key = (row["n_sessions"], row["n_slots"])
        sp = row["speedup"]
        flag = "OK" if sp > MIN_SPEEDUP else "REGRESSION"
        print(f"check sessions={key[0]} slots={key[1]} speedup {sp:.2f}x "
              f"> floor {MIN_SPEEDUP:.2f}x  {flag}")
        if flag != "OK":
            status = 1
        if key in base:
            old = base[key]["speedup"]
            floor = old / (1.0 + tolerance)
            flag = "OK" if sp >= floor else "REGRESSION"
            print(f"check sessions={key[0]} slots={key[1]} vs committed "
                  f"{old:.2f}x (allowed >= {floor:.2f}x)  {flag}")
            if flag != "OK":
                status = 1
    return status


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="reduced grid + shorter streams (CI; no JSON "
                         "rewrite)")
    ap.add_argument("--check", metavar="JSON",
                    help="compare against a committed BENCH_serve.json")
    ap.add_argument("--tolerance", type=float, default=1.0,
                    help="allowed shortfall vs the committed speedup "
                         "(speedup >= committed / (1 + tolerance))")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    if args.check and not os.path.exists(args.check):
        ap.error(f"baseline not found: {args.check}")
    grid = FAST_GRID if args.fast else GRID
    n_chunks = 3 if args.fast else 6
    chunk = 256 if args.fast else 512
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
