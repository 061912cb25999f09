"""Serving launcher: batched greedy LM generation, or a device-sharded
BLMAC filter-bank stream.

LM serving (prefill + decode steps)::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --batch 4 --prompt-len 32 --new-tokens 16

Optionally applies BLMAC CSD-P pulse-code quantization to the checkpoint
before serving (`--quant-planes P`) — the paper's variable-precision dot
product as a deployment feature (weights stored/streamed at P pulses).

FIR bank serving (the paper's workload, sharded over every visible XLA
device and double-buffered through `repro.serving.AsyncBankServer`)::

    PYTHONPATH=src python -m repro.launch.serve --fir-bank 256 \
        --taps 63 --channels 1 --chunk 4096 --chunks 32

It ends with the engine's ``push_stats()``: the share of the computed
outputs that reached the caller and the bytes read back per push.
Run it under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to
exercise the mesh path on a CPU host.  ``--program-path bank.npz``
round-trips the compiled `repro.compiler.BlmacProgram` through disk:
the first run compiles and saves, every later run warm-starts from the
file (no re-quantization, CSD encoding or trit packing at startup).

Multi-tenant session serving (N user streams, each on its own filter
selection, continuously batched into the shared lanes of ONE
`repro.serving.BankSessionServer`)::

    PYTHONPATH=src python -m repro.launch.serve --fir-bank 256 \
        --taps 63 --sessions 64 --slots 8 --chunk 512 --chunks 16

Exercises one mid-run `swap_filters` hot-swap and one pause/resume,
spot-checks a session against the numpy oracle, and prints the
`serve_stats()` surface (occupancy, queue depth, p50/p99 latency, and
the share of computed filter rows that sessions selected).

``--journal-path wal/`` makes the session server crash-safe: every
push/pull/registry change is written ahead to a CRC-framed journal and
`BankSessionServer.recover(path)` rebuilds every tenant bit-exactly
after a SIGKILL (see ``examples/session_recovery.py`` for the
kill-and-resume demo).  ``--bank-shards K`` runs the same session layer
ON TOP of a K-way `ShardedFilterBankEngine` (sessions × shards): lane
dispatches go through the sharded mesh and inherit its shard-loss
recovery.
"""
from __future__ import annotations

import argparse
import time


def serve_sessions(args) -> None:
    """--sessions path: N tenant streams over one compiled bank."""
    import numpy as np

    from repro.compiler import compile_bank
    from repro.filters import fir_bit_layers_batch, spread_lowpass_qbank
    from repro.serving import BankSessionServer

    n, n_sessions = args.fir_bank, args.sessions
    program = compile_bank(spread_lowpass_qbank(n, args.taps))
    engine = None
    if args.bank_shards:
        from repro.filters import ShardedFilterBankEngine

        engine = ShardedFilterBankEngine(
            program,
            channels=args.slots,
            n_bank_shards=args.bank_shards,
        )
        print(f"[serve] sessions × shards: {engine.describe()}")
    server = BankSessionServer(
        program,
        n_slots=args.slots,
        auto_step=False,
        engine=engine,
        journal=args.journal_path or None,
    )
    if args.journal_path:
        print(f"[serve] journaling session state to {args.journal_path}")
    rng = np.random.default_rng(0)
    # each session selects a distinct contiguous row slice of the bank
    per = max(1, n // n_sessions)
    selections = [
        np.arange((i * per) % n, (i * per) % n + per) for i in range(n_sessions)
    ]
    sessions = [server.open_session(sel) for sel in selections]
    streams = [
        rng.integers(-128, 128, args.chunk * args.chunks).astype(np.int32)
        for _ in range(n_sessions)
    ]
    outs = [[] for _ in range(n_sessions)]
    paused = None
    t0 = time.time()
    for k in range(args.chunks):
        if k == args.chunks // 3 and n_sessions > 1:
            # mid-run zero-downtime selection hot-swap on session 1
            outs[1].append(sessions[1].swap_filters(selections[1]))
        if k == args.chunks // 2 and n_sessions > 2:
            paused = (2, sessions[2].pause())  # park tenant 2 mid-stream
        for i, s in enumerate(sessions):
            if paused and i == paused[0]:
                continue
            s.push(streams[i][k * args.chunk: (k + 1) * args.chunk])
        server.step()
        if paused and k == args.chunks // 2:
            # …and resume it immediately: bit-exact continuation
            sessions[paused[0]] = server.resume_session(
                paused[1], selections[paused[0]]
            )
        for i, s in enumerate(sessions):
            out = s.pull()
            if out.shape[1]:
                outs[i].append(out)
    # feed the paused session the chunks it missed, then drain everyone
    if paused:
        i = paused[0]
        missed = streams[i][(args.chunks // 2) * args.chunk:]
        sessions[i].push(missed)
    server.step()
    for i, s in enumerate(sessions):
        out = s.pull()
        if out.shape[1]:
            outs[i].append(out)
    dt = time.time() - t0
    stats = server.serve_stats()
    agg = stats["samples_out"]
    print(f"[serve] sessions: {n_sessions} tenants × {per} filters over a "
          f"{n}-filter bank, {args.slots} shared lanes")
    print(f"[serve] {agg} output samples in {dt:.2f}s "
          f"({agg / dt:.0f} samples/s aggregate), "
          f"occupancy {stats['occupancy']:.2f}, "
          f"rounds {stats['rounds']}, "
          f"p50 {stats['latency_p50_ms']:.1f}ms / "
          f"p99 {stats['latency_p99_ms']:.1f}ms")
    if stats["rows_computed"]:
        print(f"[serve] useful rows: {stats['rows_used']} of "
              f"{stats['rows_computed']} computed "
              f"({100 * stats['rows_used'] / stats['rows_computed']:.1f}%)")
    # spot-check one full session stream against the exact numpy oracle
    check = 0
    got = np.concatenate(outs[check], axis=1)
    ref = fir_bit_layers_batch(
        streams[check][None, :], program.qbank
    )[selections[check], 0]
    assert np.array_equal(got, ref), "session stream mismatch vs oracle"
    print(f"[serve] session {check} bit-exact vs numpy oracle "
          f"({got.shape[1]} samples × {got.shape[0]} filters)")
    if stats.get("journal"):
        j = stats["journal"]
        print(f"[serve] journal: {j['appends']} appends, {j['syncs']} "
              f"fsyncs, {j['rotations']} rotations, live segment "
              f"{j['segment_bytes']} bytes at {j['path']}")
    server.close()


def push_report(stats: dict) -> str:
    """One line from an engine's ``push_stats()``: the share of the
    outputs the device computed that reached the caller, and the bytes
    read back per push."""
    pushes, computed = stats["pushes"], stats["outputs_computed"]
    share = 100 * stats["outputs_delivered"] / computed if computed else 0.0
    per_push = stats["bytes_read_back"] / pushes if pushes else 0.0
    return (f"[serve] pushes: {pushes}, {stats['outputs_delivered']} of "
            f"{computed} computed outputs delivered ({share:.1f}%), "
            f"{per_push:.0f} bytes read back per push")


def serve_fir_bank(args) -> None:
    import os
    import numpy as np

    from repro.compiler import BlmacProgram, ProgramFormatError, compile_bank
    from repro.filters import (ShardedFilterBankEngine, fir_bit_layers_batch,
                               spread_lowpass_qbank)
    from repro.serving import AsyncBankServer

    n = args.fir_bank
    qbank = spread_lowpass_qbank(n, args.taps)
    # warm-start: load the compiled program if a previous serving process
    # saved one for this bank; otherwise compile once and save it
    program = None
    if args.program_path and os.path.exists(args.program_path):
        try:
            cand = BlmacProgram.load(args.program_path)
            if np.array_equal(cand.qbank, qbank):
                program = cand
                print(f"[serve] warm-start: loaded compiled program "
                      f"{program.key[:12]}… from {args.program_path}")
            else:
                print(f"[serve] {args.program_path} is for a different "
                      f"bank; recompiling")
        except ProgramFormatError as e:
            print(f"[serve] ignoring stale program file: {e}")
    if program is None:
        program = compile_bank(qbank)
        if args.program_path:
            program.save(args.program_path)
            print(f"[serve] saved compiled program to {args.program_path}")
    engine = ShardedFilterBankEngine(program, channels=args.channels)
    print(f"[serve] {engine.describe()}")
    server = AsyncBankServer(engine, depth=args.depth)
    rng = np.random.default_rng(0)
    stream = rng.integers(
        -128, 128, (args.channels, args.chunk * args.chunks)
    ).astype(np.int32)
    done = 0
    t0 = time.time()
    for k in range(args.chunks):
        chunk = stream[:, k * args.chunk: (k + 1) * args.chunk]
        for out in server.submit(chunk):
            done += out.shape[2]
    outs = server.drain()
    done += sum(o.shape[2] for o in outs)
    dt = time.time() - t0
    print(f"[serve] fir-bank: {done} samples/filter/channel in {dt:.2f}s "
          f"({done / dt:.0f} samples/s/filter, "
          f"{done * n * args.channels / dt:.3e} filter-samples/s aggregate)")
    print(push_report(engine.push_stats()))
    # spot-check the tail chunk against the exact oracle
    if outs and outs[-1].shape[2]:
        t = args.taps
        tail_in = stream[:, -(outs[-1].shape[2] + t - 1):]
        ref = fir_bit_layers_batch(tail_in, qbank)
        assert np.array_equal(outs[-1], ref), "sharded serve output mismatch"
        print("[serve] tail chunk bit-exact vs numpy oracle")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="LM architecture (omit with --fir-bank)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--quant-planes", type=int, default=0,
                    help="CSD-P pulse-code weight quantization (0 = off)")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--fir-bank", type=int, default=0, metavar="B",
                    help="serve a B-filter BLMAC bank instead of an LM")
    ap.add_argument("--taps", type=int, default=63)
    ap.add_argument("--channels", type=int, default=1)
    ap.add_argument("--chunk", type=int, default=4096,
                    help="samples per request chunk (fir-bank mode)")
    ap.add_argument("--chunks", type=int, default=32)
    ap.add_argument("--depth", type=int, default=2,
                    help="async double-buffer depth (fir-bank mode)")
    ap.add_argument("--sessions", type=int, default=0, metavar="N",
                    help="serve N multi-tenant session streams over the "
                         "bank (fir-bank mode) instead of one sharded "
                         "stream")
    ap.add_argument("--slots", type=int, default=8,
                    help="shared batching lanes of the session server")
    ap.add_argument("--journal-path", default="",
                    help="write-ahead session journal directory (sessions "
                         "mode): makes the server crash-safe via "
                         "BankSessionServer.recover()")
    ap.add_argument("--bank-shards", type=int, default=0, metavar="K",
                    help="run the session lanes on a K-way sharded filter "
                         "bank engine (sessions mode, 0 = plain engine)")
    ap.add_argument("--program-path", default="",
                    help="compiled-program cache file (fir-bank mode): "
                         "load it to warm-start, write it after compiling")
    args = ap.parse_args()

    from repro.kernels.runtime import use_compilation_cache

    use_compilation_cache()
    if args.fir_bank and args.sessions:
        serve_sessions(args)
        return
    if args.fir_bank:
        serve_fir_bank(args)
        return
    if not args.arch:
        ap.error("--arch is required unless --fir-bank is given")

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.nn import init_params, model_decls
    from repro.serving import ServeEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.input_kind == "embeds":
        import dataclasses

        cfg = dataclasses.replace(cfg, input_kind="tokens")
    params = init_params(model_decls(cfg), jax.random.key(0))
    if args.quant_planes:
        from repro.core.serve_quant import quantize_param_tree

        params, stats = quantize_param_tree(params, args.quant_planes)
        print(f"[serve] CSD-{args.quant_planes} quantized "
              f"{stats['n_quantized']} matrices, mean rel err "
              f"{stats['mean_rel_err']:.4f}, stored bits/weight "
              f"{stats['bits_per_weight']:.1f}")
    eng = ServeEngine(cfg, params, cache_len=args.cache_len)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.time()
    out = eng.generate(prompts, max_new_tokens=args.new_tokens)
    dt = time.time() - t0
    print(f"[serve] {args.arch}: generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print(np.asarray(out)[:2])


if __name__ == "__main__":
    main()
