#!/usr/bin/env python3
"""On-chip smoke test of the BLMAC filter-bank serving path.

Drives the paper's configuration (`repro.configs.fir127`: 127 taps,
16-bit coefficients, 8-bit samples) through the normal entry points on a
TPU, compiled with Mosaic, and checks every phase bit-exact against the
numpy Eq. 2 oracle `repro.filters.fir_bit_layers_batch`:

  * stream   — `compile_bank` → `FilterBankEngine(program, channels=8)`
    with default arguments, 8 chunks of 16,384 samples per channel;
  * sweep    — the paper's 9,900-filter §3.1 sweep at 127 taps on one
    16,384-sample channel (every occupancy tile group, ~650 MB of int32
    output on the device);
  * sessions — a journalled `BankSessionServer`, 64 sessions × 4 rows
    over 8 shared lanes.

``--chips 4`` runs only the sharded path instead: the stream bank on a
4-way bank-sharded `ShardedFilterBankEngine`, and one leg with the data
axis in ``"time"`` mode (halo exchange), each against the single-device
engine and the oracle.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips of one host

Times printed here are smoke observations, not benchmark numbers.  The
script refuses to run anywhere but a TPU, and its last line is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
CHUNK = 16384  # samples per channel per push
SEED = 0


def device_check(chips: int):
    """The chip, or exit non-zero: never carry on on another platform."""
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {d.platform!r} "
                 f"({d.device_kind})")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"found {len(devices)}")
    return devices


def oracle_check(y, x, qbank, rows, channels, what):
    """``y[rows][:, channels]`` equals the oracle on signal ``x``; rows
    and channels pair up one to one (a list of rows per channel)."""
    from repro.filters import fir_bit_layers_batch

    for c, r in zip(channels, rows):
        ref = fir_bit_layers_batch(x[c:c + 1], qbank[r])[:, 0]
        if not np.array_equal(y[r, c], ref):
            raise AssertionError(f"{what}: channel {c} differs from the oracle")


def stream_phase(taps: int, rng) -> "object":
    import jax
    import jax.numpy as jnp

    from repro.compiler import compile_bank
    from repro.filters import FilterBankEngine, spread_lowpass_qbank
    from repro.kernels.blmac_fir import _bank_call, frame_signal_batch
    from repro.kernels.runtime import resolve_interpret

    n_chan, n_chunks = 8, 8
    program = compile_bank(spread_lowpass_qbank(256, taps))
    eng = FilterBankEngine(program, channels=n_chan)
    p = eng.dispatch_plan
    interpret = resolve_interpret(eng.interpret)
    print(f"[stream] plan mode={p.mode} merge={p.merge} "
          f"bank_tile={p.bank_tile} tile={p.tile} "
          f"pallas_interpret={interpret} tile_groups="
          f"{len(eng.bank_schedule.groups)}", flush=True)
    assert p.mode == "scheduled" and not interpret, "not the Mosaic kernel"

    x = rng.integers(-128, 128, (n_chan, n_chunks * CHUNK), dtype=np.int8)
    outs, secs = [], []
    for k in range(n_chunks):
        t0 = time.perf_counter()
        outs.append(eng.push(x[:, k * CHUNK:(k + 1) * CHUNK]))
        secs.append(time.perf_counter() - t0)
    print(f"[stream] first push (compile) {secs[0]:.3f} s; steady push "
          f"median {np.median(secs[2:]):.4f} s (min {min(secs[2:]):.4f}) "
          f"per {n_chan}x{CHUNK}-sample chunk, host readback included",
          flush=True)
    assert sum(o.shape[2] for o in outs) == n_chunks * CHUNK - taps + 1
    # every filter row and every channel, on whole chunks: channel c is
    # checked against rows c, c+8, …  on the priming chunk and mid-stream
    rows = [np.arange(c, 256, n_chan) for c in range(n_chan)]
    oracle_check(outs[0], x[:, :CHUNK], program.qbank, rows, range(n_chan),
                 "stream chunk 0")
    k = n_chunks // 2
    oracle_check(outs[k], x[:, k * CHUNK - taps + 1:(k + 1) * CHUNK],
                 program.qbank, rows, range(n_chan), f"stream chunk {k}")
    print(f"[stream] bit-exact vs oracle: chunks 0 and {k}, all 256 rows, "
          f"all {n_chan} channels", flush=True)

    # the steady push's kernel, as the engine dispatched it
    sched, tile = eng.bank_schedule, eng.tile
    g = sched.groups[0]
    buf = x[:, (n_chunks - 1) * CHUNK - taps + 1:].astype(np.int32)
    n_pad = -(-buf.shape[1] // tile) * tile
    frames, _ = frame_signal_batch(
        jnp.asarray(np.pad(buf, ((0, 0), (0, n_pad - buf.shape[1])))),
        taps, tile,
    )
    op = jnp.asarray(g.packed.view(np.int32))
    static = dict(taps=taps, schedule=g.schedule, tail_shift=g.tail_shift,
                  tile=tile, bank_tile=sched.tile_size, interpret=interpret)
    text = _bank_call.lower(frames, op, **static).compile().as_text()
    has_kernel = "tpu_custom_call" in text
    print(f"[stream] compiled step contains tpu_custom_call: {has_kernel}",
          flush=True)
    assert has_kernel, "the bank step did not compile to a Mosaic kernel"
    jax.block_until_ready(_bank_call(frames, op, **static))
    ksecs = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(_bank_call(frames, op, **static))
        ksecs.append(time.perf_counter() - t0)
    print(f"[stream] bank kernel step {np.median(ksecs):.5f} s median of 5 "
          f"(block_until_ready, {frames.shape[0]}x{frames.shape[1]} tiles)",
          flush=True)
    return program


def sweep_phase(taps: int, rng) -> None:
    from repro.configs.fir127 import CONFIG
    from repro.core import po2_quantize_batch
    from repro.filters import FilterBankEngine, sweep_bank

    bank = sweep_bank(taps, CONFIG.n_div, CONFIG.window)  # no process pool
    q, _ = po2_quantize_batch(bank, bits=CONFIG.coeff_bits)
    eng = FilterBankEngine(q, channels=1)
    sched = eng.bank_schedule
    x = rng.integers(-128, 128, (1, CHUNK), dtype=np.int8)
    t0 = time.perf_counter()
    y = eng.push(x)
    dt = time.perf_counter() - t0
    print(f"[sweep] {q.shape[0]} filters x {CHUNK} samples: "
          f"{len(sched.groups)} tile groups, output {y.nbytes / 1e6:.0f} MB "
          f"int32, first push (compiles included) {dt:.2f} s", flush=True)
    assert y.shape == (q.shape[0], 1, CHUNK - taps + 1), y.shape
    # a seeded sample of rows from every tile group
    rows, lo = [], 0
    for g in sched.groups:
        slots = lo + rng.choice(g.n_filters, min(4, g.n_filters), False)
        rows.extend(sched.perm[slots])
        lo += g.packed.shape[0]
    rows = np.asarray(rows)
    oracle_check(y, x, eng.qbank, [rows], [0], "sweep")
    print(f"[sweep] bit-exact vs oracle: {len(rows)} rows sampled from all "
          f"{len(sched.groups)} tile groups", flush=True)


def sessions_phase(program, rng) -> None:
    from repro.filters import fir_bit_layers_batch
    from repro.serving import BankSessionServer

    n_sessions, per, chunk, steps = 64, 4, 2048, 4
    with tempfile.TemporaryDirectory() as tmp:
        server = BankSessionServer(
            program, n_slots=8, auto_step=False, chunk_hint=chunk,
            journal=os.path.join(tmp, "wal"),
        )
        sessions = [server.open_session(np.arange(per * i, per * (i + 1)))
                    for i in range(n_sessions)]
        x = rng.integers(-128, 128, (n_sessions, steps * chunk),
                         dtype=np.int8)
        outs = [[] for _ in range(n_sessions)]
        t0 = time.perf_counter()
        for k in range(steps):
            for i, s in enumerate(sessions):
                s.push(x[i, k * chunk:(k + 1) * chunk])
            server.step()
            for i, s in enumerate(sessions):
                outs[i].append(s.pull())
        dt = time.perf_counter() - t0
        stats = server.serve_stats()
        server.close()
    print(f"[sessions] {n_sessions} sessions x {per} rows, 8 lanes, journal "
          f"on: {steps} steps, {stats['rounds']} rounds in {dt:.2f} s",
          flush=True)
    check = int(rng.integers(n_sessions))
    got = np.concatenate(outs[check], axis=1)
    ref = fir_bit_layers_batch(
        x[check:check + 1], program.qbank[per * check:per * (check + 1)]
    )[:, 0]
    assert np.array_equal(got, ref), f"session {check} differs from oracle"
    print(f"[sessions] session {check} bit-exact vs oracle "
          f"({got.shape[0]} rows x {got.shape[1]} samples)", flush=True)


def sharded_phase(devices, taps: int, rng) -> None:
    from repro.compiler import compile_bank
    from repro.distributed import bank_mesh
    from repro.filters import (FilterBankEngine, ShardedFilterBankEngine,
                               spread_lowpass_qbank)

    program = compile_bank(spread_lowpass_qbank(256, taps))
    n = len(devices)
    legs = (
        ("bank", 8, bank_mesh(n, 1, devices=devices),
         dict(n_bank_shards=n)),
        ("time", 1, bank_mesh(1, n, devices=devices),
         dict(data_mode="time")),
    )
    for name, n_chan, mesh, kw in legs:
        single = FilterBankEngine(program, channels=n_chan)
        sharded = ShardedFilterBankEngine(program, channels=n_chan,
                                          mesh=mesh, **kw)
        print(f"[{name}] {sharded.describe()}", flush=True)
        assert (sharded.n_bank_shards, sharded.n_data) == (
            (n, 1) if name == "bank" else (1, n)
        ) and sharded.data_mode == ("none" if name == "bank" else "time")
        x = rng.integers(-128, 128, (n_chan, 4 * CHUNK), dtype=np.int8)
        secs = []
        for k in range(4):
            chunk = x[:, k * CHUNK:(k + 1) * CHUNK]
            t0 = time.perf_counter()
            pend = sharded.push_async(chunk)
            placed = {d for y in pend._shard_outs for d in y.devices()}
            y = pend.result()
            secs.append(time.perf_counter() - t0)
            assert placed == set(devices), f"{name}: shards on {placed}"
            assert np.array_equal(y, single.push(chunk)), (
                f"{name}: chunk {k} differs from the single-device engine")
        rows = [np.arange(c, 256, n_chan) for c in range(n_chan)]
        oracle_check(y, x[:, 3 * CHUNK - taps + 1:], program.qbank, rows,
                     range(n_chan), f"{name} chunk 3")
        print(f"[{name}] bit-exact vs single-device engine (4 chunks) and "
              f"oracle (chunk 3, all rows); on all {n} devices; first push "
              f"{secs[0]:.2f} s, later median {np.median(secs[1:]):.4f} s",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded four-chip path")
    args = ap.parse_args(argv)

    devices = device_check(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs.fir127 import CONFIG
    from repro.kernels.runtime import use_compilation_cache

    print(f"[cache] jax compilation cache: {use_compilation_cache()}",
          flush=True)
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(devices[:4], CONFIG.taps, rng)
    else:
        program = stream_phase(CONFIG.taps, rng)
        sweep_phase(CONFIG.taps, rng)
        sessions_phase(program, rng)
    print(f"[done] all phases in {time.perf_counter() - t0:.1f} s",
          flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
