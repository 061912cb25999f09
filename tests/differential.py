"""Five-way differential verification harness.

One bank, one signal, ONE compiled program (`repro.compiler.BlmacProgram`
— shared by every leg since the one-program refactor), five independent
implementations of the BLMAC dot product — proven bit-exact against
*each other*, not just individually plausible:

  1. **oracle**   — `repro.filters.fir_bit_layers_batch` (numpy, Eq. 2),
  2. **kernel**   — `repro.kernels.blmac_fir_bank` (Pallas, packed trits,
                    sparsity-scheduled bank tiles) — exercised BOTH
                    through the one-shot wrapper and through the
                    streaming `FilterBankEngine` scheduled path
                    (occupancy grouping + order restoration),
  3. **machine**  — `repro.core.FirBlmacMachine` (scalar cycle-accurate
                    reference, per-code Python loop),
  4. **vmachine** — `repro.core.FirBlmacVMachine` (vectorized bank
                    simulator under test),
  5. **sharded**  — `repro.filters.ShardedFilterBankEngine` over a
                    (bank, data) mesh of every visible device (1×1 on a
                    plain session, 8 forced host devices in the CI
                    multi-device leg): occupancy-balanced filter
                    partition, per-shard schedules, halo exchange when
                    the mesh has a data axis, and gather-free
                    caller-order reassembly.

Beyond outputs, the harness checks what only the machines can disagree on:
per-output cycle counts (scalar vs vectorized vs the static cost model vs
`FilterBankEngine.predicted_machine_cycles`) and the weight-memory
programming decision (scalar `program` raises exactly where the vectorized
fit mask is False).  The scalar machine is slow, so its leg runs on
``scalar_samples`` filters and ``scalar_outputs`` output positions;
everything vectorized covers the whole bank.

Bank sources: `random_type1_bank` (seeded random coefficients — stress the
digit space), `sampled_sweep_bank` (real filters from the paper's §3.1
design sweep), and `adversarial_bank` (empty-layer / single-pulse /
truncated rows — the cases a layer-skip schedule can get wrong).  Used by
`tests/test_vmachine.py` and `tests/test_schedule.py`; importable from
any future test or benchmark.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.compiler import BlmacProgram, compile_bank, lower
from repro.core import (FirBlmacMachine, FirBlmacVMachine, MachineSpec,
                        machine_cycles_batch, po2_quantize_batch)
from repro.core.machine import MachineResult
from repro.filters import FilterBankEngine, sweep_bank, sweep_specs

__all__ = [
    "DifferentialReport",
    "chaos_check",
    "cse_check",
    "five_way_check",
    "four_way_check",
    "random_type1_bank",
    "sampled_sweep_bank",
    "adversarial_bank",
]


# ---------------------------------------------------------------------------
# bank sources
# ---------------------------------------------------------------------------


def random_type1_bank(
    n_filters: int,
    taps: int,
    coeff_bits: int = 16,
    seed: int = 0,
    density: float = 1.0,
) -> np.ndarray:
    """Seeded random odd-symmetric integer bank.  ``density`` < 1 zeroes a
    fraction of coefficients — sparse programs exercise long zero-runs."""
    if taps % 2 == 0:
        raise ValueError("type-I filters need an odd tap count")
    rng = np.random.default_rng(seed)
    lim = 1 << (coeff_bits - 1)
    half = rng.integers(-lim, lim, (n_filters, taps // 2 + 1))
    if density < 1.0:
        half *= rng.random(half.shape) < density
    return np.concatenate([half, half[:, :-1][:, ::-1]], axis=1)


def adversarial_bank(taps: int = 31, coeff_bits: int = 16, seed: int = 0) -> np.ndarray:
    """The cases a layer-skip schedule can get wrong, in one mixed bank:
    all-zero rows (empty schedule), single-pulse rows at the extreme
    layers, low-layer-only rows (sparse occupancy), and dense rows — in
    an order that forces the occupancy sort to permute and restore."""
    if taps % 2 == 0:
        raise ValueError("type-I filters need an odd tap count")
    rng = np.random.default_rng(seed)
    half = taps // 2
    lim = 1 << (coeff_bits - 1)
    halves = [np.zeros(half + 1, np.int64)]  # all-zero: empty schedule
    one_top = np.zeros(half + 1, np.int64)
    one_top[half] = 1 << (coeff_bits - 2)  # single pulse, MSB layer
    halves.append(one_top)
    halves.append(rng.integers(-lim, lim, half + 1))  # dense
    one_bot = np.zeros(half + 1, np.int64)
    one_bot[0] = 1  # single pulse, layer 0
    halves.append(one_bot)
    halves.append(rng.integers(-7, 8, half + 1))  # low layers only
    halves.append(np.zeros(half + 1, np.int64))  # second empty row
    halves.append(rng.integers(-lim, lim, half + 1))  # dense again
    return np.stack(
        [np.concatenate([h, h[:-1][::-1]]) for h in halves]
    )


def sampled_sweep_bank(
    taps: int = 127,
    n_div: int = 10,
    n_filters: int = 8,
    window: str = "hamming",
    coeff_bits: int = 16,
    seed: int = 0,
) -> np.ndarray:
    """Quantized filters sampled from the paper's §3.1 design sweep."""
    bank = sweep_bank(taps, n_div, window, sweep_specs(n_div))
    rng = np.random.default_rng(seed)
    rows = rng.choice(len(bank), size=min(n_filters, len(bank)), replace=False)
    q, _ = po2_quantize_batch(bank[rows], bits=coeff_bits)
    return q


# ---------------------------------------------------------------------------
# the harness
# ---------------------------------------------------------------------------


@dataclass
class DifferentialReport:
    n_filters: int
    n_out: int
    fits: np.ndarray  # (B,) bool — vectorized weight-memory verdicts
    mean_cycles: float  # over all filters, vmachine
    scalar_checked: int  # filters the scalar machine replayed
    scalar_rejected: int  # filters the scalar machine refused to program
    sharded_mesh: tuple = (0, 0)  # (n_bank_shards, n_data) of leg 5

    def __str__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"five-way OK: B={self.n_filters} n_out={self.n_out} "
            f"fits={int(self.fits.sum())}/{self.n_filters} "
            f"mean_cycles={self.mean_cycles:.1f} "
            f"scalar legs: {self.scalar_checked} replayed, "
            f"{self.scalar_rejected} reject-parity; "
            f"sharded mesh {self.sharded_mesh}"
        )


def five_way_check(
    qbank: np.ndarray | None = None,
    x: np.ndarray | None = None,
    spec: MachineSpec | None = None,
    *,
    program: BlmacProgram | None = None,
    n_out: int = 48,
    tile: int = 256,
    scalar_samples: int = 4,
    scalar_outputs: int = 8,
    seed: int = 0,
    interpret: bool | None = None,
    mesh=None,
) -> DifferentialReport:
    """Assert all five implementations agree on ``qbank``; see module doc.

    The bank is compiled ONCE (`repro.compiler.compile_bank`) and every
    leg consumes that single `BlmacProgram` — the oracle, kernel and
    sharded legs through `repro.compiler.lower`, the engines by being
    constructed from it, the machines by reading its quantized
    coefficients.  Pass a prebuilt ``program`` (e.g. one that survived a
    `save()`/`load()` round-trip) to pin the shared artifact explicitly;
    ``qbank`` may then be omitted.

    ``x`` defaults to a seeded random signal producing ``n_out`` outputs
    within the spec's sample range.  Raises AssertionError with the leg
    name on any divergence.  ``mesh`` pins the sharded leg's device mesh
    (default: every visible device on the bank axis — a 1×1 mesh on a
    single-device session, where the leg still exercises the partition,
    per-shard planning and reassembly plumbing end-to-end).
    """
    if program is None:
        if qbank is None:
            raise ValueError("five_way_check needs qbank or program")
        program = compile_bank(np.atleast_2d(np.asarray(qbank, np.int64)))
    elif qbank is not None:
        assert np.array_equal(
            np.atleast_2d(np.asarray(qbank, np.int64)), program.qbank
        ), "qbank/program mismatch"
    qbank = program.qbank
    n_filters, taps = qbank.shape
    if spec is None:
        spec = MachineSpec(taps=taps)
    assert spec.taps == taps, "spec/taps mismatch"
    rng = np.random.default_rng(seed)
    if x is None:
        lim = 1 << (spec.sample_bits - 1)
        x = rng.integers(-lim, lim, taps - 1 + n_out)
    x = np.asarray(x, np.int64)
    n_out = x.size - taps + 1

    # -- leg 1: numpy oracle -------------------------------------------------
    # lower(..., "oracle") reads ONLY program.qbank and runs the naive
    # dense Eq. 2 loop — independent of the schedule machinery under test
    oracle = lower(program, "oracle")(x)[:, 0, :]  # (B, n_out)

    # -- leg 4: vectorized machine (under test) ------------------------------
    vm = FirBlmacVMachine(spec)
    fits = vm.program_bank(qbank)
    vres = vm.run(x)
    assert np.array_equal(vres.outputs, oracle), "vmachine outputs != oracle"
    cm = machine_cycles_batch(
        qbank, spec.n_layers, spec.start_overhead, spec.fused_last_add
    )
    assert np.array_equal(vres.cycles, np.broadcast_to(cm[:, None], vres.cycles.shape)), \
        "vmachine cycles != static cost model"
    assert np.array_equal(program.machine_cycles(spec), cm), \
        "program cycle prediction != static cost model"

    # -- leg 2: Pallas bank kernel -------------------------------------------
    y = lower(program, "scheduled", tile=tile, interpret=interpret)(x)
    assert np.array_equal(np.asarray(y[:, 0, :], np.int64), oracle), \
        "pallas bank kernel != oracle"

    # -- leg 2b: streaming engine through the scheduled bank path ------------
    # (occupancy sort, tile grouping, layer-skip superlayers, order
    # restoration — everything the one-shot wrapper also uses, plus the
    # device-resident operands and the overlap-save framing)
    eng = FilterBankEngine(
        program, channels=1, tile=tile, mode="packed", interpret=interpret
    )
    assert eng.program is program, "engine did not adopt the shared program"
    y_eng = eng.push(x)[:, 0, :]
    assert np.array_equal(np.asarray(y_eng, np.int64), oracle), \
        "scheduled FilterBankEngine != oracle"
    assert np.array_equal(eng.predicted_machine_cycles(spec), vres.cycles[:, 0]), \
        "FilterBankEngine cycle prediction != vmachine"

    # -- leg 5: device-sharded engine over a (bank, data) mesh ---------------
    # occupancy-balanced partition, per-shard programs, halo
    # exchange when the mesh carries a data axis, and the gather-free
    # caller-order reassembly — checked on whatever mesh the session has
    sharded = lower(program, "sharded", mesh=mesh, interpret=interpret)
    seng = sharded.engine
    assert seng.program is program, "sharded engine did not adopt the program"
    y_sh = sharded(x)[:, 0, :]
    assert np.array_equal(np.asarray(y_sh, np.int64), oracle), (
        f"sharded engine != oracle (mesh "
        f"{seng.n_bank_shards}x{seng.n_data}, data={seng.data_mode})"
    )
    # caller-order restoration: the partition must be a true permutation
    order = np.concatenate(seng.partition.assign)
    assert np.array_equal(np.sort(order), np.arange(n_filters)), \
        "sharded partition is not a permutation of the bank"
    assert np.array_equal(order[seng.partition.inv], np.arange(n_filters)), \
        "sharded partition inverse does not restore caller order"

    # -- leg 3: scalar cycle-accurate machine (sampled) ----------------------
    n_scalar = min(scalar_samples, n_filters)
    rows = rng.choice(n_filters, size=n_scalar, replace=False)
    xs = x[: taps - 1 + min(scalar_outputs, n_out)]
    checked = rejected = 0
    for b in rows:
        m = FirBlmacMachine(spec)
        try:
            m.program(qbank[b])
        except ValueError:
            assert not fits[b], f"scalar rejected filter {b}, vmachine fit it"
            continue  # reject-parity is re-checked (and counted) below
        assert fits[b], f"vmachine rejected filter {b}, scalar programmed it"
        sres: MachineResult = m.run(xs)
        n = sres.outputs.size
        assert np.array_equal(sres.outputs, vres.outputs[b, :n]), \
            f"scalar machine outputs != vmachine (filter {b})"
        assert np.array_equal(sres.cycles, vres.cycles[b, :n]), \
            f"scalar machine cycles != vmachine (filter {b})"
        checked += 1

    # reject-parity for every filter the mask flags (cheap: program only)
    for b in np.nonzero(~fits)[0]:
        m = FirBlmacMachine(spec)
        try:
            m.program(qbank[b])
            raise AssertionError(
                f"filter {b}: vmachine says overflow, scalar programmed it"
            )
        except ValueError:
            rejected += 1

    return DifferentialReport(
        n_filters=n_filters,
        n_out=n_out,
        fits=fits,
        mean_cycles=vres.mean_cycles,
        scalar_checked=checked,
        scalar_rejected=rejected,
        sharded_mesh=(seng.n_bank_shards, seng.n_data),
    )


def cse_check(
    qbank: np.ndarray | None = None,
    x: np.ndarray | None = None,
    *,
    program: BlmacProgram | None = None,
    n_out: int = 48,
    tile: int = 256,
    seed: int = 0,
    interpret: bool | None = None,
    mesh=None,
    level=2,
    max_shared: int | None = None,
) -> dict:
    """CSE leg of the harness: optimize a compiled bank with
    `repro.compiler.cse_pass` and assert the optimized program is
    bit-exact against the PARENT's oracle on every backend —
    weight-level (``effective_qbank``), scheduled (the bank kernel and
    its combine GEMM), specialized (small banks), vmachine
    (widened-spec augmented rows + exact int64 fold), sharded (augmented
    rows across the mesh, host fold after the gather), and
    `FilterBankEngine` at a pinned tile and at its own plan, whose
    ``cse`` reads ``"optimized"``.

    Also asserts the pass's accounting: the optimized program never
    increases total pulses or §3.3 adds, and its §4 cycle prediction
    equals its augmented bank's cycles plus one per combine use.
    Returns a small report dict (counts, adds, the plan's ``cse``).
    """
    from repro.compiler import cse_pass

    if program is None:
        if qbank is None:
            raise ValueError("cse_check needs qbank or program")
        program = compile_bank(np.atleast_2d(np.asarray(qbank, np.int64)))
    opt = cse_pass(program, level, max_shared=max_shared)
    taps = program.taps
    rng = np.random.default_rng(seed)
    if x is None:
        lim = 1 << (program.spec.sample_bits - 1)
        x = rng.integers(-lim, lim, taps - 1 + n_out)
    x = np.asarray(x, np.int64)
    oracle = lower(program, "oracle")(x)[:, 0, :]

    report = {
        "n_real": program.n_filters,
        "n_shared": 0,
        "adds_parent": program.total_adds(),
        "adds_optimized": opt.total_adds(),
        "auto_cse": "",
    }
    if opt is program:  # nothing profitable: the pass declined entirely
        return report
    report["n_shared"] = opt.n_shared

    # -- accounting ----------------------------------------------------------
    assert np.array_equal(opt.effective_qbank(), program.qbank), \
        "cse: effective_qbank != parent qbank"
    assert int(opt.pulse_counts.sum()) <= int(program.pulse_counts.sum()), \
        "cse: optimized bank has MORE pulses than the parent"
    assert opt.total_adds() <= program.total_adds(), \
        "cse: optimized program has MORE §3.3 adds than the parent"
    wspec = MachineSpec(taps=taps, coeff_bits=opt.n_layers + 1)
    assert np.array_equal(
        opt.machine_cycles(),
        opt.bank.machine_cycles(wspec)[: opt.n_real] + opt.use_counts,
    ), "cse: cycle prediction != augmented cycles + combine uses"

    # -- execution legs ------------------------------------------------------
    for leg, kw in (
        ("oracle", {}),
        ("scheduled", dict(tile=tile, interpret=interpret)),
        ("vmachine", {}),
        ("sharded", dict(mesh=mesh, interpret=interpret)),
    ):
        y = np.asarray(lower(opt, leg, **kw)(x))[:, 0, :]
        assert np.array_equal(y.astype(np.int64), oracle), \
            f"cse: optimized {leg} {kw} != parent oracle"
    if opt.n_filters <= 12:  # one compile per augmented row: small banks
        y = np.asarray(
            lower(opt, "specialized", interpret=interpret)(x)
        )[:, 0, :]
        assert np.array_equal(y.astype(np.int64), oracle), \
            "cse: optimized specialized != parent oracle"

    # -- engines -------------------------------------------------------------
    eng = FilterBankEngine(
        opt, channels=1, tile=tile, mode="packed", interpret=interpret
    )
    assert eng.n_filters == opt.out_filters
    y = eng.push(x)[:, 0, :]
    assert np.array_equal(np.asarray(y, np.int64), oracle), \
        "cse: packed FilterBankEngine != parent oracle"
    auto = FilterBankEngine(opt, channels=1, mode="auto", interpret=interpret)
    assert auto.dispatch_plan.cse == "optimized" and auto.program is opt
    y = auto.push(x)[:, 0, :]
    assert np.array_equal(np.asarray(y, np.int64), oracle), \
        "cse: auto FilterBankEngine != parent oracle"
    report["auto_cse"] = auto.dispatch_plan.cse
    return report


def chaos_check(
    qbank: np.ndarray,
    kills,
    *,
    n_chunks: int = 6,
    chunk: int = 512,
    mesh=None,
    n_bank_shards: int | None = None,
    data_mode: str | None = None,
    depth: int = 2,
    seed: int = 0,
    interpret: bool | None = None,
    integrity_check: bool = True,
) -> dict:
    """Chaos leg of the harness: kill shards mid-stream, assert the
    recovered stream is bit-exact vs the oracle and the fault counters
    match the injected faults.

    ``kills`` is a list of ``(shard, at_chunk)`` grid points handed to
    `repro.distributed.faultbank.FaultInjector.kill_shard` — shard
    indices are bank-shard SLOTS at fire time (after a recovery the
    survivors renumber from 0), so sequential kills read the way a test
    reasons about the recovered mesh.  The stream runs through
    `AsyncBankServer` (double-buffered, strict order); every in-flight
    chunk at each kill is replayed from its tail snapshot through the
    re-partitioned mesh, and the concatenated output must equal the
    naive Eq. 2 oracle to the last bit.  The integrity probe is on by
    default so the halo/reassembly positions are host-verified too.
    Returns the engine's ``fault_stats()`` for further assertions.
    """
    from repro.distributed.faultbank import FaultInjector
    from repro.filters import ShardedFilterBankEngine
    from repro.serving import AsyncBankServer

    program = compile_bank(np.atleast_2d(np.asarray(qbank, np.int64)))
    rng = np.random.default_rng(seed)
    lim = 1 << (program.spec.sample_bits - 1)
    x = rng.integers(-lim, lim, n_chunks * chunk)
    oracle = lower(program, "oracle")(x)[:, 0, :]

    injector = FaultInjector()
    kills = list(kills)
    for shard, at_chunk in kills:
        injector.kill_shard(shard, at_chunk)
    eng = ShardedFilterBankEngine(
        program, mesh=mesh, n_bank_shards=n_bank_shards,
        data_mode=data_mode, interpret=interpret,
        fault_injector=injector, integrity_check=integrity_check,
    )
    server = AsyncBankServer(eng, depth=depth)
    got = []
    for k in range(n_chunks):
        got += server.submit(x[k * chunk: (k + 1) * chunk])
    got += server.drain()
    y = np.concatenate([g for g in got if g.shape[2]], axis=2)[:, 0, :]
    assert np.array_equal(np.asarray(y, np.int64), oracle), (
        f"chaos: recovered stream != oracle after kills {kills} "
        f"(final mesh {eng.n_bank_shards}x{eng.n_data})"
    )
    stats = eng.fault_stats()
    assert stats["injected"]["kills"] == len(kills), (
        f"chaos: {stats['injected']['kills']} of {len(kills)} kills fired "
        f"— the grid points never hit a live (shard, chunk)"
    )
    assert stats["lost_shards"] == len(kills), stats
    assert stats["recoveries"] == len(kills), stats
    assert stats["detections"] == len(kills), stats
    assert server.failed_chunks == 0 and server.chunks_out == n_chunks, (
        "chaos: the server dropped chunks — recovery must be lossless"
    )
    return stats


def session_chaos_check(
    qbank: np.ndarray,
    kills,
    *,
    n_sessions: int = 8,
    n_slots: int = 4,
    rows_per_session: int = 2,
    n_chunks: int = 6,
    chunk: int = 256,
    n_bank_shards: int | None = None,
    mesh=None,
    seed: int = 0,
    journal_path=None,
) -> dict:
    """Sessions × shards chaos leg: N tenant streams batched into the
    shared lanes of a `BankSessionServer` whose dispatches run through a
    `ShardedFilterBankEngine`, with shards killed mid-`step()`.

    Every session's concatenated stream must equal the Eq. 2 oracle for
    its own (stream, row-selection) to the last bit — shard loss is an
    arithmetic no-op — and the server must attribute each detected fault
    to exactly the ``n_slots`` sessions of the failed dispatch round
    (per-tenant isolation: everyone else's counter stays put).  With
    ``journal_path`` the run is also journaled, checking the WAL rides
    along with mesh recovery.  Returns the server's ``fault_stats()``.
    """
    from repro.distributed.faultbank import FaultInjector
    from repro.filters import ShardedFilterBankEngine
    from repro.serving import BankSessionServer

    program = compile_bank(np.atleast_2d(np.asarray(qbank, np.int64)))
    rng = np.random.default_rng(seed)
    lim = 1 << (program.spec.sample_bits - 1)
    n = program.n_filters
    sels = [
        np.sort(rng.choice(n, size=min(rows_per_session, n), replace=False))
        for _ in range(n_sessions)
    ]
    streams = [
        rng.integers(-lim, lim, n_chunks * chunk).astype(np.int32)
        for _ in range(n_sessions)
    ]
    oracle = lower(program, "oracle")

    injector = FaultInjector()
    kills = list(kills)
    for shard, at_chunk in kills:
        injector.kill_shard(shard, at_chunk)
    eng = ShardedFilterBankEngine(
        program, channels=n_slots, mesh=mesh, n_bank_shards=n_bank_shards,
        fault_injector=injector,
    )
    server = BankSessionServer(
        program, n_slots=n_slots, auto_step=False, engine=eng,
        step_budget_us=1e12, journal=journal_path,
    )
    sessions = [server.open_session(sel) for sel in sels]
    outs = [[] for _ in range(n_sessions)]
    for k in range(n_chunks):
        for i, s in enumerate(sessions):
            s.push(streams[i][k * chunk: (k + 1) * chunk])
        server.step()
        for i, s in enumerate(sessions):
            out = s.pull()
            if out.shape[1]:
                outs[i].append(out)
    for i in range(n_sessions):
        want = oracle(streams[i])[sels[i], 0, :]
        got = np.concatenate(outs[i], axis=1)
        assert np.array_equal(np.asarray(got, np.int64), want), (
            f"session chaos: tenant {i} diverged from its oracle after "
            f"kills {kills} (final mesh {eng.n_bank_shards}x{eng.n_data})"
        )
    stats = server.fault_stats()
    assert stats["injected"]["kills"] == len(kills), stats
    assert stats["lost_shards"] == len(kills), stats
    assert stats["recoveries"] == len(kills), stats
    # per-tenant isolation: each kill marked one round's tenants, and
    # only them — total attributed faults = kills × round size
    marked = sum(stats["per_session"].values())
    assert marked <= len(kills) * n_slots, stats
    assert stats["session_faults"] == len(kills), stats
    server.close()
    return stats


# The harness grew its fifth (sharded) leg in PR 4; the historical name
# stays importable for existing tests and external callers.
four_way_check = five_way_check
