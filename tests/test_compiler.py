"""The unified compile pipeline: content addressing, exactly-once
compilation across clients, save/load round-trips, and the five lowering
backends of one `BlmacProgram`.
"""
import json

import numpy as np
import pytest

from repro.compiler import (BlmacProgram, CompileSpec, ProgramFormatError,
                            cache_stats, clear_caches, compile_bank,
                            compile_packed, lower, pack_bank_trits)
from repro.core import machine_cycles_batch, po2_quantize_batch
from repro.filters import FilterBankEngine, fir_bit_layers_batch

from differential import adversarial_bank, five_way_check, random_type1_bank


def _qbank(n=6, taps=31, seed=0, lim=12000):
    rng = np.random.default_rng(seed)
    half = rng.integers(-lim, lim, (n, taps // 2 + 1))
    return np.concatenate([half, half[:, :-1][:, ::-1]], axis=1)


# ---------------------------------------------------------------------------
# content addressing
# ---------------------------------------------------------------------------

def test_compile_bank_is_content_addressed():
    q = _qbank()
    p1 = compile_bank(q)
    p2 = compile_bank(q.copy())  # different buffer, same content
    assert p1 is p2
    # the packed route resolves to the SAME program object
    p3 = compile_packed(pack_bank_trits(q), q.shape[1])
    assert p3 is p1
    # …and in the other order: a bank first seen packed is adopted by
    # compile_bank instead of compiled into a duplicate
    q4 = _qbank(seed=11)
    clear_caches()
    p4 = compile_packed(pack_bank_trits(q4), q4.shape[1])
    p5 = compile_bank(q4)
    assert p5 is p4
    assert cache_stats()["counters"]["bank_compiles"] == 1
    # different content -> different program
    q2 = q.copy()
    q2[0, 0] += 1
    q2[0, -1] += 1  # keep symmetry
    assert compile_bank(q2) is not p1


def test_compile_does_not_adopt_caller_buffers():
    """Programs freeze their arrays — that must never leak onto a
    caller's buffer, and mutating the caller's operand afterwards must
    not alter cached program content."""
    q = _qbank(seed=13)
    packed = pack_bank_trits(q)
    prog = compile_packed(packed, q.shape[1])
    packed[0, 0, 0] ^= 1  # caller's buffer stays writable…
    assert prog.packed[0, 0, 0] == packed[0, 0, 0] ^ 1  # …and unaliased
    qi = q.copy()
    prog2 = compile_bank(qi)
    qi[0, 0] += 2  # int input: same guarantee
    assert prog2.qbank[0, 0] == qi[0, 0] - 2


def test_engines_keep_integer_truncation_for_float_banks():
    """FilterBankEngine's historical contract: float input is cast to
    int64 (truncated), NOT po2-quantized — a float bank of integer
    values filters with exactly those values."""
    w = np.array([1.0, 2.0, 1.0])
    eng = FilterBankEngine(w, mode="packed")
    assert np.array_equal(eng.qbank, [[1, 2, 1]])
    x = np.arange(10)
    y = eng.push(x)[0, 0]
    assert np.array_equal(y, fir_bit_layers_batch(x, [1, 2, 1])[0, 0])


def test_compile_bank_quantizes_float_input():
    from repro.filters import design_bank

    h = design_bank(31, [("lowpass", 0.3), ("bandpass", (0.2, 0.6))])
    prog = compile_bank(h)
    q, k = po2_quantize_batch(h, 16)
    assert np.array_equal(prog.qbank, q)
    assert np.array_equal(prog.exponents, k)
    # float and its quantization compile to one program
    assert compile_bank(q) is prog


def test_compile_rejects_bad_banks():
    with pytest.raises(ValueError):
        compile_bank(np.ones((2, 4), np.int64))  # even taps
    with pytest.raises(ValueError):
        compile_bank(np.arange(10).reshape(2, 5))  # asymmetric
    with pytest.raises(TypeError):
        compile_bank(np.ones((2, 5), complex))
    with pytest.raises(OverflowError):
        compile_bank(_qbank(), CompileSpec(sample_bits=16))


def test_select_subprogram_slices_and_memoizes():
    q = _qbank(n=8)
    p = compile_bank(q)
    rows = np.array([5, 1, 2])
    sub = p.select(rows)
    assert sub is p.select(rows)
    assert np.array_equal(sub.qbank, q[rows])
    assert np.array_equal(sub.packed, p.packed[rows])
    assert np.array_equal(sub.pulse_counts, p.pulse_counts[rows])
    # content addressing reaches the subprogram too
    assert compile_bank(q[rows]) is sub


# ---------------------------------------------------------------------------
# exactly-once compilation across engine + bank plan + cycle predictor
# ---------------------------------------------------------------------------

def test_csd_computed_exactly_once_across_clients():
    """The regression the refactor exists for: one bank used by the
    engine, the bank plan and the cycle predictor costs ONE CSD/packing
    pass and ONE program compile, however many clients touch it."""
    from repro.compiler import bank_plan

    q = _qbank(n=12, taps=31, seed=3)
    clear_caches()
    eng = FilterBankEngine(q, mode="auto", chunk_hint=1024)  # compiles
    c1 = cache_stats()
    assert c1["counters"]["bank_compiles"] == 1
    assert c1["counters"]["csd_packings"] == 1
    plans_after_build = c1["counters"]["schedule_plans"]

    # a second engine, a direct bank_plan call and the cycle predictor
    # all resolve through the same artifact: no new compiles, packings
    # or schedule plans
    eng2 = FilterBankEngine(q, mode="auto", chunk_hint=1024)
    assert eng2.program is eng.program
    plan, sched = bank_plan(eng.program)
    assert plan == eng.dispatch_plan and sched is eng.bank_schedule
    cycles = eng.predicted_machine_cycles()
    assert np.array_equal(eng2.predicted_machine_cycles(), cycles)
    c2 = cache_stats()
    assert c2["counters"]["bank_compiles"] == 1
    assert c2["counters"]["csd_packings"] == 1
    assert c2["counters"]["schedule_plans"] == plans_after_build
    assert c2["counters"]["machine_cycle_computes"] == 1
    assert c2["program"]["hits"] >= 1
    assert np.array_equal(
        cycles, machine_cycles_batch(q, 16, 0, False)
    )


def test_cache_stats_reports_every_domain():
    stats = cache_stats()
    for domain in ("program", "cse", "specialized"):
        assert {"hits", "misses", "size"} <= set(stats[domain])
    assert "size" in stats["bank_call"]
    assert isinstance(stats["counters"], dict)


def test_caches_are_bounded():
    from repro.compiler.cache import PROGRAM_CACHE
    from repro.compiler.program import SCHEDULE_MEMO_MAX

    clear_caches()
    for seed in range(40):
        compile_bank(_qbank(n=2, taps=15, seed=seed, lim=500))
    assert len(PROGRAM_CACHE) <= PROGRAM_CACHE.max_entries
    # per-program schedule memo: one entry per geometry, bounded
    prog = compile_bank(_qbank(n=2, taps=15, seed=0, lim=500))
    for bank_tile in range(8, 8 * (SCHEDULE_MEMO_MAX + 4), 8):
        prog.schedule(bank_tile)
    assert len(prog._schedules) == SCHEDULE_MEMO_MAX


# ---------------------------------------------------------------------------
# save / load
# ---------------------------------------------------------------------------

def test_save_load_roundtrip_identical(tmp_path):
    q = adversarial_bank(taps=31)
    prog = compile_bank(q)
    sched = prog.schedule()
    x = np.random.default_rng(0).integers(-128, 128, 31 + 64)
    y = lower(prog, "scheduled", tile=128)(x)

    path = tmp_path / "bank.npz"
    prog.save(path)
    clear_caches()  # force the load to rebuild, not cache-hit
    loaded = BlmacProgram.load(path)
    assert loaded.key == prog.key
    assert np.array_equal(loaded.qbank, prog.qbank)
    assert np.array_equal(loaded.exponents, prog.exponents)
    assert np.array_equal(loaded.packed, prog.packed)
    assert np.array_equal(loaded.occupancy, prog.occupancy)
    assert np.array_equal(loaded.signatures, prog.signatures)
    assert np.array_equal(loaded.pulse_counts, prog.pulse_counts)
    # identical schedule structure
    sched2 = loaded.schedule()
    assert sched2.tile_size == sched.tile_size
    assert sched2.merge == sched.merge
    assert np.array_equal(sched2.perm, sched.perm)
    assert len(sched2.groups) == len(sched.groups)
    for g1, g2 in zip(sched.groups, sched2.groups):
        assert g1.schedule == g2.schedule
        assert g1.tail_shift == g2.tail_shift
        assert g1.sel_layers == g2.sel_layers
        assert np.array_equal(g1.packed, g2.packed)
    # identical §4 cycle counts
    assert np.array_equal(loaded.machine_cycles(), prog.machine_cycles())
    # bit-exact outputs after reload
    y2 = lower(loaded, "scheduled", tile=128)(x)
    assert np.array_equal(y, y2)
    # loading registered the program: compiling the bank is now a hit
    assert compile_bank(q) is loaded


def _rewrite_npz(path, mutate):
    with np.load(path, allow_pickle=False) as z:
        data = {k: np.array(z[k]) for k in z.files}
    mutate(data)
    with open(path, "wb") as f:
        np.savez(f, **data)


def test_load_rejects_version_mismatch(tmp_path):
    path = tmp_path / "bank.npz"
    compile_bank(_qbank(n=2)).save(path)

    def bump_version(data):
        hdr = json.loads(str(data["header"][()]))
        hdr["format_version"] = 999
        data["header"] = np.array(json.dumps(hdr))

    _rewrite_npz(path, bump_version)
    with pytest.raises(ProgramFormatError, match="version"):
        BlmacProgram.load(path)


def test_load_rejects_tampered_content(tmp_path):
    path = tmp_path / "bank.npz"
    compile_bank(_qbank(n=2)).save(path)

    def flip_trit(data):
        packed = data["packed"].copy()
        packed[0, 0, 0] ^= 1
        data["packed"] = packed

    _rewrite_npz(path, flip_trit)
    with pytest.raises(ProgramFormatError, match="digest"):
        BlmacProgram.load(path)


def test_load_rejects_tampered_coefficients(tmp_path):
    """The digest covers the trits; a corrupted qbank (which would make
    the oracle backend diverge from the kernels) must also be rejected."""
    path = tmp_path / "bank.npz"
    compile_bank(_qbank(n=2)).save(path)

    def corrupt_qbank(data):
        qbank = data["qbank"].copy()
        qbank[0, 0] += 1
        data["qbank"] = qbank

    _rewrite_npz(path, corrupt_qbank)
    with pytest.raises(ProgramFormatError, match="digest"):
        BlmacProgram.load(path)


def test_load_rejects_truncated_file(tmp_path):
    """A half-written file must fall into the ProgramFormatError path the
    serving warm-start recovers from, not an arbitrary zip exception."""
    path = tmp_path / "bank.npz"
    compile_bank(_qbank(n=2)).save(path)
    raw = path.read_bytes()
    path.write_bytes(raw[: len(raw) // 2])
    with pytest.raises(ProgramFormatError, match="unreadable"):
        BlmacProgram.load(path)


# ---------------------------------------------------------------------------
# lowering: one program, five backends
# ---------------------------------------------------------------------------

def test_lower_all_backends_agree():
    q = _qbank(n=5, taps=15, seed=7, lim=4000)
    prog = compile_bank(q)
    rng = np.random.default_rng(1)
    x = rng.integers(-128, 128, (2, 15 + 40))  # 2 channels
    ref = fir_bit_layers_batch(x, q)
    for backend in ("oracle", "specialized", "scheduled", "vmachine"):
        exe = lower(prog, backend, tile=128)
        if backend == "vmachine":
            assert exe.fits.shape == (5,)
        y = exe(x)
        assert y.shape == ref.shape, backend
        assert np.array_equal(np.asarray(y, np.int64), ref), backend
    sharded = lower(prog, "sharded", channels=2, tile=128)
    y = sharded(x)
    assert np.array_equal(np.asarray(y, np.int64), ref)
    assert sharded.engine.program is prog


def test_lower_rejects_unknowns():
    prog = compile_bank(_qbank(n=2))
    with pytest.raises(ValueError, match="backend"):
        lower(prog, "fpga")
    with pytest.raises(TypeError):
        lower(np.ones((2, 31)), "oracle")


def test_five_way_accepts_prebuilt_program(tmp_path):
    """The differential harness's five legs all consume ONE program —
    here one that survived a disk round-trip."""
    q = random_type1_bank(4, 31, coeff_bits=12, seed=5)
    prog = compile_bank(q)
    path = tmp_path / "bank.npz"
    prog.save(path)
    clear_caches()
    loaded = BlmacProgram.load(path)
    report = five_way_check(program=loaded, n_out=24)
    assert report.n_filters == 4
    # and the legacy signature still routes through one shared program
    report2 = five_way_check(q, n_out=24)
    assert report2.n_filters == 4


def test_five_way_program_qbank_mismatch_raises():
    prog = compile_bank(_qbank(n=2))
    with pytest.raises(AssertionError, match="mismatch"):
        five_way_check(_qbank(n=2, seed=9), program=prog, n_out=8)
