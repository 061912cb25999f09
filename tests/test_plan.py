"""The one dispatch plan: `repro.compiler.bank_plan` and
`repro.distributed.sharding.sharded_plan`.

Pins the kernels every benchmark configuration runs (the §3.1 grid at
127 and 255 taps, 8- and 16-bit streams, one chip and a 4-chip bank
axis), the sharded data-axis and recovery rules, and explicit geometry
overrides, each bit-exact against the numpy oracle where it runs.
"""
import functools
import json

import numpy as np
import pytest

from repro.compiler import bank_plan, compile_bank, cse_pass, lower
from repro.core import po2_quantize_batch
from repro.core.costmodel import SESSION_DISPATCH_US, predict_session_step_us
from repro.distributed import sharded_plan
from repro.filters import (FilterBankEngine, fir_bit_layers_batch,
                           spread_lowpass_qbank, sweep_bank)
from tests._subproc import run_py

# the §3.1 grid's 9,900 filters, sorted by occupancy into 256-row bank
# tiles: six tile groups, at either tap count
GRID_GROUP_ROWS = [3328, 256, 512, 768, 1792, 3328]
# the 127-tap grid over a 4-chip bank axis: filters, bank tile and tile
# group rows of each shard
SHARDS_4CHIP = [
    (2431, 248, [2480]),
    (2648, 248, [744, 248, 248, 248, 992, 248]),
    (2517, 256, [1536, 1024]),
    (2304, 256, [2304]),
]


@functools.lru_cache(maxsize=None)
def _grid(taps: int):
    """The §3.1 grid at ``taps``, 16-bit po2-quantized, compiled."""
    q, _ = po2_quantize_batch(sweep_bank(taps), 16)
    return compile_bank(q)


def _qbank(n: int, taps: int = 31) -> np.ndarray:
    return spread_lowpass_qbank(n, taps)


# ---------------------------------------------------------------------------
# the benchmark's configurations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("taps, channels, mesh", [
    pytest.param(127, 1, None, id="fir127_grid"),
    pytest.param(255, 1, None, id="fir255_grid"),
    pytest.param(127, 2, None, id="fir127_ci16"),
    pytest.param(127, 1, (4, 1), id="fir127_grid-4chip"),
])
def test_plan_of_every_benchmark_configuration(taps, channels, mesh):
    """A one-chip cell's engine runs the scheduled kernel at tile 256,
    bank tile 256 and merge 8, in six tile groups; on a 4-chip bank
    axis the grid is cut into four shards at tile 256 and merge 8."""
    program = _grid(taps)
    if mesh is None:
        eng = FilterBankEngine(program, channels=channels, chunk_hint=4096)
        plan = eng.dispatch_plan
        assert (plan.mode, plan.tile, plan.bank_tile, plan.merge) == (
            "scheduled", 256, 256, 8)
        assert [g.packed.shape[0] for g in eng.bank_schedule.groups] == \
            GRID_GROUP_ROWS
        return
    plan, part, schedules = sharded_plan(program, mesh, channels=channels)
    assert (plan.n_bank_shards, plan.n_data, plan.data_mode) == (4, 1, "none")
    assert [(p.mode, p.tile, p.bank_tile, p.merge)
            for p in plan.shard_plans] == [
        ("scheduled", 256, bt, 8) for _, bt, _ in SHARDS_4CHIP]
    assert [len(rows) for rows in part.assign] == \
        [n for n, _, _ in SHARDS_4CHIP]
    assert [[g.packed.shape[0] for g in s.groups] for s in schedules] == \
        [rows for _, _, rows in SHARDS_4CHIP]


def test_sharded_engine_on_four_chips_runs_the_pinned_plan():
    """`ShardedFilterBankEngine` with default arguments on a 4-device
    host (as the 4-chip cell builds it) runs exactly that plan."""
    out = run_py("""
import json
from repro.compiler import compile_bank
from repro.core import po2_quantize_batch
from repro.filters import ShardedFilterBankEngine, sweep_bank

q, _ = po2_quantize_batch(sweep_bank(127), 16)
eng = ShardedFilterBankEngine(compile_bank(q), channels=1, chunk_hint=4096)
print(json.dumps({
    "mesh": [eng.n_bank_shards, eng.n_data, eng.data_mode],
    "plans": [[p.mode, p.tile, p.bank_tile, p.merge]
              for p in eng.plan.shard_plans],
    "filters": [len(rows) for rows in eng.partition.assign],
    "rows": eng._shard_rows,
}))
""", devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert got["mesh"] == [4, 1, "none"]
    assert got["plans"] == [["scheduled", 256, bt, 8]
                            for _, bt, _ in SHARDS_4CHIP]
    assert got["filters"] == [n for n, _, _ in SHARDS_4CHIP]
    assert got["rows"] == [sum(rows) for _, _, rows in SHARDS_4CHIP]


# ---------------------------------------------------------------------------
# the sharded rules
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("data_mode, channels, n_data", [
    (None, 1, 1), ("channels", 2, 2), ("time", 1, 2)])
def test_sharded_data_axis_rule(data_mode, channels, n_data):
    """On a (2, 2) mesh the data axis is used only when ``data_mode``
    names it; the bank axis takes one shard per row either way."""
    prog = compile_bank(_qbank(6))
    plan, part, _ = sharded_plan(prog, (2, 2), channels=channels,
                                 data_mode=data_mode)
    assert plan.n_bank_shards == part.n_shards == 2
    assert plan.data_mode == (data_mode or "none")
    assert plan.n_data == n_data


def test_sharded_plan_runs_bank_plan_on_each_select_subprogram():
    prog = compile_bank(_qbank(20))
    plan, part, schedules = sharded_plan(prog, (3, 1), tile=128, merge=4)
    assert part is prog.partition(3)
    for rows, shard, schedule in zip(part.assign, plan.shard_plans,
                                     schedules):
        sub = prog.select(rows)
        assert (shard, schedule) == bank_plan(sub, 128, merge=4)
        assert schedule is sub.schedule(None, 4)


def test_recovery_onto_every_surviving_bank_row():
    """Unforced, a recovered engine takes one shard per surviving mesh
    row: 4 → 3 → 2, and the stream stays bit-exact."""
    out = run_py("""
import numpy as np
from repro.distributed import FaultInjector, bank_mesh
from repro.filters import (ShardedFilterBankEngine, fir_bit_layers_batch,
                           spread_lowpass_qbank)

q = spread_lowpass_qbank(11, 31)
x = np.random.default_rng(3).integers(-128, 128, 6 * 300)
inj = FaultInjector().kill_shard(0, at_chunk=1).kill_shard(2, at_chunk=3)
eng = ShardedFilterBankEngine(q, mesh=bank_mesh(4, 1), fault_injector=inj)
shards = [eng.n_bank_shards]
outs = []
for k in range(6):
    outs.append(eng.push(x[k * 300:(k + 1) * 300]))
    shards.append(eng.n_bank_shards)
y = np.concatenate(outs, axis=2)
assert np.array_equal(y, fir_bit_layers_batch(x, q)), "recovered != oracle"
assert eng.fault_stats()["recoveries"] == 2
print("SHARDS", *shards)
""", devices=4)
    shards = [int(s) for s in out.split("SHARDS")[1].split()]
    assert shards == [4, 4, 3, 3, 2, 2, 2]


# ---------------------------------------------------------------------------
# explicit geometry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("override, expect", [
    (dict(tile=128), (128, 24, 8)),
    (dict(bank_tile=8), (256, 8, 8)),
    (dict(merge=1), (256, 24, 1)),
])
def test_explicit_geometry_is_honoured(override, expect):
    """A pinned tile, bank tile or merge replaces only that value of the
    plan, on the engine and in `lower`, bit-exact on a 17-filter bank."""
    q = _qbank(17)
    x = np.random.default_rng(4).integers(-128, 128, (2, 700))
    eng = FilterBankEngine(q, channels=2, **override)
    plan = eng.dispatch_plan
    assert (plan.tile, plan.bank_tile, plan.merge) == expect
    assert eng.bank_schedule.tile_size == plan.bank_tile
    ref = fir_bit_layers_batch(x, q)
    assert np.array_equal(eng.push(x), ref)
    exe = lower(eng.program, "scheduled", **override)
    assert exe.schedule is eng.bank_schedule
    assert np.array_equal(exe(x), ref)


def test_lower_scheduled_runs_the_engine_plan():
    """`lower(program, "scheduled")` with no pins takes the engines'
    plan: the same memoized schedule, at tile 256."""
    prog = compile_bank(_qbank(9))
    plan, schedule = bank_plan(prog)
    exe = lower(prog, "scheduled")
    assert exe.schedule is schedule and plan.tile == 256
    x = np.random.default_rng(5).integers(-128, 128, 600)
    assert np.array_equal(exe(x), lower(prog, "oracle")(x))


def test_engine_plan_ignores_chunk_hint():
    """``chunk_hint`` is accepted and ignored: the plan is the same for
    short streaming chunks and long batch pushes."""
    q = _qbank(12)
    plans = {FilterBankEngine(q, chunk_hint=h).dispatch_plan
             for h in (64, 4096, 1 << 20)}
    assert len(plans) == 1


def test_optimized_program_plan_reads_optimized():
    """A CSE `OptimizedProgram` plans its shared-row layout (``cse`` =
    "optimized"); a plain program's plan leaves ``cse`` empty.  Its rows
    are coupled by the combine, so only its ``.bank`` shards."""
    parent = compile_bank(_qbank(10))
    opt = cse_pass(parent)
    assert opt is not parent
    assert bank_plan(parent)[0].cse == ""
    plan, schedule = bank_plan(opt)
    assert plan.cse == "optimized"
    assert schedule.n_filters == opt.n_filters > opt.out_filters
    with pytest.raises(NotImplementedError):
        sharded_plan(opt, (2, 1))
    assert sharded_plan(opt.bank, (2, 1))[0].n_bank_shards == 2


def test_session_admission_prices_rounds_at_the_fixed_dispatch():
    """Admission prices every round at `SESSION_DISPATCH_US`, whatever
    the bank: the step for two sessions on one slot is two rounds."""
    from repro.serving import BankSessionServer

    for n in (4, 40):
        srv = BankSessionServer(compile_bank(_qbank(n)), n_slots=1,
                                interpret=True)
        assert srv._dispatch_us() == SESSION_DISPATCH_US
        srv.open_session([0])
        assert srv.predicted_step_us(extra_sessions=1) == \
            predict_session_step_us(SESSION_DISPATCH_US, 2, 1)
