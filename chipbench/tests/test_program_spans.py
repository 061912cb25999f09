"""The program's own spans, read back from a real profiler trace on the
CPU: each push of `FilterBankEngine` and of `ShardedFilterBankEngine`
(on 4 virtual CPU devices) emits its ``blmac.`` spans, nested inside the
benchmark's ``cb.push``, with the ids that tie them to their push."""
import json
import os
import subprocess
import sys
import textwrap

from chipbench import spans as program_spans
from chipbench.tests import tiny

ROOT = tiny.BENCH.parent

TRACE_PUSHES = '''
def trace_pushes(engine, chunks, log_dir):
    """Push every chunk inside ``cb.push`` under the profiler; returns
    what `spans.extract` reads back."""
    import jax
    from jax.profiler import TraceAnnotation
    from chipbench import spans, tracing

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with TraceAnnotation("cb.window"):
            for x in chunks:
                with TraceAnnotation("cb.push"):
                    engine.push(x)
    return spans.extract(tracing.find_xplane(log_dir))
'''
exec(TRACE_PUSHES)


def _inside(inner, outer):
    return outer[1] <= inner[1] and \
        inner[1] + inner[2] <= outer[1] + outer[2]


def _per_push(trace, n_pushes):
    """The program spans of each ``cb.push``, in order."""
    pushes = [s for s in trace["host_spans"] if s[0] == "cb.push"]
    assert len(pushes) == n_pushes
    return [(p, [s for s in trace["program_spans"] if _inside(s, p)])
            for p in sorted(pushes, key=lambda s: s[1])]


def _chunks(n, channels, length, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(-128, 128, (channels, length)) for _ in range(n)]


def test_filter_bank_push_spans(tmp_path):
    from repro.compiler import compile_bank
    from repro.filters import FilterBankEngine, spread_lowpass_qbank

    eng = FilterBankEngine(compile_bank(spread_lowpass_qbank(40, 31)),
                           channels=2, mode="packed", bank_tile=8, tile=128)
    groups = [i for i, g in enumerate(eng.bank_schedule.groups)
              if g.sel_layers]
    assert len(groups) > 1
    chunks = _chunks(3, 2, 300)
    eng.push(chunks[0])  # compile outside the trace
    trace = trace_pushes(eng, chunks, str(tmp_path))
    assert not [s for s in trace["program_spans"]
                if not any(_inside(s, p) for p in trace["host_spans"])]
    for k, (_, spans) in enumerate(_per_push(trace, 3), start=1):
        names = [s[0] for s in spans]
        push = [s for s in spans if s[0] == "blmac.push"]
        assert len(push) == 1 and push[0][3] == {"chunk": k}
        for name in ("blmac.stage", "blmac.dispatch", "blmac.wait",
                     "blmac.readback"):
            assert name in names
            assert all(_inside(s, push[0]) for s in spans if s[0] == name)
        dispatch = [s for s in spans if s[0] == "blmac.dispatch"][0]
        group = [s for s in spans if s[0] == "blmac.group"]
        assert [s[3] for s in group] == [{"group": g} for g in groups]
        assert all(_inside(s, dispatch) for s in group)
        # stage, dispatch, wait and readback follow one another
        order = [s for s in spans if s[0] in (
            "blmac.dispatch", "blmac.wait", "blmac.readback")]
        assert [s[0] for s in order] == ["blmac.dispatch", "blmac.wait",
                                         "blmac.readback"]
    totals = program_spans.totals(trace)
    assert totals["blmac.push"]["count"] == 3
    assert totals["blmac.group"]["count"] == 3 * len(groups)
    # the steps of a push cover all of it but the span calls themselves
    split = program_spans.summary(trace)
    assert split["pushes"] == 3
    assert 0.5 < split["steps_share_of_push"] <= 1.0


SHARDED = TRACE_PUSHES + '''
import json, sys
import numpy as np
from repro.compiler import compile_bank
from repro.distributed.sharding import bank_mesh
from repro.filters import ShardedFilterBankEngine, spread_lowpass_qbank

eng = ShardedFilterBankEngine(
    compile_bank(spread_lowpass_qbank(64, 31)), channels=1,
    mesh=bank_mesh(4, 1), n_bank_shards=4, tile=128, chunk_hint=512)
rng = np.random.default_rng(0)
chunks = [rng.integers(-128, 128, (1, 512)) for _ in range(4)]
eng.push(chunks[0])
before = eng.push_stats()
trace = trace_pushes(eng, chunks[1:], sys.argv[1] + "/sync")
after = eng.push_stats()

# the pipelined path: push_async dispatches a chunk before the one
# ahead of it is read back, so only the chunk ids tie their spans
import jax
from chipbench import spans, tracing
from repro.serving import AsyncBankServer

server = AsyncBankServer(eng, depth=2)
first = eng._chunk_idx
with jax.profiler.trace(sys.argv[1] + "/async"):
    with jax.profiler.TraceAnnotation("cb.window"):
        for x in chunks:
            server.submit(x)
        server.drain()
pipelined = spans.extract(tracing.find_xplane(sys.argv[1] + "/async"))
print(json.dumps({"trace": trace, "shards": eng.n_bank_shards,
                  "rows": eng._shard_rows, "before": before,
                  "after": after, "pipelined": pipelined,
                  "first": first}))
'''


def test_sharded_push_spans_on_four_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SHARDED), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["shards"] == 4
    trace = out["trace"]
    for k, (_, spans) in enumerate(_per_push(trace, 3), start=1):
        push = [s for s in spans if s[0] == "blmac.push"]
        assert len(push) == 1 and push[0][3] == {"chunk": k}
        assert all(_inside(s, push[0]) for s in spans if s is not push[0])
        for name in ("blmac.shard_dispatch", "blmac.shard_read"):
            assert [s[3] for s in spans if s[0] == name] == \
                [{"chunk": k, "shard": i} for i in range(4)]
        names = [s[0] for s in spans]
        assert "blmac.stage" in names
        assert names.count("blmac.reassemble") == 1
        assert all(s[3] == {"chunk": k} for s in spans
                   if s[0] in ("blmac.stage", "blmac.reassemble"))
        last_read = max(s[1] for s in spans if s[0] == "blmac.shard_read")
        reassemble = [s for s in spans if s[0] == "blmac.reassemble"][0]
        assert reassemble[1] >= last_read
    # 3 steady pushes of 512 samples after a 30-sample tail: 542 pads to
    # the 128-sample quantum, 640, and every shard frames 640 - 30 = 610
    # outputs into 5 tiles of 128
    delta = {k: out["after"][k] - out["before"][k] for k in out["after"]}
    assert delta == {"pushes": 3, "outputs_delivered": 3 * 64 * 512,
                     "outputs_computed": 3 * sum(out["rows"]) * 640,
                     "bytes_read_back": 3 * 64 * 640 * 4}
    # pipelined pushes carry no blmac.push; each step names its chunk,
    # and chunk k + 1 is dispatched before chunk k is read back
    by_chunk = {}
    for s in out["pipelined"]["program_spans"]:
        by_chunk.setdefault(s[3].get("chunk"), []).append(s)
    ids = list(range(out["first"], out["first"] + 4))
    assert sorted(by_chunk) == ids
    first = {}
    for k in ids:
        names = [s[0] for s in by_chunk[k]]
        assert names.count("blmac.shard_dispatch") == 4
        assert names.count("blmac.shard_read") == 4
        assert names.count("blmac.reassemble") == 1
        assert "blmac.stage" in names
        for s in by_chunk[k]:
            first.setdefault((k, s[0]), s[1])
    assert first[ids[1], "blmac.shard_dispatch"] < \
        first[ids[0], "blmac.shard_read"]
