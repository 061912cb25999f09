"""The harness: its refusal off a TPU, BENCHMARK.json's entries each
finding their files, and a configuration, traffic mix and per-layer
metric added as new files being found by name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.tests import tiny

ROOT = tiny.BENCH.parent


def _benchmark():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_harness_refuses_a_cpu_and_names_it(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "fir127_grid.sweep", "--seed", "0", "--seconds", "10", "--trace",
         "0"], env=env, cwd=tmp_path, capture_output=True, text=True,
        timeout=300)
    assert res.returncode != 0
    assert res.stdout == ""
    assert "platform 'cpu'" in res.stderr


def test_harness_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "fir127_grid.sweep", "--seed", "1", "--seconds", "10", "--trace",
         "0"], cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert res.stdout == ""
    assert "no program" in res.stderr


def test_every_entry_of_the_benchmark_finds_its_files():
    bench = _benchmark()
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        assert (tiny.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["per_layer"]:
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
    for path in bench["paths"]:
        assert (ROOT / path).is_dir()


NEW_METRIC = '''"""Pushes made in the window."""


def read(ctx):
    return ctx.counters.get("pushes")
'''


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    bench = tiny.make(tmp_path, traffic=("stream",))
    cfg = dict(tiny.CONFIG, name="other", taps=63, n_filters=8)
    with open(tmp_path / "configs" / "other.json", "w") as f:
        json.dump(cfg, f)
    with open(tmp_path / "traffic" / "mono.json", "w") as f:
        json.dump(dict(tiny.TRAFFIC["stream"], channels=1, chunk=1024), f)
    (tmp_path / "metrics" / "pushes.mono.py").write_text(NEW_METRIC)
    bench["configs"].append({"name": "other", "source": "test",
                             "file": "configs/other.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "other.mono", "config": "other",
                               "traffic": "mono", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "pushes.mono", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "out_samples_per_s",
                               "workloads": ["other.mono"]})
    out = tiny.run(bench, tmp_path, "other.mono", trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["pushes.mono"]["value"] == out["attempted"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes", "busy_s", "window_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", ["tiny.stream", "tiny.tenants"])
def test_end_to_end_line_carries_the_cells_metrics(tmp_path, workload):
    bench = tiny.make(tmp_path)
    out = tiny.run(bench, tmp_path, workload)
    assert out["correct"], out["checks"]
    names = {m["name"] for m in bench["end_to_end"]}
    assert set(out["metrics"]) == names
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert list(out)[-1] == "checks"


BURSTY = {"loop": "open", "tenants": 8, "rows_per_tenant": 4, "lanes": 2,
          "journal_fsync": True, "chunk": 512, "rate_chunks_per_s": 24.0,
          "rate_skew": 1.1, "burst": {"on_s": 0.2, "off_s": 0.2},
          "chunk_sizes": {"median": 512, "sigma": 0.8, "min": 128,
                          "max": 2048},
          "warm_depths": [1, 2]}

HOOK = '''"""Every tenant sends one 300-sample chunk at the window's start, and
the closed loop runs on a sharded engine over a 1x1 mesh."""
import numpy as np


def arrivals(seed, mix, seconds):
    n = int(mix["tenants"])
    return np.zeros(n), np.arange(n), np.full(n, 300)


def make_engine(program, mix, channels, chunk_hint):
    from repro.distributed.sharding import bank_mesh
    from repro.filters import ShardedFilterBankEngine

    return ShardedFilterBankEngine(program, channels=channels,
                                   chunk_hint=chunk_hint,
                                   mesh=bank_mesh(1, 1))
'''


def _add_cell(bench, root, name, mix, hook=None):
    with open(root / "traffic" / f"{name}.json", "w") as f:
        json.dump(mix, f)
    if hook is not None:
        (root / "traffic" / f"{name}.py").write_text(hook)
    bench["workloads"].append({"name": f"tiny.{name}", "config": "tiny",
                               "traffic": name, "chips": 1, "why": "test"})


def test_mix_with_skew_bursts_and_sizes_runs_from_data_alone(tmp_path):
    bench = tiny.make(tmp_path, traffic=())
    _add_cell(bench, tmp_path, "bursty", BURSTY)
    out = tiny.run(bench, tmp_path, "tiny.bursty", seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 8
    assert out["metrics"]["latency_p95_ms"]["value"] > 0


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_mix_hooks_in_their_own_file_are_found_by_name(tmp_path, loop):
    bench = tiny.make(tmp_path, traffic=())
    base = tiny.TRAFFIC["tenants" if loop == "open" else "stream"]
    _add_cell(bench, tmp_path, "hooked", base, HOOK)
    out = tiny.run(bench, tmp_path, "tiny.hooked")
    assert out["correct"], out["checks"]
    if loop == "open":
        assert out["attempted"] == base["tenants"]
        assert out["metrics"]["out_samples_per_s"]["value"] * \
            out["metrics"]["latency_p95_ms"]["value"] > 0


def test_engine_args_with_a_mesh_build_the_named_engine():
    from chipbench import drivers
    from chipbench.design import design
    from repro.compiler import compile_bank
    from repro.filters import ShardedFilterBankEngine

    program = compile_bank(design(dict(tiny.CONFIG, taps=31, n_filters=8)))
    mix = dict(tiny.TRAFFIC["sharded"],
               engine_args={"mesh": [1, 1], "n_bank_shards": 1})
    e = drivers.build_engine(program, mix, 1, 1024)
    assert isinstance(e, ShardedFilterBankEngine)
