"""The comparison that decides ``correct`` fails what it must.

Each test drives a whole run of a small cell (set-up, window, check)
through the real harness with the timed path broken underneath, and
sees ``correct`` come out false: the float32 control in the program's
place, and each fault a cell can have (a step that leaves its stream
state unchanged, half of the batch left out, an answer altered where it
is produced, and, on several chips, one chip's part of the result left
out).  A sound run of the same cell comes out true.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from chipbench.tests import tiny

CELLS = ["tiny.stream", "tiny.tenants"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    return tiny.make(root), root


def _bad(out):
    return not out["correct"] and (
        out["checks"]["mismatched_outputs"]["value"] > 0
        or out["checks"]["missing_outputs"]["value"] > 0)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(bench, workload):
    out = tiny.run(*bench, workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_float32_control_is_not_correct(bench, workload):
    from chipbench.control import Float32Engine

    out = tiny.run(*bench, workload, make_engine=Float32Engine)
    assert _bad(out), out["checks"]
    assert out["checks"]["missing_outputs"]["value"] == 0


def _state_unchanged(monkeypatch):
    from chipbench import drivers
    from repro.filters import FilterBankEngine
    from repro.serving import BankSessionServer

    # its answers never come: wait for them only briefly
    monkeypatch.setattr(drivers, "LATE_S", 2.0)

    push, step = FilterBankEngine.push, BankSessionServer.step

    def frozen_push(self, chunk):
        tail = self._tail
        y = push(self, chunk)
        self._tail = tail
        return y

    def frozen_step(self):
        tails = {k: s.tail for k, s in self.sessions.items()}
        served = step(self)
        for k, s in self.sessions.items():
            s.tail = tails[k]
        return served

    monkeypatch.setattr(FilterBankEngine, "push", frozen_push)
    monkeypatch.setattr(BankSessionServer, "step", frozen_step)


def _patch_outputs(monkeypatch, damage):
    from repro.filters import FilterBankEngine

    apply = FilterBankEngine._apply

    def broken(self, buf):
        y = np.array(apply(self, buf))
        damage(y)
        return y

    monkeypatch.setattr(FilterBankEngine, "_apply", broken)


def _half_left_out(monkeypatch):
    def damage(y):
        y[y.shape[0] // 2:] = 0

    _patch_outputs(monkeypatch, damage)


def _answer_altered(monkeypatch):
    def damage(y):
        y[0, 0, -1] += 1

    _patch_outputs(monkeypatch, damage)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(bench, workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = tiny.run(*bench, workload)
    assert _bad(out), out["checks"]


SHARDED = r"""
import json, pathlib, sys, tempfile
import numpy as np
from chipbench.tests import tiny
from repro.filters import ShardedFilterBankEngine

root = pathlib.Path(tempfile.mkdtemp())
bench = tiny.make(root, traffic=("sharded",))
sound = tiny.run(bench, root, "tiny.sharded")
part = ShardedFilterBankEngine._materialize_shard

def chip_one_missing(self, s, p, y, off):
    got = part(self, s, p, y, off)
    return np.zeros_like(got) if s == 1 else got

ShardedFilterBankEngine._materialize_shard = chip_one_missing
broken = tiny.run(bench, root, "tiny.sharded")
print(json.dumps({"sound": sound, "broken": broken}))
"""


def test_one_chips_part_left_out_is_not_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    root = pathlib.Path(tiny.BENCH).parent
    env["PYTHONPATH"] = os.pathsep.join([str(root), str(root / "src")])
    res = subprocess.run([sys.executable, "-c", SHARDED], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    assert got["sound"]["correct"], got["sound"]["checks"]
    assert _bad(got["broken"]), got["broken"]["checks"]
