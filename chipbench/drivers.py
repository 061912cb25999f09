"""The two ways a cell offers load to the served path.

`ClosedLoop` pushes chunks back to back through `FilterBankEngine.push`
or `ShardedFilterBankEngine.push`.  `OpenLoop` sends every tenant's
chunks on a schedule through `BankSession.push`, `BankSessionServer.step`
and `BankSession.pull`, whether or not the server keeps up.

Each driver builds the system under test from a compiled program
(`setup`, counted in set-up time), drives it for a window of seconds
(`window`), keeps the outputs that the check compares, hands them out
with their input signals (`checks`), and frees the system (`close`).  A
driver calls the program only through those entry points, inside the
benchmark's own spans (``cb.push``, ``cb.step``, ``cb.pull``,
``cb.wait``), which the trace reduction reads.
"""
from __future__ import annotations

import contextlib
import os
import tempfile
import time

import numpy as np

from . import generator

POOL = 16  # distinct chunks a closed loop cycles through
WARM_PUSHES = 3  # the first push (no history yet) and steady ones
KEEP = 4  # window pushes a closed loop keeps for the check
LATE_S = 60.0  # how long an open loop waits for answers after its window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Spans:
    """``spans("push")`` is a profiler span named ``cb.push`` when
    tracing, and costs nothing otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation

        return TraceAnnotation("cb." + name)


class CompileCounter:
    """Counts executables JAX builds (compiled or read from the
    persistent cache) while it is open."""

    def __init__(self):
        self.count = 0

    def _on(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


def engine_text(e) -> str:
    """The engine's plan, for the set-up log."""
    if hasattr(e, "dispatch_plan"):
        return (f"FilterBankEngine plan={e.dispatch_plan} "
                f"groups={len(e.bank_schedule.groups)}")
    return e.describe()


def build_engine(program, traffic: dict, channels: int, chunk: int):
    """The engine a closed-loop mix names, with its ``engine_args``."""
    from repro.filters import FilterBankEngine, ShardedFilterBankEngine

    cls = {"single": FilterBankEngine,
           "sharded": ShardedFilterBankEngine}[traffic["engine"]]
    kw = dict(traffic.get("engine_args", {}))
    if "mesh" in kw:
        from repro.distributed.sharding import bank_mesh

        kw["mesh"] = bank_mesh(*kw["mesh"])
    return cls(program, channels=channels, chunk_hint=chunk, **kw)


class ClosedLoop:
    def __init__(self, cfg: dict, traffic: dict, seed: int, hooks=None):
        self.traffic = traffic
        self.hooks = hooks
        self.channels = int(traffic["channels"])
        self.chunk = int(traffic["chunk"])
        self.pool = generator.chunk_pool(seed, POOL, self.channels,
                                         self.chunk, int(cfg["sample_bits"]))
        self.keep_rng = generator.rng(seed, generator.CHECKED)
        self.kept = []  # (push index, outputs)
        self.k = 0

    def setup(self, program, make_engine=None) -> None:
        """Build the engine (``make_engine`` stands in for the program's
        own, as the control does) and push the warm-up chunks."""
        self.taps = program.taps
        if make_engine is not None:
            self.engine = make_engine(program, channels=self.channels,
                                      chunk_hint=self.chunk)
        elif hasattr(self.hooks, "make_engine"):
            self.engine = self.hooks.make_engine(program, self.traffic,
                                                 self.channels, self.chunk)
        else:
            self.engine = build_engine(program, self.traffic, self.channels,
                                       self.chunk)
        for _ in range(WARM_PUSHES):
            y = self._push()
            if self.k == 1:
                self.kept.append((0, y))  # the path with no history yet

    def _push(self):
        y = self.engine.push(self.pool[self.k % POOL])
        self.k += 1
        return y

    def window(self, seconds: float, spans: Spans) -> dict:
        lat, outputs, n = [], 0, 0
        start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            with spans("push"):
                y = self._push()
            t1 = time.perf_counter()
            lat.append(t1 - t0)
            outputs += y.size
            # a reservoir of KEEP pushes, drawn from the seed
            slot = n if n < KEEP else int(self.keep_rng.integers(n + 1))
            if slot < KEEP:
                item = (self.k - 1, y)
                if slot < len(self.kept) - 1:
                    self.kept[slot + 1] = item
                else:
                    self.kept.append(item)
            n += 1
            if t1 - start >= seconds:
                break
        return {"window_s": t1 - start, "latencies_s": lat,
                "outputs": outputs, "pushes": n, "attempted": n,
                "n_out": self.chunk, "channels": self.channels}

    def describe(self) -> str:
        return engine_text(self.engine)

    def checks(self):
        """(name, outputs (R, n), signal, rows) per compared item."""
        h = self.taps - 1
        for k, y in self.kept:
            prev = self.pool[(k - 1) % POOL][:, self.chunk - h:] if k else \
                self.pool[0][:, :0]
            x = np.concatenate([prev, self.pool[k % POOL]], axis=1)
            for c in range(self.channels):
                yield f"push {k} channel {c}", y[:, c, :], x[c], None

    def close(self) -> None:
        self.engine = None


class OpenLoop:
    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 hooks=None):
        self.tenants = int(traffic["tenants"])
        self.rows = int(traffic["rows_per_tenant"])
        self.lanes = int(traffic["lanes"])
        self.fsync = bool(traffic["journal_fsync"])
        self.chunk = int(traffic["chunk"])
        self.depths = [int(d) for d in traffic["warm_depths"]]
        self.traffic, self.hooks = traffic, hooks
        make = getattr(hooks, "arrivals", generator.arrivals)
        self.due, self.who, self.size = make(seed, traffic, seconds)
        self.n_warm = 1 + sum(self.depths)
        per = np.bincount(self.who, weights=self.size,
                          minlength=self.tenants)
        self.streams = generator.session_streams(
            seed, self.tenants, self.n_warm * self.chunk + int(per.max()),
            int(cfg["sample_bits"]))
        self.outs = [[] for _ in range(self.tenants)]
        self.delivered = np.zeros(self.tenants, np.int64)
        self.sent = np.zeros(self.tenants, np.int64)  # samples pushed

    def setup(self, program, make_engine=None) -> None:
        """Open the server and the tenants' sessions, and warm every lane
        length of ``warm_depths``.  ``make_engine`` stands in for the
        server's own lane engine, as the control does."""
        from repro.serving import BankSessionServer

        self.taps = program.taps
        if make_engine is not None:
            engine = make_engine(program, channels=self.lanes)
        elif hasattr(self.hooks, "make_engine"):
            engine = self.hooks.make_engine(program, self.traffic,
                                            self.lanes, self.chunk)
        else:
            engine = None
        self.qrows = [np.arange(self.rows * i, self.rows * (i + 1))
                      for i in range(self.tenants)]
        self._tmp = tempfile.TemporaryDirectory(prefix="chipbench-wal-")
        self.server = BankSessionServer(
            program, n_slots=self.lanes, auto_step=False,
            chunk_hint=self.chunk, journal_fsync=self.fsync,
            journal=os.path.join(self._tmp.name, "wal"), engine=engine)
        self.sessions = [self.server.open_session(r) for r in self.qrows]
        # every lane length the window can see: each tenant's first chunk
        # (no history yet), then 1, 2, ... chunks queued behind a tail
        for depth in [1] + self.depths:
            for i in range(self.tenants):
                for _ in range(depth):
                    self._send(i, self.chunk)
            self.server.step()
            self._pull_all()

    def _send(self, i: int, n: int) -> None:
        a = int(self.sent[i])
        self.sessions[i].push(self.streams[i, a:a + n])
        self.sent[i] += n

    def _pull(self, i: int, spans: Spans) -> int:
        """Pull tenant ``i``'s outputs and keep them; returns how many
        samples came."""
        with spans("pull"):
            y = self.sessions[i].pull()
        if y.shape[1]:
            self.delivered[i] += y.shape[1]
            self.outs[i].append(y)
        return y.shape[1]

    def _pull_all(self) -> None:
        for i in range(self.tenants):
            self._pull(i, Spans(False))

    def window(self, seconds: float, spans: Spans) -> dict:
        h = self.taps - 1
        n_chunks = len(self.due)
        lag, lat = [], np.full(n_chunks, np.nan)
        # window chunk j of tenant i is answered once this many outputs
        # of tenant i have been delivered
        need = [[] for _ in range(self.tenants)]
        waiting = [0] * self.tenants  # next unanswered entry of need[i]
        step_s = []
        occ0 = self.server.serve_stats()
        idx = 0
        start = time.perf_counter()
        deadline = start + seconds + LATE_S
        while True:
            now = time.perf_counter()
            while idx < n_chunks and start + self.due[idx] <= now:
                i = int(self.who[idx])
                with spans("push"):
                    self._send(i, int(self.size[idx]))
                lag.append(time.perf_counter() - start - self.due[idx])
                need[i].append((int(self.sent[i]) - h, idx))
                idx += 1
            t0 = time.perf_counter()
            with spans("step"):
                served = self.server.step()
            if served:
                step_s.append(time.perf_counter() - t0)
                for i in range(self.tenants):
                    if not self._pull(i, spans):
                        continue
                    t_done = time.perf_counter() - start
                    while waiting[i] < len(need[i]) and \
                            need[i][waiting[i]][0] <= self.delivered[i]:
                        j = need[i][waiting[i]][1]
                        lat[j] = t_done - self.due[j]
                        waiting[i] += 1
            answered = sum(waiting)
            if idx == n_chunks and answered == n_chunks:
                break
            if time.perf_counter() > deadline:
                break
            if not served and idx < n_chunks:
                pause = start + self.due[idx] - time.perf_counter()
                if pause > 0:
                    with spans("wait"):
                        time.sleep(pause)
        end = time.perf_counter()
        occ1 = self.server.serve_stats()
        rounds = occ1["rounds"] - occ0["rounds"]
        fill = (occ1["occupancy"] * occ1["rounds"]
                - occ0["occupancy"] * occ0["rounds"])
        done = lat[~np.isnan(lat)]
        return {"window_s": max(end - start, seconds), "latencies_s": done,
                "attempted": n_chunks, "missing_chunks": n_chunks - done.size,
                "gen_lag_s": lag, "step_s": step_s,
                "occupancy": fill / rounds if rounds else None,
                "outputs": int(self.size[~np.isnan(lat)].sum()) * self.rows}

    def describe(self) -> str:
        return (f"BankSessionServer lanes={self.lanes} over "
                f"{engine_text(self.server.engine)}")

    def checks(self):
        for i in range(self.tenants):
            got = np.concatenate(self.outs[i], axis=1) if self.outs[i] \
                else np.zeros((self.rows, 0), np.int32)
            x = self.streams[i, :int(self.sent[i])]
            yield f"tenant {i}", got, x, self.qrows[i]

    def close(self) -> None:
        self.server.close()
        self.server = self.sessions = None
        self._tmp.cleanup()
