"""From a profiler trace to the numbers the per-layer metrics read.

Two steps.  `extract` reads the ``.xplane.pb`` that ``jax.profiler``
writes and keeps only what the reduction needs, as plain JSON-able data:

* ``window``: the start and end, in ns on the trace's clock, of the
  benchmark's ``cb.window`` span;
* ``devices``: per chip, the device operations ``[name, start_ns,
  dur_ns, is_kernel]``, where ``is_kernel`` marks a Mosaic kernel (a
  ``tpu_custom_call``);
* ``host_spans``: the benchmark's own spans (``cb.push``, ``cb.step``,
  ``cb.pull``, ``cb.wait``) as ``[name, start_ns, dur_ns]``.

`reduce` turns that into busy time, kernel time and the idle gaps of
each chip inside the window.  The tests run `reduce` on a small recorded
trace (``tests/data/``).
"""
from __future__ import annotations

import bisect
import glob
import os

SPAN_PREFIX = "cb."
WINDOW_SPAN = "cb.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
KERNEL_MARK = "tpu_custom_call"


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def extract(path: str) -> dict:
    """The reduction's input, read from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    text = str(_stat(ev, "long_name") or "") + ev.name
                    ops.append([ev.name, int(ev.start_ns),
                                int(ev.duration_ns), KERNEL_MARK in text
                                or "custom-call" in ev.name])
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[-1]))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    w = windows[-1]
    return {"window": [w[1], w[1] + w[2]], "devices": devices,
            "host_spans": [s for s in spans if s[0] != WINDOW_SPAN]}


def describe(path: str, per_line: int = 12) -> dict:
    """Planes, lines and the first events of each, with their stats: what
    to read by hand before trusting `extract` on a new chip or JAX."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"name": line.name, "events": len(evs), "first": [
                [e.name, int(e.start_ns), int(e.duration_ns),
                 {k: str(v)[:300] for k, v in e.stats}]
                for e in evs[:per_line]]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


def _clip(start, dur, lo, hi):
    a, b = max(start, lo), min(start + dur, hi)
    return (a, b) if b > a else None


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce(trace: dict) -> dict:
    """Per chip, inside the window: ``busy_s`` (union of operations),
    ``kernel_s`` (sum of Mosaic kernel durations), ``ops_s`` (time per
    operation name) and ``gaps`` (idle intervals, ns); and the window's
    length ``window_s``."""
    lo, hi = trace["window"]
    devices = []
    for dev in trace["devices"]:
        spans, kernel, per_op = [], 0, {}
        for name, start, dur, is_kernel in dev["ops"]:
            c = _clip(start, dur, lo, hi)
            if c is None:
                continue
            spans.append(c)
            kernel += (c[1] - c[0]) if is_kernel else 0
            per_op[name] = per_op.get(name, 0) + c[1] - c[0]
        busy = _union(spans)
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        devices.append({
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "kernel_s": kernel / 1e9,
            "ops_s": {k: v / 1e9 for k, v in per_op.items()},
            "gaps": gaps,
        })
    return {"window_s": (hi - lo) / 1e9, "devices": devices}


def idle_by_span(reduced: dict, trace: dict, device: int = 0) -> dict:
    """Idle seconds of one chip, by the benchmark span the host was in at
    the middle of each gap (``"none"`` outside every span)."""
    # the benchmark's spans come from one thread, one after another
    spans = sorted(trace["host_spans"], key=lambda s: s[1])
    starts = [s[1] for s in spans]
    out = {}
    for a, b in reduced["devices"][device]["gaps"]:
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        name = spans[i][0] if i >= 0 and starts[i] + spans[i][2] >= mid \
            else "none"
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def breakdown(reduced: dict, trace: dict, top: int = 10) -> dict:
    """The device operations that took most time (summed over chips) and
    the idle time by host span, at most ``top`` of each."""
    ops = {}
    for dev in reduced["devices"]:
        for k, v in dev["ops_s"].items():
            ops[k] = ops.get(k, 0.0) + v
    idle = idle_by_span(reduced, trace) if reduced["devices"] else {}
    return {
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }
