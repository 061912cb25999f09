#!/usr/bin/env python3
"""The program's own spans in a kept profiler trace, and the split of a
push they give.

The program marks each step of a push with a ``blmac.`` span
(`repro.kernels.runtime.span`): ``blmac.push`` (its ``chunk`` id),
``blmac.stage``, ``blmac.dispatch`` (one ``blmac.group`` per tile
group), ``blmac.wait`` and ``blmac.readback`` on `FilterBankEngine`;
``blmac.shard_dispatch``, ``blmac.shard_read`` (each with its
``shard``), ``blmac.reassemble``, ``blmac.verify`` and
``blmac.recover`` on `ShardedFilterBankEngine`.  They sit on the
profiler's host plane, on the clock of the device planes.  ``run.py
--trace 1 --trace-dir <dir>`` keeps the trace; then

    python3 chipbench/spans.py <dir> [--out trace.json]

prints one JSON line: the window's pushes, the mean ``cb.push``, each
program span's ms per push, the share of ``cb.push`` that the steps of
a push cover, and each chip's idle time by the innermost span (the
benchmark's or the program's) that the host was in at each moment of
it.  ``--out`` writes what was read: `tracing.extract`'s fields plus
``program_spans``.
"""
from __future__ import annotations

import argparse
import bisect
import json
import pathlib
import sys

if __name__ == "__main__":
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from chipbench import tracing  # noqa: E402

PREFIX = "blmac."
# the steps one push runs in order, each outside the others
STEPS = ("blmac.stage", "blmac.dispatch", "blmac.wait", "blmac.readback",
         "blmac.shard_dispatch", "blmac.shard_read", "blmac.reassemble")


def extract(path: str) -> dict:
    """`tracing.extract` of one ``.xplane.pb``, plus ``program_spans``:
    ``[name, start_ns, dur_ns, ids]`` of every ``blmac.`` host event,
    ``ids`` its integer arguments, in order of start."""
    from jax.profiler import ProfileData

    trace = tracing.extract(path)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    ids = {k: v for k, v in ev.stats if isinstance(v, int)}
                    spans.append([ev.name, int(ev.start_ns),
                                  int(ev.duration_ns), ids])
    trace["program_spans"] = sorted(spans, key=lambda s: s[1])
    return trace


def totals(trace: dict) -> dict:
    """Per program span name: ``s``, its seconds inside the window, and
    ``count``, the spans that started there."""
    lo, hi = trace["window"]
    out = {}
    for name, start, dur, _ in trace["program_spans"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a and not lo <= start < hi:
            continue
        agg = out.setdefault(name, {"s": 0.0, "count": 0})
        agg["s"] += max(b - a, 0) / 1e9
        agg["count"] += lo <= start < hi
    return out


def timeline(trace: dict) -> list:
    """The innermost span open at each moment, the benchmark's or the
    program's: ``[start_ns, end_ns, name]`` segments in order, none where
    no span is open.  Spans nest, so the innermost is the latest-starting
    span that is open (of two that start together, the shorter)."""
    spans = sorted([s[:3] for s in trace["host_spans"]]
                   + [s[:3] for s in trace.get("program_spans", [])],
                   key=lambda s: (s[1], -s[2]))
    starts = [s[1] for s in spans]
    # reach[i]: the latest end among spans[:i + 1], so the walk back
    # stops once no earlier span can be open
    reach, end = [], float("-inf")
    for _, start, dur in spans:
        end = max(end, start + dur)
        reach.append(end)
    bounds = sorted({s[1] for s in spans} | {s[1] + s[2] for s in spans})
    segments = []
    for a, b in zip(bounds, bounds[1:]):
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and reach[i] >= mid:
            if starts[i] + spans[i][2] >= mid:
                name = spans[i][0]
                if segments and segments[-1][1] == a \
                        and segments[-1][2] == name:
                    segments[-1][1] = b
                else:
                    segments.append([a, b, name])
                break
            i -= 1
    return segments


def idle_by_span(reduced: dict, trace: dict, device: int = 0) -> dict:
    """Idle seconds of one chip by the innermost span the host was in
    during them (`timeline`), ``"none"`` where no span was open.  A gap
    that outlasts a span is split at the span's edges."""
    segments = timeline(trace)
    out, j = {}, 0
    for a, b in reduced["devices"][device]["gaps"]:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        named = 0
        for start, end, name in segments[j:]:
            if start >= b:
                break
            part = min(b, end) - max(a, start)
            out[name] = out.get(name, 0.0) + part / 1e9
            named += part
        if b - a > named:
            out["none"] = out.get("none", 0.0) + (b - a - named) / 1e9
    return out


def _inside(span, outer) -> bool:
    return outer[1] <= span[1] and span[1] + span[2] <= outer[1] + outer[2]


def covered(trace: dict) -> float:
    """Seconds of the window's ``cb.push`` spans that the steps of a push
    (`STEPS`) cover, as the union of their intervals."""
    lo, hi = trace["window"]
    total = 0
    for push in trace["host_spans"]:
        if push[0] != "cb.push" or not lo <= push[1] < hi:
            continue
        steps = [[s[1], s[1] + s[2]] for s in trace["program_spans"]
                 if s[0] in STEPS and _inside(s, push)]
        end = push[1]
        for a, b in sorted(steps):
            total += max(b - max(a, end), 0)
            end = max(end, b)
    return total / 1e9


def summary(trace: dict, chips: int | None = None) -> dict:
    """The split of the window's pushes: see the module's docstring."""
    reduced = tracing.reduce(trace)
    reduced["devices"] = reduced["devices"][:chips]
    lo, hi = trace["window"]
    pushes = [s for s in trace["host_spans"]
              if s[0] == "cb.push" and lo <= s[1] < hi]
    n = len(pushes)
    push_s = sum(s[2] for s in pushes) / 1e9
    out = {"pushes": n, "window_s": reduced["window_s"],
           "push_ms": 1e3 * push_s / n if n else None,
           "span_ms_per_push": {
               k: 1e3 * v["s"] / n for k, v in totals(trace).items()}
           if n else {},
           "steps_share_of_push": covered(trace) / push_s if n else None,
           "chips": []}
    for d, dev in enumerate(reduced["devices"]):
        idle = idle_by_span(reduced, trace, d)
        out["chips"].append({
            "busy_s": dev["busy_s"], "kernel_s": dev["kernel_s"],
            "idle_s_by_span": dict(sorted(idle.items(),
                                          key=lambda kv: -kv[1]))})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a --trace-dir, an .xplane.pb, or the "
                                  "JSON that --out wrote")
    ap.add_argument("--chips", type=int, default=None,
                    help="read only the first CHIPS chips")
    ap.add_argument("--out", default=None,
                    help="write the extracted trace here")
    args = ap.parse_args(argv)
    path = pathlib.Path(args.trace)
    if path.suffix == ".json":
        trace = json.loads(path.read_text())
    else:
        trace = extract(str(path) if path.is_file()
                        else tracing.find_xplane(str(path)))
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(trace))
    print(json.dumps(summary(trace, args.chips)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
