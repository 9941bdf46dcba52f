"""Reduce a profiler trace of the traced rounds to the numbers the per-layer
metrics read.

The trace (``jax.profiler`` ``.xplane.pb``) holds, per device plane, an
``XLA Ops`` line (every operation with its start and duration) and an
``XLA Modules`` line (every run of a compiled program), and on the host the
harness's ``TraceAnnotation`` spans: ``bench:window`` around the traced
rounds, ``bench:step`` around each scheduler round and ``bench:sync`` around
the closing sync, plus the program's own ``dispatch`` spans.

* the window: from the first device operation inside the ``bench:window``
  span to the end of the last, so the harness's syncs around the traced
  rounds (the device idles there while the host plans the first round, and
  after the last round while it syncs) are not counted;
* busy: the union of the device's operation intervals inside the window,
  averaged over the devices that ran any;
* idle gaps: the stretches of the window with no operation running, each
  named by the innermost host span that covers its middle;
* top ops: device time per operation name;
* modules: each program run (name, seconds), in order, for per-round time.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW = "bench:window"


def load(trace_dir) -> dict:
    """Device operations, program runs and host spans of the newest
    ``.xplane.pb`` under ``trace_dir``, times in nanoseconds."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, modules, spans = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] += [(_op_name(e.name), e.start_ns,
                                         e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] += [
                        (e.name.split("(")[0], e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith("bench:")
                          or e.name == "dispatch"]
    return {"ops": dict(ops), "modules": dict(modules), "spans": spans}


def _op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def union(intervals) -> list:
    """Merged ``(start, end)`` intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle seconds of the window, the longest operations and idle
    gaps, the program runs inside the window, and the idle share of each
    half of the window (alike when the traced rounds are enough)."""
    win = [(s, s + d) for n, s, d in trace["spans"] if n == WINDOW]
    if not win:
        raise ValueError("no bench:window span in the trace")
    span_lo, span_hi = win[0]
    inside = {dev: [(n, s, s + d) for n, s, d in ops
                    if s + d > span_lo and s < span_hi]
              for dev, ops in trace["ops"].items()}
    if not any(inside.values()):
        raise ValueError("no device operation ran in the traced window")
    lo = max(span_lo, min(s for ops in inside.values() for _, s, _ in ops))
    hi = min(span_hi, max(e for ops in inside.values() for _, _, e in ops))
    mid = (lo + hi) / 2
    busy_per_dev, halves, per_op = [], [], defaultdict(float)
    gaps, gap_list, modules = defaultdict(float), [], []
    spans = [(n, s, s + d) for n, s, d in trace["spans"] if n != WINDOW]
    for dev, ops in sorted(inside.items()):
        merged = union(_clip([(s, e) for _, s, e in ops], lo, hi))
        if not merged:
            continue
        busy_per_dev.append(sum(e - s for s, e in merged))
        halves.append([sum(e - s for s, e in _clip(merged, a, b)) / (b - a)
                       for a, b in ((lo, mid), (mid, hi))])
        for name, s, e in ops:
            c = _clip([(s, e)], lo, hi)
            if c:
                per_op[name] += (c[0][1] - c[0][0]) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = _span_at(spans, (a + b) / 2)
                gaps[name] += (b - a) / 1e9
                gap_list.append([(a - lo) / 1e9, (b - a) / 1e9, name])
        modules += [(n, s / 1e9, d / 1e9)
                    for n, s, d in trace["modules"].get(dev, [])
                    if s + d > lo and s < hi]
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_per_dev) / len(busy_per_dev) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "idle_share_halves": [1.0 - sum(h[i] for h in halves) / len(halves)
                              for i in (0, 1)],
        "top_ops": sorted(([n, t] for n, t in per_op.items()),
                          key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, t] for n, t in gaps.items()),
                            key=lambda x: -x[1])[:top],
        "longest_gaps": sorted(gap_list, key=lambda g: -g[1])[:5],
        "modules": sorted(modules, key=lambda m: m[1]),
    }


def _span_at(spans, t) -> str:
    """Name of the shortest host span that covers time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside harness spans"
