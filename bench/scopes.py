"""Device time of the traced rounds by the model's phase and part, and host
time in the program's own spans, from a profiler trace.

The program names its parts on the device (``repro.obs.scopes``): every
instruction of a compiled round program carries its scope path in its
``op_name`` metadata.  ``scope_map`` reads that path from the program's
compiled text, per instruction name, so the trace's operations (named by
instruction, ``fusion.12``) find their (phase, part) without a guess from
the name.  A fusion that spans two scopes counts where XLA's metadata puts
it; an instruction the compiler added, with no metadata, counts with the
first instruction that uses its result.

``reduce`` charges each stretch of device time to the innermost operation
running then: a leaf operation's whole time, an enclosing one (a ``while``)
only where none of its operations runs.  So nothing counts twice, and the
parts sum to the device's busy time.  An operation belongs to the program
run (``XLA Modules``) its start falls in, and takes its scope from that
program's map; one with no part scope counts as ``unscoped``.

On the host, every serve-loop span (``SPANS``) is a profiler annotation
when the program runs with ``Obs(xla_annotations=True)``: idle gaps are
named by the innermost of them, and ``host_round_share`` is the time in
``round`` spans less the ``sync`` spans inside them, over the window.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from collections import defaultdict

from repro.obs.scopes import PARTS, PHASES, SPANS, scope_of

from . import trace as TRC

UNSCOPED = "unscoped"
NO_PHASE = "other"                       # outside both phases of a round

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r'%([\w.\-]+)')
_USERS_SEARCHED = 64


def scope_map(hlo_text: str) -> dict:
    """``{instruction: [phase, part]}`` of a compiled program's text, for
    every instruction whose ``op_name`` has a phase or a part scope.

    An instruction the compiler added has no metadata (a layout copy, an
    async ``copy-start``/``copy-done`` that prefetches a weight, a buffer
    allocation): it counts with the first instruction with metadata that
    uses its result, directly or through others without."""
    meta, users, bare = {}, defaultdict(list), []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        if op is None:
            bare.append(name)
        else:
            meta[name] = list(scope_of(op.group(1)))
        for operand in _OPERAND.findall(rest.split(", metadata=")[0]):
            users[operand].append(name)
    out = {n: v for n, v in meta.items() if v != [None, None]}
    for name in bare:
        seen, queue = {name}, list(users[name])
        while queue and len(seen) < _USERS_SEARCHED:
            user = queue.pop(0)
            if user in meta:
                if meta[user] != [None, None]:
                    out[name] = meta[user]
                break
            if user not in seen:
                seen.add(user)
                queue += users[user]
    return out


def load(trace_dir) -> dict:
    """As ``trace.load``, with program runs named with their program id
    (``jit__fused_step_impl(1320576775802860182)``) and host spans that are
    the harness's ``bench:*`` or the program's own."""
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
                    ops[plane.name] += [(TRC._op_name(e.name), e.start_ns,
                                         e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] += [(e.name, e.start_ns,
                                             e.duration_ns)
                                            for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith("bench:") or e.name in SPANS]
    return {"ops": dict(ops), "modules": dict(modules), "spans": spans}


def window(trace: dict) -> tuple:
    """``(lo, hi)`` ns: the first and last device operation inside the
    ``bench:window`` span, as ``trace.reduce`` bounds it."""
    lo, hi = next((s, s + d) for n, s, d in trace["spans"]
                  if n == TRC.WINDOW)
    inside = [(s, s + d) for ops in trace["ops"].values() for _, s, d in ops
              if s + d > lo and s < hi]
    return (max(lo, min(s for s, _ in inside)),
            min(hi, max(e for _, e in inside)))


def innermost(ops) -> dict:
    """Nanoseconds of each ``key`` of ``ops`` (``(key, start, end)``) in
    which it is the innermost operation running.  An operation that outlasts
    the one around it runs on as the next's sibling."""
    heap = [(s, -e, i, k) for i, (k, s, e) in enumerate(ops) if e > s]
    heapq.heapify(heap)
    out, stack, n = defaultdict(float), [], len(heap)
    # stack entries: [key, end, time up to which it has been charged]

    def close_until(t):
        while stack and stack[-1][1] <= t:
            key, end, cur = stack.pop()
            out[key] += end - cur
            if stack:
                stack[-1][2] = end

    while heap:
        s, neg_e, _, key = heapq.heappop(heap)
        e = -neg_e
        close_until(s)
        if stack:
            top = stack[-1]
            out[top[0]] += s - top[2]
            top[2] = s
            if e > top[1]:                   # outlasts its parent
                n += 1
                heapq.heappush(heap, (top[1], -e, n, key))
                e = top[1]
        stack.append([key, e, s])
    close_until(float("inf"))
    return dict(out)


def _module_of(runs, t):
    i = bisect.bisect_right(runs[1], t) - 1
    if i >= 0 and t < runs[1][i] + runs[2][i]:
        return runs[0][i]
    return None


def op_seconds(trace: dict, lo: int, hi: int) -> dict:
    """``{(program run name, operation): seconds}`` in which each operation
    was the innermost one running inside ``[lo, hi]``, averaged over the
    devices that ran any."""
    total, devices = defaultdict(float), 0
    for dev, ops in trace["ops"].items():
        runs = sorted(trace["modules"].get(dev, []), key=lambda m: m[1])
        runs = ([n for n, _, _ in runs], [s for _, s, _ in runs],
                [d for _, _, d in runs])
        clipped = []
        for name, s, d in ops:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                clipped.append(((_module_of(runs, s), name), a, b))
        if not clipped:
            continue
        devices += 1
        for key, ns in innermost(clipped).items():
            total[key] += ns / 1e9
    return {k: v / devices for k, v in total.items()}


def device_scopes(per_op: dict, maps: dict) -> dict:
    """``{(phase, part): seconds}`` of ``op_seconds``; ``maps`` is
    ``{program run name: scope_map}``."""
    total = defaultdict(float)
    for (mod, name), t in per_op.items():
        phase, part = maps.get(mod, {}).get(name, (None, None))
        total[(phase or NO_PHASE, part or UNSCOPED)] += t
    return dict(total)


def host_round_seconds(spans, lo: int, hi: int) -> float:
    """Seconds inside ``[lo, hi]`` in ``round`` spans and outside the
    ``sync`` spans within them."""
    def clip(s, d):
        return max(0, min(s + d, hi) - max(s, lo))

    rounds = [(s, s + d) for n, s, d in spans if n == "round"]
    busy = sum(clip(s, e - s) for s, e in rounds)
    waits = sum(clip(s, d) for n, s, d in spans if n == "sync"
                and any(a <= s and s + d <= b for a, b in rounds))
    return (busy - waits) / 1e9


def reduce(trace: dict, maps: dict, top: int = 10) -> dict:
    """``trace.reduce``'s numbers over the same window, with idle gaps named
    by the program's spans, plus ``scopes`` (``[phase, part, seconds]``,
    longest first), ``part_s`` (seconds per part over both phases),
    ``uncovered_share`` (the share of busy time in unscoped operations),
    ``top_unscoped`` (the longest of those, ``[run, operation, seconds]``)
    and ``host_round_share``."""
    red = TRC.reduce(trace)
    lo, hi = window(trace)
    per_op = op_seconds(trace, lo, hi)
    scopes = device_scopes(per_op, maps)
    part_s = dict.fromkeys(PARTS + (UNSCOPED,), 0.0)
    for (_, part), t in scopes.items():
        part_s[part] += t
    red["scopes"] = sorted(([ph, pa, t] for (ph, pa), t in scopes.items()),
                           key=lambda x: -x[2])
    red["part_s"] = part_s
    red["uncovered_share"] = part_s[UNSCOPED] / red["busy_s"]
    unscoped = [[mod, name, t] for (mod, name), t in per_op.items()
                if (maps.get(mod, {}).get(name) or [None, None])[1] is None]
    red["top_unscoped"] = sorted(unscoped, key=lambda x: -x[2])[:top]
    red["host_round_share"] = (host_round_seconds(trace["spans"], lo, hi)
                               / red["window_s"])
    return red


def phase_part_table(red: dict) -> str:
    """The per-(phase, part) seconds as one line of ``phase/part=s``."""
    order = {p: i for i, p in enumerate(PHASES + (NO_PHASE,))}
    rows = sorted(red["scopes"], key=lambda x: (order[x[0]], -x[2]))
    return " ".join(f"{ph}/{pa}={t:.6f}" for ph, pa, t in rows)
