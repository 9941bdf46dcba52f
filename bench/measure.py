"""The metrics a run reports, each read by a small reader of its own.

Every metric named in ``BENCHMARK.json`` has a file ``bench/readers/<name>.py``
with a function ``read(ctx)`` that returns the metric's value from the run's
``Context``, or None when the run holds nothing for it to read; the result
line then leaves the metric out.  A new metric is a new reader file.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

from . import work as W
from .program import ROUND_PROGRAMS
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
READERS = pathlib.Path(__file__).resolve().parent / "readers"


@dataclasses.dataclass
class Context:
    plan: object                 # harness.Plan
    window: object               # harness.Window
    setup_s: float
    occupancy: list              # lane occupancy of each window round
    device_kind: str


def peak(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def traced_work(ctx: Context):
    """The replay's work of each traced round that ran a program, or None
    without a trace."""
    tw = ctx.window.trace
    if tw is None:
        return None
    return [w for w in ctx.plan.replay.work[tw.first_round:tw.last_round]
            if w.kind != "idle"]


def round_program_runs(ctx: Context) -> list:
    """``(kind, seconds)`` of each run of a round program in the traced
    window, or [] without a trace."""
    tw = ctx.window.trace
    if tw is None:
        return []
    return [(k, d) for n, _, d in tw.reduced["modules"]
            for prog, k in ROUND_PROGRAMS.items() if n.startswith(prog)]


def program_seconds(ctx: Context, kind: str) -> list:
    """Device seconds of each run of the round program ``kind`` ("fused" or
    "decode") in the traced window."""
    return [d for k, d in round_program_runs(ctx) if k == kind]


def dims(ctx: Context) -> W.Dims:
    return W.Dims.of(ctx.plan.cell.config)


def roofline_share(ctx: Context):
    """Least time of the traced rounds over the device's busy time in the
    traced window (the union of its operations, which is those rounds')."""
    work = traced_work(ctx)
    if not work:
        return None
    pk, dm = peak(ctx.device_kind), dims(ctx)
    least = sum(W.least_seconds(*W.round_counts(dm, w), pk) for w in work)
    return 100.0 * least / ctx.window.trace.reduced["busy_s"]


def mfu(ctx: Context):
    work = traced_work(ctx)
    if not work:
        return None
    dm = dims(ctx)
    flops = sum(W.round_counts(dm, w)[0] for w in work)
    return 100.0 * flops / (ctx.window.trace.reduced["window_s"]
                            * peak(ctx.device_kind)["bf16_flops_per_s"])


def decode_step_ms(ctx: Context):
    """Device time of the traced decode-only rounds' programs over the
    decode steps those rounds ran.  Sums, not means: the trace may show two
    back-to-back runs of a program as one."""
    work = traced_work(ctx)
    runs = program_seconds(ctx, "decode") if work else []
    steps = sum(len(w.decode) for w in work or [] if w.kind == "decode")
    if not runs or not steps:
        return None
    return 1e3 * sum(runs) / steps


def idle_share(ctx: Context):
    tw = ctx.window.trace
    return None if tw is None else 100.0 * tw.reduced["idle_share"]


def occupancy(ctx: Context):
    occ = ctx.occupancy
    return 100.0 * sum(occ) / len(occ) if occ else None


def read_all(metrics: list, ctx: Context, root: pathlib.Path) -> dict:
    """``{name: {"value", "unit"}}`` of every metric whose reader found
    something to read."""
    out = {}
    for m in metrics:
        path = root / "bench" / "readers" / f"{m['name']}.py"
        if not path.exists():
            path = READERS / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_reader_{m['name']}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
