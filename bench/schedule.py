"""What a replay of a cell's schedule on the host yields, before the chip
does any work (the replay itself is ``program.replay``).

The scheduler decides everything from the request list: which requests a
round admits, how each prefill is chunked and padded, which lane bucket a
decode burst runs at, and so every program the window will call.  No
decision reads the clock or a token value, once no request can end before
its budget (the harness gives the engine a stop id that sampling cannot
produce, and checks every finished request).  So the real
``ContinuousBatchingScheduler``, driven over ``program.HostEngine`` (an
engine that keeps only lane counters, on the CPU), makes the same rounds the
chip will.  The replay gives:

* each distinct device program the rounds call, with one set of arguments
  for it (``Call``), which set-up runs once so that nothing compiles in the
  window;
* each round's work (``RoundWork``): the decode steps with their live
  lanes and context lengths, and the prefill passes with their rows, which
  the work function turns into operations and bytes;
* the scheduler's counters after every round, which the harness compares
  with the real run's, so a schedule that parts from the replay is caught.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np


@dataclasses.dataclass
class RoundWork:
    kind: str                           # "fused" or "decode"
    decode: list                        # per step: (live lanes, sum of contexts)
    prefill: list                       # per pass: [(tokens, pos0), ...] rows


@dataclasses.dataclass
class Call:
    fn: str                             # "_fused_step" or "_decode_chunk_serve"
    kwargs: dict                        # static arguments
    admit: Optional[dict]               # host leaves as the scheduler built them
    parts: tuple                        # sub-caches replaced by "fresh"/"out"
    first_round: int


class _Sub:
    """Stands in for a chunked prefill's dense sub-cache: ``fresh`` when made
    by ``make_cache`` (an uncommitted array on the chip), else a program
    output (committed).  JAX keys compiled programs on that difference."""

    def __init__(self, fresh: bool):
        self.fresh = fresh


def _leaf_sig(x):
    if isinstance(x, _Sub):
        return ("fresh" if x.fresh else "out",)
    a = np.asarray(x)
    return ("np", a.shape, str(a.dtype))


def signature(fn: str, kwargs: dict, admit, parts) -> tuple:
    """Key of the compiled program a call runs: static arguments plus the
    structure, shapes, dtypes and kinds of the per-round inputs."""
    leaves, tree = jax.tree_util.tree_flatten(
        (admit, parts), is_leaf=lambda x: isinstance(x, _Sub))
    return (fn, tuple(sorted(kwargs.items())), str(tree),
            tuple(_leaf_sig(x) for x in leaves))


COUNTERS = ("decode_steps", "prefill_tokens", "prefill_chunks",
            "active_lane_steps", "dispatches", "compactions", "page_waits",
            "prefix_hits", "preemptions")


@dataclasses.dataclass
class Replay:
    calls: list[Call]
    work: list[RoundWork]               # one per round, in order
    counters: list[dict]                # scheduler counters after each round
    harvest_sizes: list[int]            # lanes retired in one round, as seen
    window_start: int                   # first round with every request due
    sync_rounds: list[int]              # rounds the harness syncs before
    compaction_rounds: list[int]        # rounds that compacted the lanes
    rounds: int


