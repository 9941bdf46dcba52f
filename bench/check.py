"""Decide ``correct``: every number compared, each beside its limit.

Served tokens: a sample of the requests the window finished, drawn from the
seed and always holding the longest of them, until it has the cell's
``check_tokens`` served tokens.  The reference runs once over each prompt
with its served tokens; ``logit_gap`` is the widest gap by which a served
token's reference logit lies below the reference's best at its position.
All tokens are greedy, so a sound program's gap is rounding only.

The yardstick, in a traced run: every per-layer metric listed for the cell
reads a value, and the round programs are found in the trace.

The run itself: no request may end before its budget, the backlog may not
drain in the window, nothing may compile in the window, and the scheduler's
counters at the window's end must equal the host replay's for that round
(else the per-round work the metrics read is not what ran).
"""

from __future__ import annotations

import numpy as np

from . import reference as REF


def schedule_checks(plan, sched, win) -> list[dict]:
    short = sum(int(r["n_generated"] != plan.requests[rid].budget)
                for rid, r in sched.results.items())
    cs = plan.replay.counters
    if 0 < win.first_round and win.last_round <= len(cs):
        a, b = cs[win.first_round - 1], cs[win.last_round - 1]
        diff = sum(int(win.counters[k] - win.counters0[k] != b[k] - a[k])
                   for k in a)
    else:                       # past the replay: the backlog had drained
        diff = len(cs[0])
    return [
        {"name": "ended_before_budget", "value": short, "limit": 0},
        {"name": "counters_off_replay", "value": diff, "limit": 0},
        {"name": "backlog_drained", "value": int(win.queued_at_end == 0),
         "limit": 0},
        {"name": "window_compiles", "value": win.compiles, "limit": 0},
    ]


def trace_checks(metric_list: list, metrics: dict, ctx) -> list[dict]:
    """The yardstick itself, in a traced run: every per-layer metric listed
    for the cell found something to read, and the traced rounds' programs
    were found in the trace under the round programs' names (a renamed or
    split round program would otherwise silence the metrics that read it)."""
    from .measure import round_program_runs, traced_work

    unread = [m["name"] for m in metric_list if m["name"] not in metrics]
    ran = traced_work(ctx) or []
    found = round_program_runs(ctx)
    return [
        {"name": "per_layer_unread", "value": len(unread), "limit": 0},
        {"name": "round_programs_unfound",
         "value": int(bool(ran) and not found), "limit": 0},
    ]


def sample(served: dict, seed: int, tokens: int) -> list[int]:
    """Request ids to check: the one with the longest sequence, then others
    in an order drawn from the seed, until ``tokens`` served tokens."""
    if not served:
        return []
    rids = sorted(served)
    longest = max(rids, key=lambda r: (len(served[r][0]) + len(served[r][1]),
                                       -r))
    rest = [r for r in rids if r != longest]
    order = np.random.default_rng([seed, 1]).permutation(len(rest))
    picked, n = [longest], len(served[longest][1])
    for i in order:
        if n >= tokens:
            break
        picked.append(rest[i])
        n += len(served[rest[i]][1])
    return picked


def gaps(conf: dict, params, served: dict, rids: list, length: int,
         control: bool = False) -> list[float]:
    """Per request, the widest gap of its served tokens; with ``control``,
    of the tokens the float8 reference puts first instead."""
    out = []
    for rid in rids:
        prompt, toks = served[rid]
        seq = np.concatenate([prompt, toks]).astype(np.int32)
        padded = np.zeros((length,), np.int32)
        padded[:len(seq)] = seq
        rows = slice(len(prompt) - 1, len(seq) - 1)
        ref = np.asarray(REF.logits(conf, params, padded))[rows]
        if control:
            low = np.asarray(REF.logits(conf, params, padded,
                                        quantize=True))[rows]
            toks = low.argmax(axis=-1)
        out.append(REF.served_gap(ref, np.asarray(toks)))
    return out


def served_checks(plan, served: dict, seed: int,
                  control: bool = False) -> list[dict]:
    """The reference comparison, run once the program's state is freed.
    With ``control``, also the float8 control's reading on the same sample
    (``control_gap``: calibration only, never part of ``correct``)."""
    import jax

    conf = plan.cell.config
    limit = float(plan.server["logit_gap_limit"])
    rids = sample(served, seed, int(plan.server["check_tokens"]))
    if not rids:
        return [{"name": "checked_requests", "value": 0, "limit": -1}]
    params = plan.init_fn(jax.random.PRNGKey(seed))
    g = gaps(conf, params, served, rids, plan.server["max_len"])
    out = [{"name": "logit_gap", "value": max(g), "limit": limit}]
    if control:
        c = gaps(conf, params, served, rids, plan.server["max_len"],
                 control=True)
        out.append({"name": "control_gap", "value": max(c), "limit": None})
    return out
