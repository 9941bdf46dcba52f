"""Everything the benchmark knows of the program's private names.

The harness drives the launcher's ``ServeEngine`` and
``ContinuousBatchingScheduler`` through their public constructors and
``submit``/``step``, but a few things have no public hook yet, and each sits
here, in this one module:

* ``HostEngine``: a stand-in for ``ServeEngine`` that keeps only lane
  counters, with the signatures of its round programs ``_fused_step`` and
  ``_decode_chunk_serve``, so the real scheduler can be replayed on the host
  (``replay``), which calls ``_flush_stash`` where the harness syncs;
* the serve state the scheduler holds between rounds (``state_of``), the
  round in flight (``flush``), and the committed tokens of live lanes
  (``committed_tokens``);
* warming the round programs and the harvest's scatters before the window
  (``warm_empty``, ``warm``, ``call_inputs``), and the same programs' bodies
  for sizing without a chip (``sizing_programs``);
* the compiled programs' names in a profiler trace (``ROUND_PROGRAMS``), and
  the host clock around each call of a round program (``RoundClock``).

A change to the program's round programs, its harvest or its serve state is
a change to this module only.  The public hook that would let it go is
listed in ``PERF.md`` (Open questions).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import ContinuousBatchingScheduler

from . import schedule as SC
from .schedule import Call, Replay, RoundWork, _Sub, signature

# compiled programs that each carry one whole scheduling round, as a
# profiler trace names them
ROUND_PROGRAMS = {"jit__fused_step_impl": "fused",
                  "jit__decode_chunk_impl": "decode"}


# ---------------------------------------------------------------------------
# the host replay
# ---------------------------------------------------------------------------

class HostEngine:
    """The parts of ``ServeEngine`` the scheduler calls, kept as lane
    counters on the host: a burst runs each live lane until its budget, as
    the device loop does when no stop token is sampled."""

    def __init__(self, cfg, max_new_tokens: int, stop_token: int):
        self.cfg = cfg
        self.max_new_tokens = max_new_tokens
        self.stop_token = stop_token
        self.params = None
        self.mesh = None
        self.default_sampling = None
        self.calls: dict = {}
        self.work: list[RoundWork] = []
        self.round = 0

    def make_paged_cache(self, b, max_len, *, page_size, pool_pages,
                         src_len=None):
        n_pages = -(-max_len // page_size)
        return {"page_table": jnp.zeros((b, n_pages), jnp.int32),
                "pos": jnp.zeros((b,), jnp.int32)}

    def make_cache(self, b, max_len, batch=None, src_len=None):
        return _Sub(fresh=True)

    def _record(self, fn, kwargs, admit, parts):
        key = signature(fn, kwargs, admit, parts)
        if key not in self.calls:
            self.calls[key] = Call(fn, dict(kwargs), admit, parts, self.round)

    @staticmethod
    def _splice(pos, n_gen, budget, p, lanes, lens, pos0, budgets):
        for lane, n, p0, bud in zip(lanes, lens, pos0, budgets):
            if lane >= len(p):               # padded row: dropped on the chip
                continue
            pos[lane] = p0 + n
            n_gen[lane] = 1
            budget[lane] = bud
            p[lane] = bud > 1

    def _burst(self, pos, n_gen, budget, p, n_steps, width):
        w = len(p) if width is None else width
        steps = []
        while p[:w].any() and len(steps) < n_steps:
            live = np.flatnonzero(p[:w])
            pos[live] += 1
            steps.append((len(live), int(pos[live].sum())))
            n_gen[live] += 1
            p[live] &= n_gen[live] < budget[live]
        return steps

    def _state(self, cache, n_gen, budget, p):
        return (np.asarray(cache["pos"]).copy(), np.asarray(n_gen).copy(),
                np.asarray(budget).copy(), np.asarray(p).copy())

    def _decode_chunk_serve(self, params, cache, out_buf, tok, p, n_gen,
                            budget, sstate, *, n_steps, stochastic, width):
        kwargs = {"n_steps": n_steps, "stochastic": stochastic,
                  "width": width}
        self._record("_decode_chunk_serve", kwargs, None, ())
        pos, ng, bud, pp = self._state(cache, n_gen, budget, p)
        steps = self._burst(pos, ng, bud, pp, n_steps, width)
        self.work.append(RoundWork("decode", steps, []))
        cache = dict(cache, pos=jnp.asarray(pos))
        return (cache, out_buf, tok, jnp.asarray(pp), jnp.asarray(ng),
                sstate, jnp.int32(len(steps)))

    def _fused_step(self, params, cache, out_buf, tok, p, n_gen, budget,
                    sstate, admit, parts, *, n_steps, stochastic, admit_stoch,
                    part_final, part_stoch, max_len, width):
        kwargs = {"n_steps": n_steps, "stochastic": stochastic,
                  "admit_stoch": admit_stoch, "part_final": part_final,
                  "part_stoch": part_stoch, "max_len": max_len,
                  "width": width}
        self._record("_fused_step", kwargs, admit, parts)
        pos, ng, bud, pp = self._state(cache, n_gen, budget, p)
        prefill, out_parts = [], []
        for part, final in zip(parts, part_final):
            b = part["batch"]
            prefill.append([(int(b["lens"][0]), int(b["pos0"][0]))])
            if final:
                self._splice(pos, ng, bud, pp, part["lane"], b["lens"],
                             b["pos0"], part["budget"])
            else:
                out_parts.append(_Sub(fresh=False))
        if admit is not None:
            b = admit["batch"]
            lanes = admit["lanes"]
            real = lanes < len(pp)
            prefill.append([(int(n), int(p0)) for n, p0 in
                            zip(b["lens"][real], b["pos0"][real])])
            self._splice(pos, ng, bud, pp, lanes, b["lens"], b["pos0"],
                         admit["budgets"])
        steps = self._burst(pos, ng, bud, pp, n_steps, width)
        self.work.append(RoundWork("fused", steps, prefill))
        cache = dict(cache, pos=jnp.asarray(pos))
        return (cache, out_buf, tok, jnp.asarray(pp), jnp.asarray(ng),
                jnp.asarray(bud), sstate, jnp.int32(len(steps)),
                tuple(out_parts))


def replay(cfg, server: dict, prompts: list, budgets: list, arrivals: list,
           sync_offsets: tuple, max_rounds: int) -> Replay:
    """Run the real scheduler over ``HostEngine`` on the CPU until the queue
    is empty or ``max_rounds`` rounds have run.

    The window opens at the first round at which every request is due.  A
    synced boundary (the harness's ``flush``) harvests the round in flight
    before the next is planned, which moves admissions a round earlier; the
    replay syncs where the harness does: before the window's rounds
    ``sync_offsets``."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        eng = HostEngine(cfg, server["max_new_tokens"], server["stop_token"])
        sched = make_scheduler(eng, server)
        for toks, bud, arr in zip(prompts, budgets, arrivals):
            sched.submit(toks, max_new_tokens=bud, arrival=arr)
        counters, harvest, compacted = [], set(), []
        start, syncs = None, set()
        while sched.queue and eng.round < max_rounds:
            if start is None and sched.now >= max(arrivals):
                start = eng.round
                syncs = {start + o for o in sync_offsets}
            if eng.round in syncs:
                sched._flush_stash()
            done = len(sched.results)
            before = len(eng.work)
            sched.step()
            if len(eng.work) == before:     # an idle round: nothing ran
                eng.work.append(RoundWork("idle", [], []))
            if len(sched.results) > done:
                harvest.add(len(sched.results) - done)
            counters.append({k: int(sched.stats[k]) for k in SC.COUNTERS})
            if len(counters) > 1 and (counters[-1]["compactions"]
                                      > counters[-2]["compactions"]):
                compacted.append(eng.round)
            eng.round += 1
    if start is None:
        raise ValueError("the backlog was never all due: raise the replay's "
                         "round limit or shorten the ramp")
    return Replay(list(eng.calls.values()), eng.work, counters,
                  sorted(harvest), start, sorted(syncs), compacted,
                  eng.round)


def make_scheduler(engine, server: dict,
                   obs=None) -> ContinuousBatchingScheduler:
    """The scheduler as the launcher builds it for paged, chunked, fused and
    overlapped serving, with the cell's sizes."""
    return ContinuousBatchingScheduler(
        engine, capacity=server["capacity"], max_len=server["max_len"],
        chunk=server["decode_chunk"], page_size=server["page_size"],
        pool_pages=server["pool_pages"],
        prefill_chunk=server["prefill_chunk"], fused=True, overlap=True,
        obs=obs)


# ---------------------------------------------------------------------------
# the live server
# ---------------------------------------------------------------------------

def state_of(sched):
    return (sched.cache, sched.out_buf, sched.tok, sched.p, sched.n_gen,
            sched.budget, sched.sstate)


def set_state(sched, state):
    (sched.cache, sched.out_buf, sched.tok, sched.p, sched.n_gen,
     sched.budget, sched.sstate) = state


def build_server(plan, params, obs=None):
    from repro.serve import ServeEngine

    eng = ServeEngine(plan.cfg, params,
                      max_new_tokens=plan.server["max_new_tokens"],
                      stop_token=plan.server["stop_token"], obs=obs)
    sched = make_scheduler(eng, plan.server, obs=obs)
    return eng, sched


def call_inputs(call: SC.Call, plan, eng=None, sds=None):
    """The call's per-round inputs with every write aimed where it changes
    nothing: admitted rows at the out-of-range lane (the program's scatters
    drop it) and page copies at the trash page."""
    cap = plan.server["capacity"]
    trash = plan.server["pool_pages"]

    def neutral(d):
        d = dict(d)
        if "lanes" in d:
            d["lanes"] = np.full_like(d["lanes"], cap)
        if "lane" in d:
            d["lane"] = np.full_like(d["lane"], cap)
        if "copy_dsts" in d:
            d["copy_dsts"] = np.full_like(d["copy_dsts"], trash)
        return d

    def sub(s):
        if sds is not None:
            return jax.eval_shape(
                lambda: eng.make_cache(1, plan.server["max_len"]))
        fresh = eng.make_cache(1, plan.server["max_len"])
        return fresh if s.fresh else jax.device_put(fresh, jax.devices()[0])

    admit = None if call.admit is None else neutral(call.admit)
    parts = []
    for part in call.parts:
        part = neutral(part)
        part["cache"] = sub(part["cache"])
        parts.append(part)
    parts = tuple(parts)
    if sds is not None:
        admit, parts = sds((admit, parts))
    return admit, parts


def warm_empty(plan, sched) -> int:
    """Before the ramp, while no lane is live: commit the served state to
    the chip (as every program output is), and run the lane compaction's
    gathers over the identity permutation if the window can compact."""
    import jax

    set_state(sched, jax.device_put(state_of(sched), jax.devices()[0]))
    if not any(r >= plan.window_start
               for r in plan.replay.compaction_rounds):
        return 0
    sched._compact(np.zeros((plan.server["capacity"],), bool))
    jax.block_until_ready(state_of(sched))
    return 1


def warm(plan, eng, sched, horizon: int) -> int:
    """Run once every program the window's first ``horizon`` rounds call
    that the ramp did not: with the active partition all off, so no lane
    decodes, and every admitted row aimed at a dropped lane and the trash
    page, so each call leaves the served state as it was.  Then the
    harvest's page-table scatters, one per retired-lane count."""
    import jax
    import jax.numpy as jnp

    off = jax.device_put(jnp.zeros_like(sched.p), jax.devices()[0])
    hi = plan.window_start + horizon
    n = 0
    for call in plan.replay.calls:
        if not plan.window_start <= call.first_round < hi:
            continue
        admit, parts = call_inputs(call, plan, eng=eng)
        cache, out_buf, tok, _, n_gen, budget, sstate = state_of(sched)
        if call.fn == "_fused_step":
            out = eng._fused_step(eng.params, cache, out_buf, tok, off,
                                  n_gen, budget, sstate, admit, parts,
                                  **call.kwargs)
            sched.cache, sched.budget, sched.sstate = out[0], out[5], out[6]
        else:
            out = eng._decode_chunk_serve(eng.params, cache, out_buf, tok,
                                          off, n_gen, budget, sstate,
                                          **call.kwargs)
            sched.cache, sched.sstate = out[0], out[5]
        jax.block_until_ready(out)
        n += 1
    for k in plan.replay.harvest_sizes:
        idx = jnp.asarray(np.arange(k), jnp.int32)
        jax.block_until_ready(
            sched.cache["page_table"].at[idx].set(sched.trash_page))
        n += 1
    return n


def flush(sched):
    """Sync the round in flight: afterwards every dispatched round has run
    and been harvested."""
    import jax
    sched._flush_stash()
    jax.block_until_ready(state_of(sched))


def committed_tokens(sched) -> int:
    """Output tokens committed so far (synced state only): finished requests'
    tokens plus those of every live, fully prefilled lane."""
    done = sum(r["n_generated"] for r in sched.results.values())
    live = (sched.lane_rid >= 0) & ~sched._lane_pending
    return done + int(sched._host_ngen[live].sum())


def sizing_programs(eng) -> dict:
    """The round programs' bodies jitted with the donation the chip gets
    (the engine donates its serve state only off the CPU), for compiling a
    described TPU's programs on the CPU."""
    return {"_fused_step": jax.jit(
        eng._fused_step_impl, donate_argnums=(1, 6, 7, 8, 9),
        static_argnames=("n_steps", "stochastic", "admit_stoch", "part_final",
                         "part_stoch", "max_len", "width")),
        "_decode_chunk_serve": jax.jit(
            eng._decode_chunk_impl, donate_argnums=(1, 7),
            static_argnames=("n_steps", "stochastic", "width"))}


class RoundClock:
    """Host times at which each round's program was called and returned,
    by round: with the host time of the whole ``step()``, they split a slow
    round into planning before the call, the call (dispatch), and the wait
    for the previous round's results after it."""

    def __init__(self, eng, sched):
        self.calls: dict = {}
        for name in ("_fused_step", "_decode_chunk_serve"):
            setattr(eng, name, self._timed(getattr(eng, name), sched))

    def _timed(self, fn, sched):
        def call(*args, **kwargs):
            t = time.perf_counter()
            out = fn(*args, **kwargs)
            # the scheduler counts the round before it calls the program
            self.calls[int(sched.stats["steps"]) - 1] = (
                t, time.perf_counter())
            return out
        return call
