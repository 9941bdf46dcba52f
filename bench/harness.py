"""Run one cell: set up, ramp, measure a window, check what it served.

A run, in order:

1. set-up: JAX and the chip, the cell's plan (request list, replay of the
   schedule on the host), the weights from the seed, the ramp (real rounds
   in which the first ``capacity`` requests arrive over ``ramp_steps``
   decode steps, until every request is due), and one call of every
   program the window can reach that the ramp did not run (``warm``);
2. the window: ``ContinuousBatchingScheduler.step`` over the launcher's
   engine and scheduler until ``--seconds`` have passed, from one synced
   round boundary to the next; with ``--trace 1`` a few of its rounds run
   under the profiler;
3. the check (``check.py``): the served tokens of a sample of the requests
   the window finished, against the plain float32 reference, after the
   program's state is freed.

``run`` returns the result line's object; the set-up, window, trace and
check lines go to standard error.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import pathlib
import shutil
import sys
import time


from . import model as M
from . import program as P
from . import schedule as SC
from . import spec as SP
from . import traffic as TR

STOP_TOKEN = -1          # no sampled id equals it: every request runs to budget
MAX_REPLAY_ROUNDS = 5000
TRACE_SKIP_ROUNDS = 2    # window rounds before the traced ones


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# plan: everything that is known before the chip does any work
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Plan:
    cell: SP.Cell
    seed: int
    cfg: object                      # the program's ModelConfig
    server: dict
    requests: list
    prompts: list
    arrivals: list
    replay: SC.Replay
    window_start: int                # first round of the window
    weight_shapes: object
    init_fn: object


def server_of(cell: SP.Cell, requests) -> dict:
    s = dict(cell.server)
    ps = int(s["page_size"])
    longest = max(r.prompt_len + r.budget for r in requests)
    s["max_len"] = -(-longest // ps) * ps
    s["max_new_tokens"] = max(r.budget for r in requests)
    s["stop_token"] = STOP_TOKEN
    return s


def ramp_arrivals(n: int, capacity: int, ramp_steps: int) -> list:
    """Decode-step arrival of each request: the first ``capacity`` come
    evenly over ``ramp_steps`` decode steps, so lanes fill with staggered
    ages and the first of them finish as the window opens; the rest of the
    backlog is due at ``ramp_steps``."""
    return [float(ramp_steps * min(i, capacity) // capacity)
            for i in range(n)]


def plan_cell(cell: SP.Cell, seed: int, trace: bool = False) -> Plan:
    """The run's plan.  A traced run syncs around its traced rounds, which
    moves admissions a round earlier there, so its replay syncs there too;
    an untraced run syncs only where its window opens and closes."""
    cfg = M.program_config(cell.config)
    requests = TR.request_list(cell.traffic)
    prompts = TR.prompt_tokens(requests, cfg.vocab_size, seed)
    server = server_of(cell, requests)
    arrivals = ramp_arrivals(len(requests), server["capacity"],
                             server["ramp_steps"])
    skip, traced = TRACE_SKIP_ROUNDS, int(server["trace_rounds"])
    syncs = (0, skip, skip + traced) if trace else (0,)
    replay = P.replay(cfg, server, prompts, [r.budget for r in requests],
                      arrivals, syncs, MAX_REPLAY_ROUNDS)
    start = replay.window_start
    shapes = M.weight_shapes(cell.config)
    return Plan(cell, seed, cfg, server, requests, prompts, arrivals, replay,
                start, shapes, M.weight_init_fn(shapes))


# ---------------------------------------------------------------------------
# the server and its programs
# ---------------------------------------------------------------------------

def horizon(round_seconds: list, seconds: float, plan: Plan) -> int:
    """Rounds the window could reach: four times what ``seconds`` holds at
    the pace of the ramp's last rounds that compiled nothing (they carry
    fewer lanes, so run no slower than the window's), plus the traced rounds
    and their syncs.  Without such a round, every round of the replay."""
    tail = sorted(round_seconds[-8:])
    if not tail:
        return plan.replay.rounds
    t = tail[len(tail) // 2]
    extra = TRACE_SKIP_ROUNDS + int(plan.server["trace_rounds"])
    return int(math.ceil(4 * seconds / max(t, 1e-3))) + extra + 8


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class CompileLog:
    """Programs traced, compiled or loaded from the persistent cache, and the
    seconds spent on each, through ``jax.monitoring``."""

    STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile",
              "/jax/compilation_cache/cache_retrieval_time_sec": "load"}

    def __init__(self):
        import jax
        self.traces = self.compiles = self.cache_hits = 0
        self.seconds = 0.0
        self.by_stage = dict.fromkeys(self.STAGES.values(), 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        if event in self.STAGES:
            self.by_stage[self.STAGES[event]] += secs
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        return self.traces + self.compiles + self.cache_hits


@dataclasses.dataclass
class Window:
    t0: float                        # perf_counter at the first timed round
    seconds: float
    first_round: int
    last_round: int                  # exclusive
    out_tokens: int
    prefill_tokens: int
    compiles: int
    finished: list                   # rids finished in the window
    queued_at_end: int
    counters0: dict                  # scheduler counters at the start
    counters: dict                   # scheduler counters after the last step
    step_seconds: list               # (host seconds of step(), round,
                                     # perf_counter at its start) each
    clock: object = None             # program.RoundClock of the window
    trace: object = None             # TraceWindow with --trace 1


def run_window(plan: Plan, sched, seconds: float, compile_log: CompileLog,
               trace_dir=None) -> Window:
    """Step the scheduler from one synced round boundary until ``seconds``
    have passed, then sync.  With ``trace_dir``, the ``trace_rounds`` rounds
    after the first ``TRACE_SKIP_ROUNDS`` run under the profiler, between two
    more syncs (the plan's replay syncs there too); an untraced window has
    no sync inside it."""
    import jax

    counters0 = {k: int(sched.stats[k]) for k in SC.COUNTERS}
    P.flush(sched)
    # set-up's garbage is collected now and what survives it set aside, and
    # the collector stays off until the window closes: a collection pass
    # inside the window would stall the host loop that feeds the chip
    gc.collect()
    gc.freeze()
    gc.disable()
    done0 = set(sched.results)
    tok0 = P.committed_tokens(sched)
    pre0 = int(sched.stats["prefill_tokens"])
    r0 = int(sched.stats["steps"])
    c0 = compile_log.count()
    syncs = set(plan.replay.sync_rounds) - {r0}
    tw, counters, paused, step_s = None, counters0, 0.0, []
    clock = P.RoundClock(sched.engine, sched)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 - paused < seconds:
        r = int(sched.stats["steps"])
        if r == min(syncs, default=-1):
            syncs.discard(r)
            if trace_dir is not None and tw is None:
                tw, counters, paused = traced_rounds(plan, sched, trace_dir)
                syncs = set()               # the traced rounds synced both
                continue
            P.flush(sched)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:step"):
            sched.step()
        step_s.append((time.perf_counter() - t, r, t))
        counters = {k: int(sched.stats[k]) for k in SC.COUNTERS}
    P.flush(sched)
    t1 = time.perf_counter()
    gc.enable()
    gc.unfreeze()
    return Window(
        t0=t0, seconds=t1 - t0, first_round=r0,
        last_round=int(sched.stats["steps"]),
        out_tokens=P.committed_tokens(sched) - tok0,
        prefill_tokens=int(sched.stats["prefill_tokens"]) - pre0,
        compiles=compile_log.count() - c0,
        finished=sorted(set(sched.results) - done0),
        queued_at_end=len(sched.queue), counters0=counters0,
        counters=counters, step_seconds=step_s, clock=clock, trace=tw)


@dataclasses.dataclass
class TraceWindow:
    first_round: int
    last_round: int
    trace_dir: pathlib.Path
    reduced: dict = None


def traced_rounds(plan: Plan, sched, trace_dir):
    """Run the cell's ``trace_rounds`` rounds under the profiler, between two
    synced round boundaries.  Returns the traced rounds, the scheduler's
    counters after the last traced step, and the seconds the profiler
    itself took to start and to write the trace (which the window does not
    count against ``--seconds``)."""
    import jax

    P.flush(sched)
    shutil.rmtree(trace_dir, ignore_errors=True)
    t = time.perf_counter()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # no Python call tracing: it slows
    opts.enable_hlo_proto = False       # the host and swells the trace
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    overhead = time.perf_counter() - t
    r0 = int(sched.stats["steps"])
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(int(plan.server["trace_rounds"])):
            with jax.profiler.TraceAnnotation("bench:step"):
                sched.step()
        counters = {k: int(sched.stats[k]) for k in SC.COUNTERS}
        with jax.profiler.TraceAnnotation("bench:sync"):
            P.flush(sched)
    t = time.perf_counter()
    jax.profiler.stop_trace()
    overhead += time.perf_counter() - t
    return (TraceWindow(r0, int(sched.stats["steps"]), trace_dir),
            counters, overhead)


def reduce_trace(tw: TraceWindow):
    """Reduce the traced rounds' profile (after the window has closed) and
    delete it."""
    from . import trace as TRC

    t = time.perf_counter()
    tw.reduced = TRC.reduce(TRC.load(tw.trace_dir))
    shutil.rmtree(tw.trace_dir, ignore_errors=True)
    names = {}
    for n, _, _ in tw.reduced["modules"]:
        names[n] = names.get(n, 0) + 1
    red = tw.reduced
    log(f"[trace] rounds={tw.first_round}..{tw.last_round} "
        f"reduce_s={time.perf_counter() - t:.3f} "
        f"window_s={red['window_s']:.6f} busy_s={red['busy_s']:.6f} "
        f"idle_share_halves={red['idle_share_halves']} "
        f"longest_gaps={red['longest_gaps']} modules={names}")


def log_rounds(win: Window):
    """The median host time of a window round, and the slowest rounds, each
    split into planning before its program's call, the call, and the rest
    (the wait for the previous round's results, and the harvest)."""
    steps = sorted(win.step_seconds, reverse=True)
    if not steps:
        return
    slow = []
    for dt, r, t in steps[:5]:
        if r in win.clock.calls:
            a, b = win.clock.calls[r]
            slow.append(f"{r}:{dt:.4f}(plan={a - t:.4f},call={b - a:.4f},"
                        f"after={t + dt - b:.4f})")
        else:
            slow.append(f"{r}:{dt:.4f}(no call)")
    log(f"[rounds] step_s_median={steps[len(steps) // 2][0]:.6f} "
        f"slowest=" + ",".join(slow))


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------

def check_devices(cell: SP.Cell, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"no accelerator: JAX found {devs[0].platform}")
    if len(devs) < cell.chips:
        raise NoChip(f"cell asks for {cell.chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_process: float, root: pathlib.Path = SP.ROOT,
        require_chip: bool = True, fault=None, control: bool = False) -> dict:
    """One run of ``workload``; returns the result line's object.  ``fault``
    (tests only) is called with the engine before the ramp, to break the
    timed path underneath.  ``control`` (calibration only) adds the float8
    control's reading beside the check's, under ``control_gap``."""
    import jax

    from repro.obs import Obs, Tracer
    from repro.runtime.compile_cache import enable_compile_cache

    from . import check as CK
    from . import measure as MX

    cell = SP.load_cell(workload, root)
    enable_compile_cache()
    compile_log = CompileLog()
    devs = check_devices(cell, require_chip)
    parts = {"jax_init": time.perf_counter() - t_process}

    t = time.perf_counter()
    plan = plan_cell(cell, seed, trace)
    parts["plan"] = time.perf_counter() - t
    M.check_layout(plan.cfg, plan.weight_shapes)

    t = time.perf_counter()
    params = plan.init_fn(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    parts["weights"] = time.perf_counter() - t

    obs = Obs(tracer=Tracer(), xla_annotations=True) if trace else None
    eng, sched = P.build_server(plan, params, obs=obs)
    if fault is not None:
        fault(eng)
    for toks, req, arr in zip(plan.prompts, plan.requests, plan.arrivals):
        sched.submit(toks, max_new_tokens=req.budget, arrival=arr)

    t = time.perf_counter()
    c = compile_log.seconds
    P.warm_empty(plan, sched)
    ramp_rounds = []                    # seconds of rounds that compiled nothing
    while sched.stats["steps"] < plan.window_start:
        t1, n1 = time.perf_counter(), compile_log.count()
        sched.step()
        if compile_log.count() == n1:
            ramp_rounds.append(time.perf_counter() - t1)
    parts["ramp"] = time.perf_counter() - t
    parts["ramp_compile"] = compile_log.seconds - c

    t = time.perf_counter()
    c = compile_log.seconds
    h = horizon(ramp_rounds, seconds, plan)
    n_warm = P.warm(plan, eng, sched, h)
    parts["warm"] = time.perf_counter() - t
    parts["warm_compile"] = compile_log.seconds - c
    reqs = plan.requests
    log(f"[traffic] requests={len(reqs)} "
        f"prompt_tokens={sum(r.prompt_len for r in reqs)} "
        f"output_budget={sum(r.budget for r in reqs)} "
        f"window_start_round={plan.window_start}")

    trace_dir = (root / ".bench_out" / "trace" / f"{workload}.{seed}"
                 if trace else None)
    compiles_setup = (compile_log.compiles, compile_log.cache_hits)
    win = run_window(plan, sched, seconds, compile_log, trace_dir)
    setup_s = win.t0 - t_process
    if win.trace is not None:
        reduce_trace(win.trace)
    log("[setup] " + " ".join(f"{k}={v:.3f}" for k, v in parts.items())
        + f" setup_s={setup_s:.3f} warm_calls={n_warm} horizon={h} "
        f"programs={len(plan.replay.calls)} compiles={compiles_setup[0]} "
        f"cache_hits={compiles_setup[1]} " + " ".join(
            f"{k}_s={v:.3f}" for k, v in compile_log.by_stage.items()))
    sched_checks = CK.schedule_checks(plan, sched, win)
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    log(f"[window] seconds={win.seconds:.6f} rounds={win.first_round}.."
        f"{win.last_round} out_tokens={win.out_tokens} "
        f"prefill_tokens={win.prefill_tokens} finished={len(win.finished)} "
        f"queued_at_end={win.queued_at_end} window_compiles={win.compiles} "
        f"memory_peak_bytes={peak}")
    log_rounds(win)

    served = {rid: (plan.prompts[rid], sched.results[rid]["tokens"])
              for rid in win.finished}
    occupancy = list(
        sched.stats["occupancy_trace"][win.first_round:win.last_round])
    release(eng, sched)
    del params, eng, sched
    gc.collect()

    ctx = MX.Context(plan=plan, window=win, setup_s=setup_s,
                     occupancy=occupancy, device_kind=devs[0].device_kind)
    metric_list = cell.per_layer if trace else cell.end_to_end
    metrics = MX.read_all(metric_list, ctx, cell.root)
    if trace:
        sched_checks += CK.trace_checks(metric_list, metrics, ctx)

    t = time.perf_counter()
    checks = sched_checks + CK.served_checks(plan, served, seed, control)
    ctrl = [c for c in checks if c["limit"] is None]
    checks = [c for c in checks if c["limit"] is not None]
    log(f"[check] seconds={time.perf_counter() - t:.3f}")
    correct = all(c["value"] <= c["limit"] for c in checks)
    for c in checks:
        log(f"[check] {c['name']}={c['value']!r} limit={c['limit']!r}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(win.finished),
           "failed": sum(c["value"] > c["limit"] for c in checks),
           "metrics": metrics,
           "device": device}
    if trace and win.trace is not None:
        red = win.trace.reduced
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["top_ops"],
                            "idle_gaps": red["idle_gaps"]}
    if ctrl:
        out["control_gap"] = ctrl[0]["value"]
    out["check"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                    for c in checks}
    return out


def release(eng, sched):
    """Free the program's device state: weights, pools, lane buffers."""
    import jax
    for x in jax.tree.leaves((P.state_of(sched), eng.params)):
        if hasattr(x, "delete"):
            try:
                x.delete()
            except RuntimeError:
                pass
