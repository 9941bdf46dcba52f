"""repro.obs: the zero-sync telemetry contract.

Three pins:

* tracing ON serves BYTE-IDENTICAL tokens at EQUAL dispatch/host-sync
  counts vs tracing OFF, on a run that exercises paging + prefix sharing +
  chunked prefill + compaction + the async overlap harvest all at once —
  observability reads host-side values the serve loop already holds and
  never adds a device sync;
* the streaming log2 histograms reproduce numpy.percentile within their
  bucket resolution (2**(1/SUBDIV) relative) without storing samples;
* the exported Chrome/Perfetto trace passes structural validation (B/E
  nesting per track, monotonic timestamps, all spans closed) and replays
  the round anatomy docs/ARCHITECTURE.md documents: phase spans nest
  inside round spans, plan precedes the dispatch, and every request track
  opens at submit, sees admitted/first_token, and closes at harvest.
"""

import json

import jax
import numpy as np
import pytest

from repro.models import ModelConfig, get_model
from repro.obs import (NULL_SPAN, LogHistogram, MetricsRegistry, Obs,
                       StatsView, Tracer, validate_trace)
from repro.obs.scopes import SPANS
from repro.obs.trace import PID_REQUESTS, PID_SERVE
from repro.serve import ContinuousBatchingScheduler, SamplingParams, ServeEngine

BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab_size=64, param_dtype="float32", compute_dtype="float32")

FAMILY_OVER = {
    "dense": {},
    "moe": dict(first_k_dense=1, n_experts=4, top_k=2, capacity_factor=4.0),
    "ssm": dict(ssm_state=16, ssm_headdim=16, ssm_chunk=4),
    "hybrid": dict(ssm_state=16, ssm_headdim=16, ssm_chunk=4,
                   shared_attn_period=2),
    "encdec": dict(n_enc_layers=2, n_dec_layers=2),
}
SRC_LEN = 12


def _mk_engine(family="dense"):
    cfg = ModelConfig(name=f"t-obs-{family}", family=family,
                      **{**BASE, **FAMILY_OVER[family]})
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, ServeEngine(cfg, params, max_new_tokens=6, stop_token=7)


@pytest.fixture(scope="module")
def engine():
    return _mk_engine()[1]


def _trace(rng, n, family="dense", d_model=64):
    """Ragged arrivals, ragged prompts/budgets, a shared system prefix on
    half the requests (the prefix-sharing + host-swap traffic shape)."""
    shared = np.arange(1, 9)
    out, t = [], 0.0
    for _ in range(n):
        t += rng.exponential(1.5)
        prompt = rng.randint(1, 64, rng.randint(3, 14))
        if rng.rand() < 0.5:
            prompt = np.concatenate([shared, prompt])[:16]
        extras = None
        if family == "encdec":
            sl = int(rng.randint(2, SRC_LEN - 1))
            extras = {"src_emb": rng.randn(sl, d_model).astype(np.float32)}
        out.append((t, prompt, int(rng.randint(3, 8)), extras))
    return out


def _serve(eng, trace, obs=None, combo=True, **kw):
    """``combo=True`` (the dense-family default) is the all-features-on
    configuration: paged + prefix sharing + chunked prefill + host swap +
    compaction + fused step + async overlap harvest, with mixed
    greedy/stochastic lanes."""
    if combo:
        kw = dict(page_size=4, prefill_chunk=4, host_swap_pages=8, **kw)
    sched = ContinuousBatchingScheduler(
        eng, capacity=4, max_len=24, chunk=3, compact_threshold=0.5,
        fused=True, overlap=True, obs=obs, **kw)
    for rid, (arrival, prompt, max_new, extras) in enumerate(trace):
        sp = (SamplingParams(temperature=0.8, top_p=0.9, seed=rid,
                             greedy=False) if rid % 3 == 0 else None)
        sched.submit(prompt, arrival=arrival, max_new_tokens=max_new,
                     sampling=sp, extras=extras)
    results = sched.run()
    return results, dict(sched.stats)


# ----------------------------------------------------------------------
# the hard contract: tracing observes, never perturbs
# ----------------------------------------------------------------------

def test_tracing_on_off_byte_identity(engine):
    trace = _trace(np.random.RandomState(0), 10)
    r_off, s_off = _serve(engine, trace)
    obs = Obs(tracer=Tracer())
    r_on, s_on = _serve(engine, trace, obs=obs)
    assert r_off.keys() == r_on.keys()
    for rid in r_off:
        assert np.array_equal(r_off[rid]["tokens"], r_on[rid]["tokens"]), (
            f"rid {rid}: tracing changed served tokens")
        assert r_off[rid]["n_generated"] == r_on[rid]["n_generated"]
    assert s_on["dispatches"] == s_off["dispatches"]
    assert s_on["host_syncs"] == s_off["host_syncs"]
    # full stats equality, not just the headline counters
    assert s_on == s_off
    assert len(obs.tracer.events) > 0


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid",
                                    "encdec"])
def test_tracing_identity_all_families(family):
    """Acceptance criterion: EVERY family serves byte-identical tokens at
    equal dispatch and host-sync counts with tracing on (fused + overlap
    loop; the paged combo is pinned separately above)."""
    cfg, eng = _mk_engine(family)
    trace = _trace(np.random.RandomState(3), 6, family=family,
                   d_model=cfg.d_model)
    kw = {"src_len": SRC_LEN} if family == "encdec" else {}
    r_off, s_off = _serve(eng, trace, combo=False, **kw)
    r_on, s_on = _serve(eng, trace, combo=False, obs=Obs(tracer=Tracer()),
                        **kw)
    for rid in r_off:
        ta, tb = r_off[rid]["tokens"], r_on[rid]["tokens"]
        assert ta.dtype == tb.dtype and ta.tobytes() == tb.tobytes(), (
            family, rid)
    assert s_on == s_off, family


def test_off_recorder_is_noop(engine):
    """Without a tracer or annotations every hook is a no-op (shared
    NULL_SPAN, immediate returns) — nothing accumulates anywhere but the
    metrics registry."""
    obs = Obs()
    assert not obs.tracing
    for name in SPANS:
        assert obs.span(name, what="x") is NULL_SPAN   # the shared singleton
    obs.event("x")
    obs.counter("occupancy", 0.5)
    obs.request_begin(0)
    obs.request_event(0, "y")
    obs.request_end(0)
    assert obs.export("/nonexistent/never-written.json") == 0


def test_annotations_without_tracer_record_nothing():
    """``xla_annotations`` alone: every span is a profiler annotation of its
    own name, and nothing else is recorded."""
    obs = Obs(xla_annotations=True)
    assert not obs.tracing
    span = obs.span("round", round=3)
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span:
        with obs.span("sync", what="harvest"):
            pass
    obs.event("x")
    obs.request_begin(0)
    obs.request_end(0)
    assert obs.tracer is None
    assert obs.export("/nonexistent/never-written.json") == 0
    # with a tracer too, the span records and annotates
    both = Obs(tracer=Tracer(), xla_annotations=True)
    with both.span("round", round=0):
        pass
    assert [e["ph"] for e in both.tracer.events] == ["B", "E"]


def test_annotated_serve_spans_reach_the_profiler(engine, tmp_path):
    """A profile of a run with annotations on holds the serve loop's spans
    by name, and the run serves byte-identical tokens at equal dispatch and
    host-sync counts."""
    from jax.profiler import ProfileData

    trace = _trace(np.random.RandomState(2), 6)
    r_off, s_off = _serve(engine, trace)
    jax.profiler.start_trace(str(tmp_path))
    try:
        r_on, s_on = _serve(engine, trace, obs=Obs(xla_annotations=True))
    finally:
        jax.profiler.stop_trace()
    for rid in r_off:
        assert r_off[rid]["tokens"].tobytes() == r_on[rid]["tokens"].tobytes()
    assert s_on == s_off
    (pb,) = tmp_path.glob("**/*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(pb)).planes
             for line in plane.lines for e in line.events}
    assert {"round", "plan", "dispatch", "harvest", "sync"} <= names
    # every span name a traced serve run records is one of SPANS
    obs = Obs(tracer=Tracer())
    _serve(engine, trace, obs=obs)
    assert {e["name"] for e in obs.tracer.events
            if e["ph"] == "B" and e["pid"] == PID_SERVE} <= set(SPANS)


# ----------------------------------------------------------------------
# histogram percentiles vs numpy
# ----------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential"])
def test_histogram_percentiles_within_bucket_tolerance(dist):
    rng = np.random.RandomState(7)
    if dist == "lognormal":
        vals = rng.lognormal(2.0, 1.0, 4000)
    elif dist == "uniform":
        vals = rng.uniform(0.5, 50.0, 4000)
    else:
        vals = rng.exponential(10.0, 4000) + 0.01
    h = LogHistogram("lat", unit="ms", percentiles=(50, 90, 99))
    for v in vals:
        h.record(float(v))
    # one bucket spans a 2**(1/SUBDIV) relative range; nearest-rank vs
    # linear interpolation adds at most one more bucket of slack
    tol = 2.0 ** (2.0 / LogHistogram.SUBDIV) - 1.0
    for q in (50, 90, 99):
        ref = float(np.percentile(vals, q))
        est = h.percentile(q)
        assert abs(est - ref) / ref <= tol, (dist, q, est, ref)
    assert h.count == len(vals)
    assert h.mean == pytest.approx(float(vals.mean()))


def test_histogram_edge_cases():
    h = LogHistogram("lat")
    assert h.percentile(50) == 0.0               # empty
    h.record(0.0)
    h.record(-1.0)                               # zero bucket
    assert h.percentile(50) == 0.0
    h2 = LogHistogram("one")
    h2.record(3.0)
    assert h2.percentile(50) == pytest.approx(3.0, rel=0.1)
    assert h2.snapshot().keys() == {"one_p50_ms", "one_p99_ms"}


def test_stats_view_is_a_dict_facade():
    reg = MetricsRegistry()
    reg.counter("steps", key="rounds")
    reg.series("occupancy_trace", key="mean_occupancy")
    view = reg.stats_view()
    assert isinstance(view, StatsView)
    view["steps"] += 2
    view["steps"] += 1
    view["occupancy_trace"].append(0.5)
    view["occupancy_trace"].append(1.0)
    view["new_counter"] = 7                      # auto-registers
    assert view["steps"] == 3
    assert dict(view) == {"steps": 3, "occupancy_trace": [0.5, 1.0],
                          "new_counter": 7}
    # snapshot speaks the bench's key language, not the stat names
    assert reg.snapshot() == {"rounds": 3, "mean_occupancy": 0.75,
                              "new_counter": 7}


# ----------------------------------------------------------------------
# trace schema + round-anatomy replay
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run(engine):
    obs = Obs(tracer=Tracer())
    results, stats = _serve(engine, _trace(np.random.RandomState(1), 8),
                            obs=obs)
    obs.tracer.close()
    return obs.tracer.trace_events(), results, stats


def test_trace_validates(traced_run):
    events, _, _ = traced_run
    assert validate_trace(events) == []
    # round-trips through JSON (what export() writes)
    assert validate_trace(json.loads(json.dumps(events))) == []


def test_trace_replays_round_anatomy(traced_run):
    """The serve-loop track replays docs/ARCHITECTURE.md §1: every phase
    span nests inside a round span, and within a round the plan phase
    precedes the fused dispatch which precedes the (delayed) harvest."""
    events, _, stats = traced_run
    serve = [e for e in events
             if e.get("pid") == PID_SERVE and e.get("ph") in ("B", "E")
             and e.get("tid") == 0]
    depth = 0
    round_depth = None
    rounds = 0
    phases_seen: set = set()
    order: list = []
    orders: list = []
    for ev in serve:
        if ev["ph"] == "B":
            depth += 1
            if ev["name"] == "round":
                assert round_depth is None, "rounds must not nest"
                round_depth = depth
                order = []
                rounds += 1
            elif round_depth is not None:
                assert depth > round_depth, (
                    f"phase {ev['name']} outside a round span")
                if depth == round_depth + 1:
                    order.append(ev["name"])
                    phases_seen.add(ev["name"])
        else:
            if round_depth is not None and depth == round_depth:
                round_depth = None
                orders.append(order)
            depth -= 1
    assert rounds == stats["steps"]
    # the fused path's core phases all occurred somewhere in the run
    assert {"plan", "dispatch", "harvest"} <= phases_seen
    for order in orders:
        if "plan" in order and "dispatch" in order:
            assert order.index("plan") < order.index("dispatch")
        if "dispatch" in order and "harvest" in order:
            assert order.index("dispatch") < order.index("harvest")
    # every sync span carries its reason and nests under the serve track
    syncs = [e for e in events if e.get("name") == "sync"
             and e.get("ph") == "B"]
    assert len(syncs) == stats["host_syncs"]
    assert all(e["args"]["what"] for e in syncs)


def test_trace_request_lifecycles(traced_run):
    """pid 2 carries one track per request: opened at submit, annotated
    with admitted/first_token, closed exactly once at harvest."""
    events, results, _ = traced_run
    tracks: dict = {}
    for ev in events:
        if ev.get("pid") != PID_REQUESTS or ev.get("ph") == "M":
            continue
        tracks.setdefault(ev["tid"], []).append(ev)
    assert set(tracks) == set(results)
    for rid, evs in tracks.items():
        phs = [e["ph"] for e in evs]
        assert phs[0] == "B" and phs[-1] == "E" and phs.count("B") == 1, rid
        assert evs[0]["args"]["prompt_len"] > 0
        names = [e.get("name") for e in evs if e["ph"] == "i"]
        assert "admitted" in names and "first_token" in names, (rid, names)
        assert evs[-1]["args"]["n_generated"] == results[rid]["n_generated"]


def test_validate_trace_catches_malformed():
    ok = [{"ph": "B", "ts": 1.0, "pid": 1, "tid": 0, "name": "a"},
          {"ph": "E", "ts": 2.0, "pid": 1, "tid": 0, "name": "a"}]
    assert validate_trace(ok) == []
    unclosed = ok[:1]
    assert any("never closed" in e for e in validate_trace(unclosed))
    dangling = ok[1:]
    assert any("no open B" in e for e in validate_trace(dangling))
    crossed = [dict(ok[0]), {"ph": "E", "ts": 2.0, "pid": 1, "tid": 0,
                             "name": "b"}]
    assert any("closes B" in e for e in validate_trace(crossed))
    backwards = [dict(ok[0], ts=5.0), dict(ok[1], ts=2.0)]
    assert any("not monotonic" in e for e in validate_trace(backwards))
    bad_ph = [{"ph": "Z", "ts": 1.0, "pid": 1, "tid": 0}]
    assert any("unknown phase" in e for e in validate_trace(bad_ph))


def test_tracer_close_heals_open_spans():
    tr = Tracer()
    tr._emit("B", "round", 0, None)
    tr.request_begin(3, prompt_len=4)
    tr.close()
    assert validate_trace(tr.trace_events()) == []
