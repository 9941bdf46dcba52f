"""Serving throughput under Poisson traffic: tokens/sec and lane occupancy
for the continuous-batching scheduler vs the static-batch engine, at several
lane capacities — plus a PAGED leg (native paged decode: flash attention
reads K/V through the page table, no dense-view gather on the hot path)
whose pool is sized from ``--paged-mem-frac`` of the dense KV footprint.
At the default fraction 1.0 the paged leg runs at MATCHED memory and the
recorded ``dense_paged_ratio`` (paged / continuous tokens-per-sec) is the
regression guard the CI smoke job gates with ``--min-paged-ratio`` — a
full-view copy reintroduced on the decode path shows up as the ratio
collapsing.  A second record at half memory (``paged_half``) shows the
page-gated admission behavior under real memory pressure.  Emits
``BENCH_serving.json`` so the perf trajectory of the serve path is recorded
per PR.

Scheduler legs run the FUSED step program with the async overlap harvest
(one dispatch + one blocking sync per round); each continuous record carries
``continuous_static_ratio`` (continuous / static tokens-per-sec) plus
per-request TTFT/TPOT p50/p99, and ``--min-continuous-ratio`` gates the
largest capacity's ratio in CI — per-round host dispatch overhead creeping
back into the serve loop shows up as that ratio collapsing.

``--chaos`` adds an OVERLOAD leg: priority bursts with deadlines and a
bounded queue against a pool sized for half the lanes, under a deterministic
``ChaosMonkey`` alloc-failure schedule.  It is a behavior gate, not a speed
number: zero page leaks after drain, ``preemptions > 0`` (the starved
high-priority arrivals actually preempted), and every request finishing as
``done``/``preempted_resumed`` must serve tokens byte-identical to a calm
twin on ample resources — preemption spill/resume is bit-exact.

``--tp-mesh DxM`` adds a tensor-parallel leg: the same trace served through
a mesh-backed engine (lanes sharded over "data", KV-head pools and MLP over
"model").  On the forced host-device CPU mesh this is a STRUCTURE check,
not a speed number: the leg hard-fails unless its dispatch count and token
count match the 1-device continuous leg exactly (mesh sharding must not
reintroduce per-token host syncs into the serve loop).

    PYTHONPATH=src python -m benchmarks.bench_serving [--fast] \
        [--seed 0] [--trace-len 8] [--min-paged-ratio 0.5] \
        [--min-continuous-ratio 0.2]

The arrival trace is Poisson in DECODE-STEP time (the scheduler's clock):
request inter-arrival gaps are exponential with the given rate, so bursts and
lulls both occur — exactly the ragged traffic that makes lane recycling (and
compaction below the occupancy threshold) pay off.  A fraction of requests
share a common "system prompt" prefix, the traffic shape that prefix sharing
converts into skipped prefill work.  ``--seed``/``--trace-len`` pin the trace
for the CI smoke job (deterministic, < 2 min).
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.paging import pages_needed
from repro.dist import collectives as C
from repro.launch.mesh import force_host_devices, make_mesh, parse_mesh
from repro.models import ModelConfig, get_model
from repro.obs import Obs, Tracer
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import (
    ChaosConfig,
    ChaosMonkey,
    ContinuousBatchingScheduler,
    FinishReason,
    SamplingParams,
    ServeEngine,
    burst_trace,
)

CFG = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
           vocab_size=256, param_dtype="float32", compute_dtype="float32")


def poisson_trace(rng, n_requests, rate, prompt_lo, prompt_hi,
                  share_frac=0.0, shared_prefix_len=8):
    """(arrival_step, prompt) pairs with exponential inter-arrival gaps.

    ``share_frac`` of the requests open with one common prefix (a "system
    prompt"), the traffic shape prefix sharing converts into refcount bumps.
    """
    t = 0.0
    out = []
    prefix = rng.randint(1, CFG["vocab_size"], shared_prefix_len)
    for _ in range(n_requests):
        t += rng.exponential(1.0 / rate)
        prompt = rng.randint(1, CFG["vocab_size"],
                             rng.randint(prompt_lo, prompt_hi))
        if rng.rand() < share_frac:
            prompt = np.concatenate([prefix, prompt])[:prompt_hi]
        # ragged per-request budgets: co-admitted requests then retire at
        # DIFFERENT rounds, so a donor's prefix pages are still resident when
        # sharers arrive (uniform budgets retire whole admission waves at
        # once and the prefix index would always be empty at lookup time)
        out.append((t, prompt, int(rng.randint(3, 9))))
    return out


def session_trace(rng, n_users, turns, page_size, turn_gap=60.0):
    """Multi-turn conversations: each user's turn t+1 prompt EXTENDS its
    turn t prompt, and turn waves are gapped far enough apart in decode-step
    time that a turn's lanes retire — and their prefix pages leave residency
    — before the follow-up arrives.  This is the traffic shape the host-swap
    eviction tier converts into cross-request session hits; without it every
    follow-up pays full prefill."""
    prompts = {u: rng.randint(1, CFG["vocab_size"],
                              int(rng.randint(page_size, page_size + 5)))
               for u in range(n_users)}
    out = []
    t = 0.0
    for turn in range(turns):
        for u in range(n_users):
            out.append((t + float(rng.rand()), prompts[u].copy(),
                        int(rng.randint(3, 9))))
            if turn + 1 < turns:
                ext = rng.randint(1, CFG["vocab_size"],
                                  int(rng.randint(4, 9)))
                prompts[u] = np.concatenate([prompts[u], ext])
        t += turn_gap
    return out


def bench_capacity(eng, trace, *, capacity, max_len, chunk,
                   compact_threshold, page_size=None, pool_pages=None,
                   sampling=None, prefill_chunk=None, fused=True,
                   overlap=True, host_swap_pages=None, collect=None,
                   obs=None, trace_dir=None, leg="serve"):
    """One scheduler run; ``sampling`` is a per-request SamplingParams
    factory rid -> params (None = greedy).  Steps the scheduler manually so
    per-DECODE-STEP latency percentiles can be reported alongside
    throughput (p99 is the number continuous batching is supposed to hold
    down while admission/compaction churn the lane vector).  Default is the
    fused step program with the async overlap harvest — one dispatch and one
    blocking sync per round.

    The per-leg summary IS the obs registry snapshot: counters/series live
    in the scheduler's registry, latency percentiles come from streaming
    log2 histograms (no stored sample lists), and ``snapshot()`` emits the
    exact key shape BENCH_serving.json promises — every scheduler leg now
    carries every counter (swap/session/prefix keys are 0 where the feature
    is off).  Pass ``obs`` (e.g. with a tracer) to share/record the run;
    with ``trace_dir`` set a fresh tracer is attached and the leg's
    Chrome/Perfetto timeline is exported to ``<trace_dir>/<leg>.json``.
    """
    if obs is None:
        obs = Obs(tracer=Tracer()) if trace_dir else Obs()
    reg = obs.metrics
    # wall-clock latency histograms: decode_step (per-round latency amortized
    # over its decode steps), TTFT (submit -> first token committed to a
    # dispatch), TPOT (first token -> harvest, per subsequent token)
    for name in ("decode_step", "ttft", "tpot"):
        reg.histogram(name, unit="ms", percentiles=(50, 99))
    sched = ContinuousBatchingScheduler(
        eng, capacity=capacity, max_len=max_len, chunk=chunk,
        compact_threshold=compact_threshold, page_size=page_size,
        pool_pages=pool_pages, prefill_chunk=prefill_chunk,
        fused=fused, overlap=overlap, host_swap_pages=host_swap_pages,
        obs=obs)
    for rid, (arrival, prompt, max_new) in enumerate(trace):
        sched.submit(prompt, arrival=arrival, max_new_tokens=max_new,
                     sampling=sampling(rid) if sampling else None)
    t0 = time.perf_counter()
    while sched.queue or (sched.lane_rid >= 0).any():
        ds0 = sched.stats["decode_steps"]
        s0 = time.perf_counter()
        sched.step()
        dt = time.perf_counter() - s0
        ran = sched.stats["decode_steps"] - ds0
        if ran:                      # amortize the round over its decode steps
            for _ in range(ran):
                reg.observe("decode_step", dt / ran * 1e3)
    sched.run()                      # overlap: harvest the final stash
    wall = time.perf_counter() - t0
    results = sched.results
    toks = sum(r["n_generated"] for r in results.values())
    for r in results:
        rt = sched.req_times[r]
        reg.observe("ttft", (rt["first_token"] - rt["submitted"]) * 1e3)
        reg.observe("tpot", (rt["finished"] - rt["first_token"]) * 1e3
                    / max(results[r]["n_generated"] - 1, 1))
    rec = {
        "capacity": capacity,
        "requests": len(results),
        "tokens": int(toks),
        "wall_s": wall,
        "tokens_per_s": toks / wall,
        "lane_efficiency": (sched.stats["active_lane_steps"]
                            / max(sched.stats["lane_steps"], 1)),
    }
    rec.update(reg.snapshot())
    rec["prefix_hit_rate"] = rec["prefix_hits"] / max(len(results), 1)
    if page_size is not None:
        # memory-honest throughput accounting: the KV bytes actually held on
        # device (pools + quantization scale pools) and the mean concurrent
        # lanes each byte buys — narrow pools serve the same occupancy from
        # fewer bytes, which is the whole point of quantized pages
        kv_bytes = sum(int(v.nbytes) for k, v in sched.cache.items()
                       if k.endswith("_pages") or k.endswith("_pages_scale"))
        rec.update({
            "page_size": page_size,
            "pool_pages": sched.pool_pages,
            "page_dtype": eng.page_dtype or "float32",
            "kv_cache_bytes": kv_bytes,
            "lanes_per_byte": rec["mean_occupancy"] * capacity / kv_bytes,
        })
    if host_swap_pages:
        rec.update({
            "host_swap_pages": host_swap_pages,
            "cross_request_hit_rate": (sched.stats["session_hits"]
                                       / max(len(results), 1)),
        })
    if prefill_chunk is not None:
        rec["prefill_chunk"] = prefill_chunk
    if collect is not None:
        for rid, r in results.items():
            collect[rid] = r["tokens"].tolist()
    if trace_dir and obs.tracing:
        os.makedirs(trace_dir, exist_ok=True)
        rec["trace_events"] = obs.export(
            os.path.join(trace_dir, f"{leg}.json"))
    return rec


def bench_static(eng, trace, *, capacity, max_len):
    """Static batching baseline: serve the same requests in fixed batches of
    ``capacity`` (each batch waits for its slowest lane)."""
    prompts = [p for _, p, _ in trace]
    t0 = time.perf_counter()
    toks = 0
    for i in range(0, len(prompts), capacity):
        chunk = prompts[i:i + capacity]
        plen = max(len(p) for p in chunk)
        toks_arr = np.zeros((len(chunk), plen), np.int32)
        lens = np.zeros((len(chunk),), np.int32)
        for j, p in enumerate(chunk):
            toks_arr[j, :len(p)] = p
            lens[j] = len(p)
        res = eng.generate({"tokens": jnp.asarray(toks_arr),
                            "lens": jnp.asarray(lens)}, max_len=max_len)
        toks += int(res["n_generated"].sum())
    wall = time.perf_counter() - t0
    return {"capacity": capacity, "tokens": toks, "wall_s": wall,
            "tokens_per_s": toks / wall}


def bench_overload(eng, reqs, *, capacity, max_len, page_size, pool_pages,
                   max_queue=None, chaos=None, obs=None, trace_dir=None,
                   leg="chaos"):
    """Overload leg: a priority burst trace on a deliberately starved pool,
    optionally under a deterministic :class:`ChaosMonkey`.  Returns the
    per-leg record plus ``{rid: tokens}`` for the calm-twin identity gate.

    Unlike the throughput legs this one measures BEHAVIOR, not speed: the
    record carries the robustness counters (preemptions / shed / cancelled /
    deadline_misses / resume_page_ins), a finish-reason census, injected
    fault counts and ``page_leaks`` (allocator pages still live after
    drain — the number the CI gate pins at zero)."""
    if obs is None:
        obs = Obs(tracer=Tracer()) if trace_dir else Obs()
    sched = ContinuousBatchingScheduler(
        eng, capacity=capacity, max_len=max_len, chunk=1,
        compact_threshold=0.5, page_size=page_size, pool_pages=pool_pages,
        fused=True, overlap=True, max_queue=max_queue, obs=obs)
    monkey = ChaosMonkey(chaos).install(sched) if chaos else None
    for r in reqs:
        sched.submit(r["tokens"], arrival=r["arrival"],
                     priority=r["priority"], deadline=r.get("deadline"))
    t0 = time.perf_counter()
    results = monkey.run(sched) if monkey else sched.run()
    wall = time.perf_counter() - t0
    reasons = collections.Counter(
        r["finish_reason"].value for r in results.values())
    toks = sum(r["n_generated"] for r in results.values())
    rec = {
        "capacity": capacity,
        "pool_pages": pool_pages,
        "max_queue": max_queue,
        "requests": len(results),
        "tokens": int(toks),
        "wall_s": wall,
        "tokens_per_s": toks / wall,
        "page_leaks": int(sched.allocator.live_pages),
        "finish_reasons": {k.value: int(reasons.get(k.value, 0))
                           for k in FinishReason},
    }
    if monkey:
        rec.update({
            "chaos_seed": chaos.seed,
            "chaos_alloc_failures": monkey.alloc_failures,
            "chaos_cancels": monkey.cancels,
            "chaos_corruptions": monkey.corruptions,
        })
    rec.update(obs.metrics.snapshot())
    if trace_dir and obs.tracing:
        os.makedirs(trace_dir, exist_ok=True)
        rec["trace_events"] = obs.export(os.path.join(trace_dir,
                                                      f"{leg}.json"))
    tokens = {rid: (r["tokens"], r["finish_reason"])
              for rid, r in results.items()}
    return rec, tokens


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--requests", "--trace-len", dest="trace_len", type=int,
                    default=None,
                    help="number of requests in the trace (deterministic "
                         "given --seed)")
    ap.add_argument("--seed", type=int, default=0,
                    help="trace RNG seed (fixed trace for the CI smoke job)")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per decode step")
    ap.add_argument("--share-frac", type=float, default=0.4,
                    help="fraction of requests opening with the common "
                         "system-prompt prefix")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page size for the paged leg")
    ap.add_argument("--paged-mem-frac", type=float, default=1.0,
                    help="paged pool size as a fraction of the dense KV "
                         "footprint (capacity * pages-per-lane); 1.0 = "
                         "matched memory, the dense_paged_ratio baseline")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="run the scheduler legs with chunked admission "
                         "prefill at this chunk size")
    ap.add_argument("--page-dtype", choices=["int8", "fp8"], default="int8",
                    help="narrow element type for the QUANTIZED paged leg "
                         "(pools hold narrow bytes + per-slot f32 scales, "
                         "dequantized inside the paged-attention gather)")
    ap.add_argument("--min-quant-lanes-ratio", type=float, default=None,
                    help="exit non-zero unless the quantized leg's lanes-"
                         "per-byte reaches this multiple of the matched-"
                         "memory f32 paged leg's — the CI guard that "
                         "quantized pages actually buy concurrency per "
                         "KV byte")
    ap.add_argument("--session-users", type=int, default=4,
                    help="users in the multi-turn session trace (the host-"
                         "swap leg); 0 disables the leg")
    ap.add_argument("--session-turns", type=int, default=3,
                    help="turns per user in the session trace")
    ap.add_argument("--host-swap-pages", type=int, default=64,
                    help="host LRU swap store capacity (pages) for the "
                         "session leg")
    ap.add_argument("--min-paged-ratio", type=float, default=None,
                    help="exit non-zero unless every matched-memory paged "
                         "leg reaches this fraction of the continuous "
                         "(dense-cache) throughput — the CI regression "
                         "guard against a full-view copy on the hot path")
    ap.add_argument("--min-continuous-ratio", type=float, default=None,
                    help="exit non-zero unless the LARGEST capacity's "
                         "continuous/static throughput ratio reaches this "
                         "floor — the CI regression guard against per-round "
                         "host dispatch overhead creeping back into the "
                         "serve loop (fused step + async harvest)")
    ap.add_argument("--tp-mesh", default=None, metavar="DxM",
                    help="add a tensor-parallel leg: serve the same trace "
                         "through a ServeEngine on a (data, model) mesh of "
                         "this shape (forces DxM host CPU devices when the "
                         "process has fewer).  The leg is gated HARD on "
                         "matching the 1-device continuous leg's dispatch "
                         "count — sharding must not add host syncs")
    ap.add_argument("--psum", choices=C.PSUM_MODES, default="fast",
                    help="psum flavor for shard_map-level collectives")
    ap.add_argument("--chaos", action="store_true",
                    help="overload + fault-injection leg: a priority burst "
                         "on a pool sized for HALF the lanes (preemption "
                         "must fire) with deadlines, a bounded queue and a "
                         "deterministic alloc-failure schedule; gates zero "
                         "page leaks, preemptions > 0 and byte-identity of "
                         "every finished request against a calm twin on "
                         "ample resources")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="ChaosConfig seed: the injected fault schedule is "
                         "a pure function of this (replayable)")
    ap.add_argument("--sampling", action="store_true",
                    help="add a stochastic leg (temperature=0.8, top_p=0.9, "
                         "per-request seed = rid): exercises the per-lane "
                         "predicated sampler deterministically")
    ap.add_argument("--trace-dir", default=None,
                    help="export a Chrome/Perfetto trace_event JSON per "
                         "showcase leg (paged/quantized/session/tp + the "
                         "traced continuous leg) into this directory; "
                         "continuous legs stay untraced so the trace-"
                         "overhead gate compares cleanly")
    ap.add_argument("--max-trace-overhead", type=float, default=None,
                    help="run an extra TRACED continuous leg at the largest "
                         "capacity and exit non-zero unless (a) its tokens/"
                         "dispatches/host_syncs equal the untraced leg's "
                         "exactly (tracing must observe, not perturb) and "
                         "(b) its tokens_per_s loss stays within this "
                         "fraction (0.10 = at most 10%% slower)")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    n_requests = args.trace_len or (8 if args.fast else 24)
    capacities = [2, 4] if args.fast else [2, 4, 8]
    max_new, max_len = 8, 24

    C.set_psum_mode(args.psum)
    mesh = None
    if args.tp_mesh is not None:
        d, m = parse_mesh(args.tp_mesh)
        # must run before the first device op below initializes the backend
        force_host_devices(d * m)
        mesh = make_mesh((d, m), ("data", "model"))

    cfg = ModelConfig(name="bench-serve", family="dense", **CFG)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_new_tokens=max_new, stop_token=7)
    # the quantized engine shares params; only its page pools differ (narrow
    # elements + scale pools, dequantized inside the paged gather)
    eng_q = ServeEngine(cfg, params, max_new_tokens=max_new, stop_token=7,
                        page_dtype=args.page_dtype)

    rng = np.random.RandomState(args.seed)
    trace = poisson_trace(rng, n_requests, args.rate, 4, 13,
                          share_frac=args.share_frac,
                          shared_prefix_len=args.page_size)

    record = {"bench": "serving", "requests": n_requests, "rate": args.rate,
              "seed": args.seed, "share_frac": args.share_frac,
              "max_new_tokens": max_new, "cfg": CFG,
              "paged_attn": eng.paged_attn,
              "page_size": args.page_size,
              "page_dtype": args.page_dtype,
              "paged_mem_frac": args.paged_mem_frac,
              "psum_mode": args.psum,
              "continuous": [], "static": [], "paged": [], "paged_half": [],
              "quantized": [], "session": [], "sampled": [], "tp": [],
              "traced": [], "chaos": []}

    def _sampled_params(rid: int):
        # fixed per-request seed (the rid) => the stochastic leg is exactly
        # reproducible run-to-run and across capacities
        return SamplingParams(temperature=0.8, top_p=0.9, seed=rid,
                              greedy=False)
    for cap in capacities:
        # untimed warmup over the FULL trace: the admission prefill shapes
        # are bucketed but still trace-dependent, so replaying the identical
        # trace guarantees the timed run hits only compiled programs
        bench_capacity(eng, trace, capacity=cap, max_len=max_len, chunk=4,
                       compact_threshold=0.5, prefill_chunk=args.prefill_chunk)
        r = bench_capacity(eng, trace, capacity=cap, max_len=max_len,
                           chunk=4, compact_threshold=0.5,
                           prefill_chunk=args.prefill_chunk)
        record["continuous"].append(r)
        bench_static(eng, trace, capacity=cap, max_len=max_len)  # warmup
        s = bench_static(eng, trace, capacity=cap, max_len=max_len)
        record["static"].append(s)
        r["continuous_static_ratio"] = r["tokens_per_s"] / s["tokens_per_s"]
        # paged legs: the pool is an HONEST fraction of the dense KV
        # footprint (dense pages = capacity * pages-per-lane; the +1 trash
        # page is reported, not hidden).  The floor is one lane's worst case
        # — below that a max-size request can never admit.  The matched-
        # memory leg (--paged-mem-frac, default 1.0) carries the
        # dense_paged_ratio regression number; the half-memory leg shows
        # page-gated admission under real pressure.
        per_lane = pages_needed(max_len, args.page_size)
        dense_pages = cap * per_lane
        legs = [("paged", args.paged_mem_frac)]
        # skip the half leg when it would duplicate the main one byte-for-byte
        if (max(int(round(dense_pages * 0.5)), per_lane)
                != max(int(round(dense_pages * args.paged_mem_frac)), per_lane)):
            legs.append(("paged_half", 0.5))
        for leg_name, frac in legs:
            pool = max(int(round(dense_pages * frac)), per_lane)
            bench_capacity(eng, trace, capacity=cap, max_len=max_len, chunk=4,
                           compact_threshold=0.5, page_size=args.page_size,
                           pool_pages=pool, prefill_chunk=args.prefill_chunk)
            p = bench_capacity(eng, trace, capacity=cap, max_len=max_len,
                               chunk=4, compact_threshold=0.5,
                               page_size=args.page_size, pool_pages=pool,
                               prefill_chunk=args.prefill_chunk,
                               trace_dir=args.trace_dir,
                               leg=f"{leg_name}_cap{cap}")
            p["mem_frac"] = frac
            p["dense_pages"] = dense_pages
            p["dense_paged_ratio"] = p["tokens_per_s"] / r["tokens_per_s"]
            record[leg_name].append(p)
        p = record["paged"][-1]
        half = ""
        if len(legs) > 1:
            ph = record["paged_half"][-1]
            half = (f"   paged@half {ph['tokens_per_s']:8.1f} tok/s "
                    f"(ratio {ph['dense_paged_ratio']:.2f}, "
                    f"waits {ph['page_waits']})")
        print(f"capacity={cap:2d}  continuous {r['tokens_per_s']:8.1f} tok/s "
              f"(occ {r['mean_occupancy']:.2f}, "
              f"compactions {r['compactions']}, "
              f"p50/p99 {r['decode_step_p50_ms']:.1f}/"
              f"{r['decode_step_p99_ms']:.1f} ms, "
              f"ttft p50 {r['ttft_p50_ms']:.0f} ms, "
              f"syncs {r['host_syncs']}/{r['rounds']}r, "
              f"c/s {r['continuous_static_ratio']:.2f})   "
              f"static {s['tokens_per_s']:8.1f} tok/s   "
              f"paged@{p['pool_pages']}/{dense_pages}pg "
              f"{p['tokens_per_s']:8.1f} tok/s "
              f"(ratio {p['dense_paged_ratio']:.2f}, "
              f"p50 {p['decode_step_p50_ms']:.1f} ms, "
              f"prefix hits {p['prefix_hits']}/{p['requests']})" + half)
        # quantized leg: the SAME page count as the matched-memory paged leg
        # but narrow pool bytes — occupancy holds while the KV footprint
        # shrinks ~4x, so lanes_per_byte (concurrent lanes per KV byte) is
        # the headline; quant_lanes_ratio is what CI gates
        pool = max(int(round(dense_pages * args.paged_mem_frac)), per_lane)
        bench_capacity(eng_q, trace, capacity=cap, max_len=max_len, chunk=4,
                       compact_threshold=0.5, page_size=args.page_size,
                       pool_pages=pool, prefill_chunk=args.prefill_chunk)
        q = bench_capacity(eng_q, trace, capacity=cap, max_len=max_len,
                           chunk=4, compact_threshold=0.5,
                           page_size=args.page_size, pool_pages=pool,
                           prefill_chunk=args.prefill_chunk,
                           trace_dir=args.trace_dir,
                           leg=f"quantized_cap{cap}")
        q["mem_frac"] = args.paged_mem_frac
        q["dense_paged_ratio"] = q["tokens_per_s"] / r["tokens_per_s"]
        q["quant_lanes_ratio"] = (q["lanes_per_byte"]
                                  / max(p["lanes_per_byte"], 1e-12))
        record["quantized"].append(q)
        print(f"             quantized({q['page_dtype']}) "
              f"{q['tokens_per_s']:8.1f} tok/s "
              f"(kv {q['kv_cache_bytes'] / 1e6:.2f} vs "
              f"{p['kv_cache_bytes'] / 1e6:.2f} MB, "
              f"lanes/byte x{q['quant_lanes_ratio']:.2f})")
        if args.sampling:
            bench_capacity(eng, trace, capacity=cap, max_len=max_len,
                           chunk=4, compact_threshold=0.5,
                           sampling=_sampled_params)       # warmup
            q = bench_capacity(eng, trace, capacity=cap, max_len=max_len,
                               chunk=4, compact_threshold=0.5,
                               sampling=_sampled_params)
            q.update(temperature=0.8, top_p=0.9)
            record["sampled"].append(q)
            print(f"             sampled(T=0.8,p=0.9) "
                  f"{q['tokens_per_s']:8.1f} tok/s "
                  f"(p50/p99 {q['decode_step_p50_ms']:.1f}/"
                  f"{q['decode_step_p99_ms']:.1f} ms)")

    if args.chaos:
        # overload leg: bursts of prompts (every 4th at priority 5, every
        # 5th with a tight deadline) against a pool sized for HALF the
        # lanes and a bounded queue — shed, deadline misses and preemption
        # all fire on this trace by construction.  The calm twin replays
        # the SAME submissions (same rids) on ample pages with no queue
        # bound or chaos; every request the chaos leg finishes as done /
        # preempted_resumed must serve byte-identical tokens.
        cap = capacities[-1]
        per_lane = pages_needed(max_len, args.page_size)
        n_chaos = max(n_requests, 12)
        reqs = burst_trace(n_chaos, prompt_len=9, vocab=CFG["vocab_size"],
                           burst=cap, gap=8.0, seed=args.seed,
                           priority_of=lambda i: 5 if i % 4 == 3 else 0)
        for i, r in enumerate(reqs):
            if i % 5 == 4:
                r["deadline"] = r["arrival"] + 4.0
        # the whole trace is submitted up front (arrivals gate DUE-ness,
        # not queue entry), so the queue bound must leave room for the
        # later high-priority bursts to contend — bound it just under the
        # trace length: the overflow sheds, the rest overloads
        chaos_cfg = ChaosConfig(seed=args.chaos_seed, alloc_fail_rate=0.1)
        kw = dict(capacity=cap, max_len=max_len, page_size=args.page_size)
        bench_overload(eng, reqs, pool_pages=(cap // 2) * per_lane,
                       max_queue=n_chaos - 2, chaos=chaos_cfg, **kw)  # warmup
        ch, got = bench_overload(eng, reqs, pool_pages=(cap // 2) * per_lane,
                                 max_queue=n_chaos - 2, chaos=chaos_cfg, **kw,
                                 trace_dir=args.trace_dir,
                                 leg=f"chaos_cap{cap}")
        calm_reqs = [dict(r, deadline=None) for r in reqs]
        _, calm = bench_overload(eng, calm_reqs, pool_pages=cap * per_lane,
                                 **kw)
        finished = {rid for rid, (_, why) in got.items()
                    if why in (FinishReason.DONE,
                               FinishReason.PREEMPTED_RESUMED)}
        ch["tokens_identical_calm"] = all(
            got[rid][0].tobytes() == calm[rid][0].tobytes()
            for rid in finished)
        record["chaos"].append(ch)
        fr = ch["finish_reasons"]
        print(f"chaos({n_chaos} reqs, burst={cap})  "
              f"preempt {ch['preemptions']} shed {ch['shed']} "
              f"deadline {ch['deadline_misses']} "
              f"cancelled {ch['cancelled']}  "
              f"alloc-faults {ch['chaos_alloc_failures']}  "
              f"leaks {ch['page_leaks']}pg  "
              f"done {fr['done']}+{fr['preempted_resumed']} resumed  "
              f"identical to calm: {ch['tokens_identical_calm']}")
        if (ch["page_leaks"] != 0 or ch["preemptions"] == 0
                or not ch["tokens_identical_calm"]):
            print("FAIL chaos leg: expected zero page leaks, "
                  "preemptions > 0 and byte-identical finished tokens")
            raise SystemExit(1)

    if args.session_users:
        # multi-turn SESSION leg: each user's turn t+1 prompt extends turn
        # t's, and turn waves are gapped so the earlier lane has retired —
        # its prefix pages are off-pool — before the follow-up arrives.  A
        # hit can then only come from the host-swap tier paging the evicted
        # prefix back in.  Two gates ride the leg: cross-request hits must
        # actually occur, and the warm run's greedy tokens must equal the
        # cold (swap-disabled) run byte-for-byte — page-in restores the
        # same pool bytes that were spilled.
        cap = capacities[-1]
        s_max_len = 48
        s_trace = session_trace(np.random.RandomState(args.seed + 1),
                                args.session_users, args.session_turns,
                                args.page_size)
        kw = dict(capacity=cap, max_len=s_max_len, chunk=4,
                  compact_threshold=0.5, page_size=args.page_size,
                  pool_pages=cap * pages_needed(s_max_len, args.page_size))
        cold: dict = {}
        bench_capacity(eng, s_trace, **kw, collect=cold)
        warm: dict = {}
        sess = bench_capacity(eng, s_trace, **kw,
                              host_swap_pages=args.host_swap_pages,
                              collect=warm, trace_dir=args.trace_dir,
                              leg=f"session_cap{cap}")
        follow_ups = args.session_users * (args.session_turns - 1)
        sess.update({
            "users": args.session_users,
            "turns": args.session_turns,
            "follow_up_requests": follow_ups,
            "tokens_identical_cold": warm == cold,
        })
        record["session"].append(sess)
        print(f"session({args.session_users}u x {args.session_turns}t)  "
              f"hits {sess['session_hits']}/{follow_ups} follow-ups "
              f"({sess['session_hit_tokens']} tokens skipped, "
              f"swap out/in {sess['swap_out_pages']}/"
              f"{sess['swap_in_pages']} pages)  "
              f"tokens identical to cold: {sess['tokens_identical_cold']}")
        if sess["session_hits"] == 0 or not sess["tokens_identical_cold"]:
            print("FAIL session leg: expected cross-request hits > 0 with "
                  "byte-identical tokens after page-in")
            raise SystemExit(1)

    if mesh is not None:
        # tensor-parallel leg at the LARGEST capacity: same trace through a
        # mesh-backed engine (lanes over "data", KV heads/MLP over "model").
        # On a forced host-device CPU mesh this measures dispatch structure,
        # not speed — the HARD gate is that the sharded serve loop issues
        # exactly as many dispatches as the 1-device fused leg (sharding must
        # not reintroduce per-token host syncs), and tokens match byte-ness
        # aside, count-for-count.
        cap = capacities[-1]
        eng_tp = ServeEngine(cfg, params, max_new_tokens=max_new,
                             stop_token=7, mesh=mesh)
        bench_capacity(eng_tp, trace, capacity=cap, max_len=max_len, chunk=4,
                       compact_threshold=0.5, prefill_chunk=args.prefill_chunk)
        t = bench_capacity(eng_tp, trace, capacity=cap, max_len=max_len,
                           chunk=4, compact_threshold=0.5,
                           prefill_chunk=args.prefill_chunk,
                           trace_dir=args.trace_dir, leg=f"tp_cap{cap}")
        t["mesh"] = args.tp_mesh
        t["psum_mode"] = args.psum
        base = next(r for r in record["continuous"] if r["capacity"] == cap)
        t["tp_continuous_ratio"] = t["tokens_per_s"] / base["tokens_per_s"]
        record["tp"].append(t)
        print(f"capacity={cap:2d}  tp@{args.tp_mesh} "
              f"{t['tokens_per_s']:8.1f} tok/s "
              f"(ratio {t['tp_continuous_ratio']:.2f}, "
              f"dispatches {t['dispatches']} vs {base['dispatches']}, "
              f"syncs {t['host_syncs']}/{t['rounds']}r)")
        if (t["dispatches"] != base["dispatches"]
                or t["tokens"] != base["tokens"]):
            print(f"FAIL tp leg: dispatches {t['dispatches']} / tokens "
                  f"{t['tokens']} != continuous leg's "
                  f"{base['dispatches']} / {base['tokens']}")
            raise SystemExit(1)
        print(f"tp dispatch count matches continuous at capacity {cap}: ok")

    if args.max_trace_overhead is not None or args.trace_dir:
        # traced continuous leg at the largest capacity vs an UNTRACED twin:
        # the zero-sync telemetry contract, gated.  Tokens, dispatches and
        # host_syncs must match exactly (tracing observes, never perturbs)
        # and the throughput loss must stay under --max-trace-overhead.
        # The twin runs back-to-back with the traced leg and both take their
        # best-of-3 tokens_per_s — wall clocks this short are at the mercy
        # of CI machine noise, and the gate must measure tracing, not a
        # neighboring job.
        cap = capacities[-1]
        kw = dict(capacity=cap, max_len=max_len, chunk=4,
                  compact_threshold=0.5, prefill_chunk=args.prefill_chunk)
        base = tr = None
        for _ in range(3):
            b = bench_capacity(eng, trace, **kw)
            if base is None or b["tokens_per_s"] > base["tokens_per_s"]:
                base = b
            t = bench_capacity(eng, trace, **kw, obs=Obs(tracer=Tracer()),
                               trace_dir=args.trace_dir,
                               leg=f"traced_cap{cap}")
            if tr is None or t["tokens_per_s"] > tr["tokens_per_s"]:
                tr = t
        tr["trace_overhead"] = 1.0 - tr["tokens_per_s"] / base["tokens_per_s"]
        record["traced"].append(tr)
        print(f"capacity={cap:2d}  traced "
              f"{tr['tokens_per_s']:8.1f} tok/s "
              f"(overhead {tr['trace_overhead'] * 100:+.1f}%, "
              f"{tr.get('trace_events', 0)} events)")
        if (tr["tokens"] != base["tokens"]
                or tr["dispatches"] != base["dispatches"]
                or tr["host_syncs"] != base["host_syncs"]):
            print(f"FAIL traced leg: tokens/dispatches/syncs "
                  f"{tr['tokens']}/{tr['dispatches']}/{tr['host_syncs']} != "
                  f"untraced {base['tokens']}/{base['dispatches']}/"
                  f"{base['host_syncs']} — tracing perturbed the serve loop")
            raise SystemExit(1)
        if (args.max_trace_overhead is not None
                and tr["trace_overhead"] > args.max_trace_overhead):
            print(f"FAIL traced leg: tokens_per_s overhead "
                  f"{tr['trace_overhead'] * 100:.1f}% > "
                  f"{args.max_trace_overhead * 100:.0f}%")
            raise SystemExit(1)
        if args.max_trace_overhead is not None:
            print(f"trace overhead within "
                  f"{args.max_trace_overhead * 100:.0f}%: ok")

    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {args.out}")

    if args.min_paged_ratio is not None:
        bad = [p for p in record["paged"]
               if p["dense_paged_ratio"] < args.min_paged_ratio]
        if bad:
            for p in bad:
                print(f"FAIL capacity={p['capacity']}: paged/continuous "
                      f"ratio {p['dense_paged_ratio']:.2f} < "
                      f"{args.min_paged_ratio} at mem_frac={p['mem_frac']}")
            raise SystemExit(1)
        print(f"paged/continuous ratio >= {args.min_paged_ratio} "
              f"at mem_frac={args.paged_mem_frac}: ok")

    if args.min_quant_lanes_ratio is not None:
        bad = [q for q in record["quantized"]
               if q["quant_lanes_ratio"] < args.min_quant_lanes_ratio]
        if bad:
            for q in bad:
                print(f"FAIL capacity={q['capacity']}: quantized lanes/byte "
                      f"x{q['quant_lanes_ratio']:.2f} < "
                      f"{args.min_quant_lanes_ratio} vs f32 paged")
            raise SystemExit(1)
        print(f"quantized lanes-per-byte >= {args.min_quant_lanes_ratio}x "
              f"f32 paged at matched page count: ok")

    if args.min_continuous_ratio is not None:
        top = record["continuous"][-1]
        if top["continuous_static_ratio"] < args.min_continuous_ratio:
            print(f"FAIL capacity={top['capacity']}: continuous/static "
                  f"ratio {top['continuous_static_ratio']:.2f} < "
                  f"{args.min_continuous_ratio}")
            raise SystemExit(1)
        print(f"continuous/static ratio "
              f"{top['continuous_static_ratio']:.2f} >= "
              f"{args.min_continuous_ratio} "
              f"at capacity {top['capacity']}: ok")


if __name__ == "__main__":
    main()
