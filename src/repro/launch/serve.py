"""Serving launcher: bring up a ServeEngine for an arch and push a stream of
ragged requests through the continuous-batching scheduler (default), or a
single static batch with --static.

The architecture's published config is served by default, with weights held
in its compute dtype (bf16: serving keeps no f32 master copy).  ``--reduce``
cuts it to 2 layers x d64 in float32 for a CPU rehearsal.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm_3b --reduce

``main(argv)`` is also the in-process entry point (``chip_smoke.py``): a
process that has touched JAX holds the chip, so a child could not serve.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import all_arch_names, get_config
from repro.dist import collectives as C
from repro.models import get_model
from repro.obs import Obs, Tracer
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import (
    ChaosConfig,
    ChaosMonkey,
    ContinuousBatchingScheduler,
    SamplingParams,
    ServeEngine,
)

from .mesh import force_host_devices, make_mesh, parse_mesh
from .train import reduce_config


def main(argv=None):
    """Parse ``argv`` (default: ``sys.argv``), serve, print per-request
    lines, and return ``{"results", "stats", "prompts", "engine"}``:
    results by request id, the scheduler's stats (None under --static), the
    prompt of each request id, and the engine (config + params)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_3b", choices=all_arch_names())
    ap.add_argument("--reduce", action="store_true",
                    help="CPU rehearsal: cut the architecture to 2 layers x "
                         "d64 in float32 (default: the published config)")
    ap.add_argument("--attn-impl", choices=["xla", "kernel"], default=None,
                    help="attention implementation (default: the config's); "
                         "'kernel' runs the Pallas flash kernels, compiled "
                         "on an accelerator and interpreted on the CPU")
    ap.add_argument("--batch", type=int, default=4,
                    help="lane capacity (scheduler) / batch size (--static)")
    ap.add_argument("--requests", type=int, default=10,
                    help="number of streamed requests (scheduler mode)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=4,
                    help="decode steps between admission opportunities")
    ap.add_argument("--compact-threshold", type=float, default=0.5)
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page; enables the paged cache "
                         "(admission gated on page availability)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="physical pages in the pool (default: the dense "
                         "footprint, capacity * pages-per-lane)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable prompt-prefix page sharing under --page-size")
    ap.add_argument("--page-dtype", choices=["int8", "fp8"], default=None,
                    help="quantized KV page pools: pages hold narrow elements "
                         "with per-(page, head, slot) f32 absmax scales, "
                         "dequantized inside the paged-attention gather "
                         "(requires --page-size)")
    ap.add_argument("--host-swap-pages", type=int, default=None,
                    help="host-side LRU swap store capacity in pages: shared-"
                         "prefix pages spill to host on eviction and page "
                         "back in on a later prompt hit — the cross-request "
                         "session cache (requires --page-size)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split admission prefill into chunks of this many "
                         "tokens interleaved with decode rounds (long "
                         "prompts stop stalling resident lanes; dense/moe "
                         "families)")
    ap.add_argument("--paged-attn", choices=["native", "gather"],
                    default="native",
                    help="paged decode path: 'native' reads K/V through the "
                         "page table inside flash attention; 'gather' is the "
                         "reference oracle (dense view materialized per step)")
    ap.add_argument("--no-fused", action="store_true",
                    help="run the legacy multi-dispatch host loop instead of "
                         "the fused one-dispatch-per-round step program")
    ap.add_argument("--overlap", action="store_true",
                    help="async host loop: dispatch round N+1 before reading "
                         "round N (one blocking sync per round)")
    ap.add_argument("--src-len", type=int, default=None,
                    help="encdec: padded encoder memory length the scheduler "
                         "allocates caches for (default: --prompt-len)")
    ap.add_argument("--static", action="store_true",
                    help="one-shot ServeEngine.generate instead of scheduler")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission queue: submits past this many "
                         "queued requests are SHED (typed partial result) "
                         "instead of queueing unboundedly")
    ap.add_argument("--deadline-steps", type=float, default=None,
                    help="per-request completion deadline, in decode steps "
                         "after arrival: a request still decoding past it "
                         "retires with its partial output "
                         "(finish_reason='deadline')")
    ap.add_argument("--priority-every", type=int, default=0,
                    help="mark every Nth request priority 5 (0 disables): "
                         "under page/lane starvation high-priority arrivals "
                         "preempt a lower-priority lane and the victim later "
                         "resumes bit-exactly")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="install a deterministic ChaosMonkey with this "
                         "seed (requires --chaos-* rates below to do "
                         "anything)")
    ap.add_argument("--chaos-alloc-fail-rate", type=float, default=0.0,
                    help="probability each page allocation spuriously fails "
                         "(models transient pool pressure)")
    ap.add_argument("--chaos-cancel-rate", type=float, default=0.0,
                    help="per-round probability each live request is "
                         "cancelled (exercises every cancel branch)")
    ap.add_argument("--chaos-swap-corrupt-rate", type=float, default=0.0,
                    help="probability a host-swap insert is byte-flipped "
                         "after its CRC — the next hit must degrade to a "
                         "cold prefill, never serve corrupt K/V")
    ap.add_argument("--temperature", type=float, default=None,
                    help="enable per-request stochastic sampling at this "
                         "temperature (default: greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k vocab filtering (0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus filtering mass (1.0 disables)")
    ap.add_argument("--min-p", type=float, default=0.0,
                    help="min-p filtering (0 disables)")
    ap.add_argument("--repetition-penalty", type=float, default=1.0)
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base sampling seed; request i uses seed+i, so "
                         "every stream is reproducible per request")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="mesh-sharded serving: data x model axes (e.g. 4x2 "
                         "= lanes over 4 ways, KV heads/MLP/experts over 2). "
                         "On a host-only box the device count is forced via "
                         "XLA_FLAGS; served tokens are byte-identical to the "
                         "unsharded loop")
    ap.add_argument("--psum", choices=list(C.PSUM_MODES), default="fast",
                    help="cross-device reduction ordering for shard_map-"
                         "level code: native all-reduce, or the "
                         "deterministic ordered (fadda) / pairwise (faddv) "
                         "collectives")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="record the serve run's round/request timeline and "
                         "export Chrome/Perfetto trace_event JSON to FILE "
                         "(open in ui.perfetto.dev); served tokens and "
                         "dispatch/sync counts are unchanged by tracing")
    ap.add_argument("--metrics", action="store_true",
                    help="print the obs registry snapshot (the flat "
                         "counter/percentile dict the serving bench records "
                         "per leg) after the run")
    ap.add_argument("--xla-annotations", action="store_true",
                    help="make every serve-loop span (round, plan, "
                         "admit, dispatch, harvest, sync, ...) a jax."
                         "profiler.TraceAnnotation, with or without "
                         "--trace-out, so a concurrently captured XLA "
                         "device profile holds the host's rounds on its "
                         "clock")
    args = ap.parse_args(argv)
    enable_compile_cache()

    C.set_psum_mode(args.psum)
    obs = Obs(tracer=Tracer() if args.trace_out else None,
              xla_annotations=args.xla_annotations)
    if args.metrics or args.trace_out:
        C.set_obs(obs)
    mesh = None
    if args.mesh is not None:
        d, m = parse_mesh(args.mesh)
        # must precede ANY backend touch (jax initializes devices lazily)
        force_host_devices(d * m)
        mesh = make_mesh((d, m), ("data", "model"))

    def _sampling(i: int):
        """Per-request SamplingParams (None = greedy) for request index i."""
        if args.temperature is None:
            return None
        return SamplingParams(temperature=args.temperature, top_k=args.top_k,
                              top_p=args.top_p, min_p=args.min_p,
                              repetition_penalty=args.repetition_penalty,
                              seed=args.sample_seed + i, greedy=False)

    cfg = get_config(args.arch)
    # full width serves from weights in the compute dtype (no f32 master)
    cfg = (reduce_config(cfg) if args.reduce
           else cfg.replace(param_dtype=cfg.compute_dtype))
    if args.attn_impl is not None:
        cfg = cfg.replace(attn_impl=args.attn_impl)

    model = get_model(cfg)
    # one jitted init: the weights materialize in their own dtype, with no
    # f32 staging copy of a whole layer stack on the device
    params = jax.jit(lambda k: model.init(k, cfg)[0])(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(1, cfg.vocab_size, (args.batch, args.prompt_len))),
        "lens": jnp.asarray(rng.randint(4, args.prompt_len + 1, args.batch))}
    if cfg.family == "dense" and cfg.cross_attn_group:
        batch["cross_emb"] = jnp.asarray(
            rng.randn(args.batch, cfg.n_cross_tokens, cfg.d_model)
            .astype(np.float32))
    if cfg.family == "encdec":
        batch["src_emb"] = jnp.asarray(
            rng.randn(args.batch, args.prompt_len, cfg.d_model)
            .astype(np.float32))
        batch["src_lens"] = jnp.full((args.batch,), args.prompt_len, jnp.int32)

    if args.page_dtype is not None and args.page_size is None:
        ap.error("--page-dtype requires --page-size (quantization lives in "
                 "the page pools)")
    if args.host_swap_pages is not None and args.page_size is None:
        ap.error("--host-swap-pages requires --page-size (the swap tier "
                 "moves pages)")
    eng = ServeEngine(cfg, params, max_new_tokens=args.max_new, stop_token=7,
                      paged_attn=args.paged_attn, mesh=mesh,
                      page_dtype=args.page_dtype, obs=obs)
    if args.static or cfg.cross_attn_group:
        # vlm cross_emb extras are per-batch, not yet per-request: static path
        res = eng.generate(batch, sampling=[_sampling(i)
                                            for i in range(args.batch)])
        results, prompts = {}, {}
        for i in range(args.batch):
            n = int(res["n_generated"][i])
            results[i] = {"tokens": np.asarray(res["tokens"][i, :n]),
                          "n_generated": n}
            prompts[i] = np.asarray(batch["tokens"][i, :int(batch["lens"][i])])
            print(f"req{i} len={int(batch['lens'][i]):2d} -> "
                  f"{results[i]['tokens'].tolist()}")
        _finish_obs(args, obs)
        return {"results": results, "stats": None, "prompts": prompts,
                "engine": eng}

    # ---- continuous batching: stream requests through the lane vector ----
    max_len = args.prompt_len + args.max_new
    src_len = ((args.src_len or args.prompt_len)
               if cfg.family == "encdec" else None)
    sched = ContinuousBatchingScheduler(
        eng, capacity=args.batch, max_len=max_len, chunk=args.chunk,
        compact_threshold=args.compact_threshold, page_size=args.page_size,
        pool_pages=args.pool_pages,
        prefix_sharing=not args.no_prefix_sharing,
        host_swap_pages=args.host_swap_pages,
        prefill_chunk=args.prefill_chunk,
        fused=not args.no_fused, overlap=args.overlap, src_len=src_len,
        max_queue=args.max_queue, obs=obs)
    monkey = None
    if args.chaos_seed is not None:
        monkey = ChaosMonkey(ChaosConfig(
            seed=args.chaos_seed,
            alloc_fail_rate=args.chaos_alloc_fail_rate,
            cancel_rate=args.chaos_cancel_rate,
            swap_corrupt_rate=args.chaos_swap_corrupt_rate)).install(sched)
    prompts = {}
    for i in range(args.requests):
        plen = int(rng.randint(4, args.prompt_len + 1))
        extras = None
        if cfg.family == "encdec":
            sl = int(rng.randint(2, src_len + 1))
            extras = {"src_emb": rng.randn(sl, cfg.d_model)
                      .astype(np.float32)}
        prio = (5 if args.priority_every and i % args.priority_every == 0
                else 0)
        prompt = rng.randint(1, cfg.vocab_size, plen)
        rid = sched.submit(prompt, sampling=_sampling(i), extras=extras,
                           priority=prio,
                           deadline=(args.deadline_steps
                                     if args.deadline_steps else None))
        prompts[rid] = prompt
    results = monkey.run(sched) if monkey else sched.run()
    for rid in sorted(results):
        r = results[rid]
        print(f"req{rid} len={len(prompts[rid]):2d} "
              f"[{r['finish_reason'].value}] -> "
              f"{r['tokens'].tolist()}")
    occ = sched.stats["occupancy_trace"]
    print(f"[scheduler] rounds={sched.stats['steps']} "
          f"dispatches={sched.stats['dispatches']} "
          f"host syncs={sched.stats['host_syncs']} "
          f"compactions={sched.stats['compactions']} "
          f"mean occupancy={sum(occ) / max(len(occ), 1):.2f}"
          + (f"  prefill chunks={sched.stats['prefill_chunks']}"
             if args.prefill_chunk else ""))
    st = sched.stats
    if (st["preemptions"] or st["cancelled"] or st["shed"]
            or st["deadline_misses"] or monkey):
        print(f"[robustness] preemptions={st['preemptions']} "
              f"(pages back in={st['resume_page_ins']})  "
              f"cancelled={st['cancelled']}  shed={st['shed']}  "
              f"deadline misses={st['deadline_misses']}"
              + (f"  [chaos seed={args.chaos_seed}: "
                 f"alloc fails={monkey.alloc_failures} "
                 f"cancels={monkey.cancels} "
                 f"corruptions={monkey.corruptions}]" if monkey else ""))
    if args.page_size is not None:
        pocc = sched.stats["page_occupancy_trace"]
        print(f"[paged] pool={sched.pool_pages} pages "
              f"(page_size={args.page_size})  "
              f"mean pool occupancy={sum(pocc) / max(len(pocc), 1):.2f}  "
              f"prefix hits={sched.stats['prefix_hits']} "
              f"({sched.stats['prefix_hit_tokens']} tokens skipped)  "
              f"page waits={sched.stats['page_waits']}"
              + (f"  page_dtype={args.page_dtype}" if args.page_dtype
                 else ""))
        if args.host_swap_pages:
            print(f"[swap] session hits={sched.stats['session_hits']} "
                  f"({sched.stats['session_hit_tokens']} tokens skipped)  "
                  f"out={sched.stats['swap_out_pages']} "
                  f"in={sched.stats['swap_in_pages']} pages  "
                  f"store={len(sched.host_swap)}/{args.host_swap_pages}  "
                  f"checksum failures="
                  f"{sched.stats['swap_checksum_failures']}")
    _finish_obs(args, obs)
    return {"results": results, "stats": sched.stats, "prompts": prompts,
            "engine": eng}


def _finish_obs(args, obs):
    """Export the trace / print the metrics snapshot per the CLI flags."""
    if args.trace_out:
        n = obs.export(args.trace_out)
        print(f"[obs] wrote {n} trace events to {args.trace_out} "
              "(open in ui.perfetto.dev or chrome://tracing)")
    if args.metrics:
        import json
        print("[obs] " + json.dumps(obs.metrics.snapshot(), indent=2,
                                    sort_keys=True))


if __name__ == "__main__":
    main()
