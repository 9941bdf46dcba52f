#!/usr/bin/env python3
"""Serve stablelm_3b at its full published width on a TPU: the quickest proof
that the system still starts on the chip.

    python chip_smoke.py              # one chip: phases 1 and 2
    python chip_smoke.py --chips 4    # only the 2x2 mesh against chip 0

Phase 1 serves 16 seeded requests through the normal launcher
(``repro.launch.serve.main``, in this process: a process that has touched JAX
holds the chip) with bf16 weights, a paged KV cache, chunked prefill and the
overlapped host loop, and checks that every request finishes with a token.
Phase 2 serves the same requests with ``attn_impl="kernel"``, so the paged and
dense Pallas flash kernels run compiled, and compares each request's prefill
logits with phase 1's against ``LOGIT_BOUND``.

``--chips 4`` serves the same requests through ``--mesh 2x2`` (data=2 x
model=2) and unsharded on chip 0, and reports whether the served tokens are
byte-identical.

Weights come from ``PRNGKey(0)`` and prompts from ``RandomState(0)``: nothing
is read from outside the checkout.  Without a TPU, or without the repository's
``src/`` beside this file, the script exits non-zero and prints no result.
The last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
N_REQUESTS = 16
PROMPT_LEN = 512
SERVE_ARGS = ["--arch", "stablelm_3b", "--batch", "8",
              "--requests", str(N_REQUESTS), "--prompt-len", str(PROMPT_LEN),
              "--max-new", "64", "--page-size", "16", "--prefill-chunk", "256",
              "--overlap"]
# Relative L2 distance between the two attention paths' prefill logits.
# Both run bf16 activations and agree to f32 rounding up to their bf16
# output cast, where an element may round one ulp (2^-8) apart.  Such flips
# feed 32 residual layers; even if every layer's were independent and
# full-size, they would add up to about sqrt(32) * 2^-8 ~ 2.2% of the logit
# norm.  5% leaves twice that, while a wrong mask, a stale page or a skipped
# block moves the logits by O(1).
LOGIT_BOUND = 0.05


class _CompileLog:
    """Counts the programs made ready for the backend (compiled, or loaded
    from the persistent cache), the seconds that took, and the cache's hits
    and misses, through ``jax.monitoring``."""

    def __init__(self, jax):
        self.n = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _serve(label, extra):
    """One launcher run; every request must finish ``done`` with a token."""
    from repro.launch import serve
    from repro.serve import FinishReason

    t = time.perf_counter()
    out = serve.main(SERVE_ARGS + extra)
    wall = time.perf_counter() - t
    gc.collect()                                # the scheduler and its pools
    res = out["results"]
    bad = sorted(rid for rid, r in res.items()
                 if r["finish_reason"] is not FinishReason.DONE
                 or r["n_generated"] < 1)
    if len(res) != N_REQUESTS or bad:
        raise RuntimeError(f"{label}: {len(res)}/{N_REQUESTS} results, "
                           f"not done or empty: {bad}")
    n_tok = sum(r["n_generated"] for r in res.values())
    print(f"[{label}] attn_impl={out['engine'].cfg.attn_impl} "
          f"requests={len(res)} done={len(res)} tokens={n_tok} "
          f"rounds={out['stats']['steps']} "
          f"dispatches={out['stats']['dispatches']} "
          f"prefill_chunks={out['stats']['prefill_chunks']} "
          f"wall_s={wall:.1f} (compilation included)", flush=True)
    return out


def _prefill_logits(eng, prompts):
    """First-token logits of every request, from the engine's own jitted
    prefill over the (unpadded) vocabulary, in rows of 8."""
    import numpy as np

    rids = sorted(prompts)
    rows = []
    for i in range(0, len(rids), 8):
        chunk = rids[i:i + 8]
        toks = np.zeros((len(chunk), PROMPT_LEN), np.int32)
        lens = np.zeros((len(chunk),), np.int32)
        for j, rid in enumerate(chunk):
            p = prompts[rid]
            toks[j, :len(p)] = p
            lens[j] = len(p)
        logits, _ = eng._prefill(eng.params, {"tokens": toks, "lens": lens},
                                 eng.make_cache(len(chunk), PROMPT_LEN))
        rows.append(np.asarray(logits, np.float32)[:, :eng.cfg.vocab_size])
    return dict(zip(rids, np.concatenate(rows)))


def _release(out):
    """Free a finished run's weights now: the engine's jitted closures keep
    it alive until a garbage collection, and two copies of full-width
    weights do not fit one chip."""
    import jax

    for x in jax.tree.leaves(out.pop("engine").params):
        x.delete()
    gc.collect()


def _token_agreement(a, b):
    """(identical positions, positions) over each request's common prefix."""
    same = total = 0
    for rid in a:
        x, y = a[rid]["tokens"], b[rid]["tokens"]
        n = min(len(x), len(y))
        same += int((x[:n] == y[:n]).sum())
        total += max(len(x), len(y))
    return same, total


def _describe(eng):
    import jax

    cfg = eng.cfg
    nbytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    print(f"[smoke] arch={cfg.name} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} param_dtype={cfg.param_dtype} "
          f"compute_dtype={cfg.compute_dtype}")
    print(f"[smoke] param_bytes={nbytes}", flush=True)


def _one_chip():
    import numpy as np

    out1 = _serve("phase1", [])
    _describe(out1["engine"])
    ref = _prefill_logits(out1["engine"], out1["prompts"])
    _release(out1)
    out2 = _serve("phase2", ["--attn-impl", "kernel"])
    got = _prefill_logits(out2["engine"], out2["prompts"])
    rel = {rid: float(np.linalg.norm(got[rid] - ref[rid])
                      / np.linalg.norm(ref[rid])) for rid in ref}
    worst = max(rel, key=rel.get)
    max_abs = max(float(np.abs(got[r] - ref[r]).max()) for r in ref)
    argmax_same = sum(int(got[r].argmax() == ref[r].argmax()) for r in ref)
    same, total = _token_agreement(out1["results"], out2["results"])
    print(f"[phase2] prefill logits vs phase1: max rel_l2={rel[worst]:.3e} "
          f"(req{worst}) bound={LOGIT_BOUND} max_abs={max_abs:.3e} "
          f"first-token argmax same={argmax_same}/{len(ref)}")
    print(f"[phase2] identical greedy tokens: {same}/{total} "
          f"({same / total:.3f}; reported, not gated)", flush=True)
    if rel[worst] > LOGIT_BOUND:
        raise RuntimeError(f"kernel prefill logits of req{worst} are "
                           f"{rel[worst]:.3e} from the XLA path "
                           f"(bound {LOGIT_BOUND})")


def _four_chips():
    import numpy as np

    sharded = _serve("mesh2x2", ["--mesh", "2x2"])
    _describe(sharded["engine"])
    got = _prefill_logits(sharded["engine"], sharded["prompts"])
    _release(sharded)
    plain = _serve("chip0", [])
    ref = _prefill_logits(plain["engine"], plain["prompts"])
    same, total = _token_agreement(plain["results"], sharded["results"])
    identical = all(plain["results"][r]["tokens"].tobytes()
                    == sharded["results"][r]["tokens"].tobytes()
                    for r in plain["results"])
    max_abs = max(float(np.abs(got[r] - ref[r]).max()) for r in ref)
    print(f"[mesh2x2] tokens byte-identical to chip 0: {identical} "
          f"({same}/{total} positions); prefill logits max_abs diff="
          f"{max_abs:.3e}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the 2x2-mesh path and its one-chip "
                         "reference")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}", file=sys.stderr)
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devs)}",
              file=sys.stderr)
        return 1
    entries = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
               else 0)
    log = _CompileLog(jax)
    print(f"[smoke] device={devs[0].device_kind} count={len(devs)} "
          f"jax={jax.__version__} cache_dir={cache_dir} "
          f"cache_entries_at_start={entries}", flush=True)
    if args.chips == 4:
        _four_chips()
    else:
        _one_chip()
    stats = devs[0].memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}")
    print(f"[smoke] compilations={log.n} compile_s={log.seconds:.1f} "
          f"cache_hits={log.hits} cache_misses={log.misses} "
          f"warm_cache={entries > 0}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
