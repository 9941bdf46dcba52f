"""Training launcher: real training for small/medium runs on the local
devices (see dryrun.py for the 80-cell mesh-scale lowering driver).

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --steps 50 --batch 8 --seq 128 --reduce

XLA latency-hiding flags for real TPU runs (compute/comm overlap):
    LIBTPU_INIT_ARGS="--xla_tpu_enable_async_collective_fusion=true
    --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true
    --xla_enable_async_all_gather=true"
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import all_arch_names, get_config
from repro.data import SyntheticLM
from repro.runtime import FaultTolerantLoop
from repro.runtime.compile_cache import enable_compile_cache
from repro.train.step import init_state, make_train_step

REDUCE = dict(n_layers=2, d_model=64, d_ff=128, vocab_size=256, n_heads=4,
              n_kv_heads=2, head_dim=16, n_cross_tokens=16,
              param_dtype="float32", compute_dtype="float32")


def reduce_config(cfg):
    """Shrink an architecture's dims for a CPU rehearsal, keeping its family
    and topology (layer pattern, expert routing, encoder/decoder split)."""
    over = dict(REDUCE)
    if cfg.family in ("ssm", "hybrid"):
        over.update(ssm_state=16, ssm_headdim=16, ssm_chunk=16)
    if cfg.family == "moe":
        over.update(n_experts=8, top_k=2,
                    d_ff_dense=128 if cfg.first_k_dense else None)
    if cfg.family == "encdec":
        over.update(n_enc_layers=2, n_dec_layers=2)
    if cfg.family == "hybrid":
        over.update(n_layers=5, shared_attn_period=2)
    if cfg.cross_attn_group:
        over.update(n_layers=10)
    return cfg.replace(**{k: v for k, v in over.items() if v is not None})


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2_130m", choices=all_arch_names())
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduce", action="store_true",
                    help="shrink dims for CPU (keeps family/topology)")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduce_config(cfg)
    print(f"arch={cfg.name} family={cfg.family} params={cfg.param_count():.3e}")

    state, _ = init_state(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, microbatch=args.microbatch),
                   donate_argnums=(0,))
    data = SyntheticLM(cfg.vocab_size, args.seq, seed=0)

    import numpy as np

    def batch_fn(s):
        tokens, labels, lens = data.batch(s, args.batch)
        batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels),
                 "lens": jnp.asarray(lens)}
        if cfg.family == "dense" and cfg.cross_attn_group:
            batch["cross_emb"] = jnp.asarray(
                np.random.RandomState(s).randn(
                    args.batch, cfg.n_cross_tokens, cfg.d_model)
                .astype(np.float32))
        if cfg.family == "encdec":
            batch["src_emb"] = jnp.asarray(
                np.random.RandomState(s).randn(args.batch, args.seq,
                                               cfg.d_model).astype(np.float32))
            batch["src_lens"] = jnp.full((args.batch,), args.seq, jnp.int32)
        return batch

    loop = FaultTolerantLoop(step, batch_fn, ckpt_dir=args.ckpt_dir,
                             save_every=10)
    state, hist = loop.run(state, args.steps, metrics_cb=lambda s, m: print(
        f"  step {s:3d} loss {float(m['loss']):.4f}") if s % 5 == 0 else None)
    print(f"loss {hist[0][1]:.4f} -> {hist[-1][1]:.4f}")


if __name__ == "__main__":
    main()
