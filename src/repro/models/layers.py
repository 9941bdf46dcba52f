"""Shared model building blocks (pure functional, dtype-disciplined).

Conventions
-----------
* every ``*_init`` returns a params pytree; the matching ``*_axes`` returns a
  tree of logical-axis tuples (one name or None per array dim) consumed by
  ``repro.dist.sharding`` — the mesh-agnostic resolution is the cluster-scale
  VLA story (DESIGN.md §2).
* master params live in ``cfg.param_dtype``; matmul inputs are cast to
  ``cfg.compute_dtype``; norms/softmax/rope run in f32.
* stacked layers carry a leading "layers" axis and are consumed by lax.scan.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import paging as PG
from repro.dist import sharding as SH
from repro.kernels.flash_attention import flash_attention
from repro.obs.scopes import scope


def shard_act(cfg, x, axes):
    """Activation sharding constraint (Megatron-TP pattern), opt-in via
    cfg.act_shard; no-op outside dist.sharding.use_mesh_rules."""
    if cfg.act_shard == "none":
        return x
    return SH.constrain(x, axes)


def shard_pool(cfg, pool):
    """KV page-pool sharding constraint: heads over the model axis, the page
    and page-size dims whole (pools are addressed by table gathers — a split
    page dim would turn every gather into a collective).  Same opt-in as
    shard_act; a head count that doesn't divide the axis replicates."""
    if cfg.act_shard == "none":
        return pool
    nd = pool.ndim
    return SH.constrain(pool, (None,) * (nd - 3) + ("kv_heads", None, None))


def shard_scale(cfg, scale):
    """Per-slot scale-pool sharding constraint: ``lead + (P, Hkv, ps)`` rides
    its pool — heads over the model axis, page dims whole."""
    if cfg.act_shard == "none":
        return scale
    nd = scale.ndim
    return SH.constrain(scale, (None,) * (nd - 2) + ("kv_heads", None))


def shard_residual(cfg, x):
    """Megatron-SP: residual stream (B, S, d) sharded over the model axis on
    the seq dim between blocks (only under act_shard='tp_sp').  The remat-
    saved per-layer carry shrinks by the TP degree; XLA inserts the
    all-gather/reduce-scatter pair at the qkv/mlp boundaries."""
    if cfg.act_shard != "tp_sp":
        return x
    return SH.constrain(x, ("batch", "act_seq", None))

# ---------------------------------------------------------------------------
# dtype helpers
# ---------------------------------------------------------------------------

def pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


def cdt(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(cfg, d):
    p = {"scale": jnp.ones((d,), pdt(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), pdt(cfg))
    return p


def norm_axes(cfg):
    ax = {"scale": ("embed",)}
    if cfg.norm == "layernorm":
        ax["bias"] = ("embed",)
    return ax


def apply_norm(p, x, cfg):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def _rms_headdim(x, eps=1e-6):
    """qk-norm: rmsnorm over the head dim (no learned scale for simplicity)."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float, rotary_frac: float = 1.0):
    """x: (B, H, S, Dh); positions: (B, S) int32.  Half-split convention."""
    dh = x.shape[-1]
    rd = int(dh * rotary_frac)
    rd -= rd % 2
    if rd == 0:
        return x
    half = rd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, :, None] * freqs  # (B,1,S,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rd].astype(jnp.float32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rd:]], axis=-1)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def embed_init(key, cfg):
    v, d = cfg.padded_vocab, cfg.d_model
    k1, k2 = jax.random.split(key)
    p = {"tok": _normal(k1, (v, d), d ** -0.5, pdt(cfg))}
    if not cfg.tie_embeddings:
        p["unembed"] = _normal(k2, (d, v), d ** -0.5, pdt(cfg))
    return p


def embed_axes(cfg):
    ax = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        ax["unembed"] = ("embed", "vocab")
    return ax


@scope("embed")
def embed(p, ids, cfg):
    x = jnp.take(p["tok"], ids, axis=0).astype(cdt(cfg))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt(cfg))
    return shard_act(cfg, x, ("batch", None, None))


@scope("logits")
def unembed(p, x, cfg):
    w = (p["tok"].T if cfg.tie_embeddings else p["unembed"]).astype(cdt(cfg))
    logits = jnp.einsum("bsd,dv->bsv", x.astype(cdt(cfg)), w)
    logits = shard_act(cfg, logits, ("batch", None, "act_vocab"))
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits.astype(jnp.float32) / c) * c
        logits = logits.astype(cdt(cfg))
    if cfg.padded_vocab != cfg.vocab_size:
        # padded slots are dead: mask so losses/samplers never pick them
        lane = jnp.arange(cfg.padded_vocab)
        logits = jnp.where(lane >= cfg.vocab_size, jnp.asarray(-1e30, logits.dtype),
                           logits)
    return logits


# ---------------------------------------------------------------------------
# mlp
# ---------------------------------------------------------------------------

def mlp_init(key, cfg, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_down": _normal(ks[2], (f, d), f ** -0.5, pdt(cfg))}
    if cfg.activation in ("swiglu", "geglu"):
        p["w_gate"] = _normal(ks[0], (d, f), d ** -0.5, pdt(cfg))
        p["w_up"] = _normal(ks[1], (d, f), d ** -0.5, pdt(cfg))
    else:
        p["w_up"] = _normal(ks[1], (d, f), d ** -0.5, pdt(cfg))
    if cfg.use_bias:
        p["b_up"] = jnp.zeros((f,), pdt(cfg))
        p["b_down"] = jnp.zeros((d,), pdt(cfg))
    return p


def mlp_axes(cfg, d_ff: Optional[int] = None):
    # w_down's ff dim feeds the down-proj contraction ("mlp_in", see wo)
    ax = {"w_down": ("mlp_in", "embed")}
    if cfg.activation in ("swiglu", "geglu"):
        ax["w_gate"] = ("embed", "mlp")
        ax["w_up"] = ("embed", "mlp")
    else:
        ax["w_up"] = ("embed", "mlp")
    if cfg.use_bias:
        ax["b_up"] = ("mlp",)
        ax["b_down"] = ("embed",)
    return ax


@scope("mlp")
def mlp(p, x, cfg):
    act_axes = ("batch",) + (None,) * (x.ndim - 2) + ("act_mlp",)
    xc = x.astype(cdt(cfg))
    up = shard_act(cfg, xc @ p["w_up"].astype(cdt(cfg)), act_axes)
    if cfg.use_bias:
        up = up + p["b_up"].astype(cdt(cfg))
    if cfg.activation == "swiglu":
        up = jax.nn.silu(shard_act(cfg, xc @ p["w_gate"].astype(cdt(cfg)),
                                   act_axes)) * up
    elif cfg.activation == "geglu":
        up = jax.nn.gelu(shard_act(cfg, xc @ p["w_gate"].astype(cdt(cfg)),
                                   act_axes), approximate=True) * up
    else:
        up = jax.nn.gelu(up, approximate=True)
    # the down-proj input (see act_attn_in): training keeps it sharded and
    # psums the partial dots; serving gathers the (small) intermediate here
    # so the contraction runs whole — bitwise-identical logits, and the
    # collective is a few KB of activations, not the up-proj weights
    up = shard_act(cfg, up,
                   ("batch",) + (None,) * (x.ndim - 2) + ("act_mlp_in",))
    out = up @ p["w_down"].astype(cdt(cfg))
    if cfg.use_bias:
        out = out + p["b_down"].astype(cdt(cfg))
    out = shard_act(cfg, out, ("batch",) + (None,) * (x.ndim - 1))
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (self / cross, cached / uncached, local / global)
# ---------------------------------------------------------------------------

def attn_init(key, cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    qo, kvo = cfg.n_heads * hd, cfg.n_kv_heads * hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": _normal(ks[0], (d, qo), d ** -0.5, pdt(cfg)),
        "wk": _normal(ks[1], (d, kvo), d ** -0.5, pdt(cfg)),
        "wv": _normal(ks[2], (d, kvo), d ** -0.5, pdt(cfg)),
        "wo": _normal(ks[3], (qo, d), qo ** -0.5, pdt(cfg)),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((qo,), pdt(cfg))
        p["bk"] = jnp.zeros((kvo,), pdt(cfg))
        p["bv"] = jnp.zeros((kvo,), pdt(cfg))
        p["bo"] = jnp.zeros((d,), pdt(cfg))
    return p


def attn_axes(cfg):
    # wo's first dim feeds the out-proj CONTRACTION: it gets its own logical
    # name so serving can replicate it (a contraction split psums partial
    # dots, which reassociates the f32 sum — bitwise-identical serving
    # gathers the merged heads and runs the full dot instead)
    ax = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
          "wv": ("embed", "kv_heads"), "wo": ("heads_in", "embed")}
    if cfg.use_bias:
        ax.update({"bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",),
                   "bo": ("embed",)})
    return ax


def _split_heads(t, n_heads, hd):
    b, s, _ = t.shape
    return t.reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)


def _merge_heads(t):
    b, h, s, hd = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def update_kv_cache(k_cache, v_cache, k_new, v_new, pos):
    """Write (B, Hkv, Snew, Dh) at per-row offsets pos (B,) into (B, Hkv, Smax, Dh)."""
    def row(kc, vc, kn, vn, p0):
        kc = jax.lax.dynamic_update_slice(kc, kn.astype(kc.dtype), (0, p0, 0))
        vc = jax.lax.dynamic_update_slice(vc, vn.astype(vc.dtype), (0, p0, 0))
        return kc, vc
    return jax.vmap(row)(k_cache, v_cache, k_new, v_new, pos)


def attention(p, x, positions, cfg, *,
              kv_x=None, causal=True, window=None, kv_lens=None,
              q_offset=None, cache=None, cache_pos=None, use_rope=True):
    """Returns (out, new_cache_kv_or_None).

    - ``kv_x``: cross-attention source (image/frame/encoder memory).
    - ``cache``: (k_cache, v_cache) of shape (B, Hkv, Smax, Dh); new K/V are
      written at ``cache_pos`` (B,) and attention runs over the cache.
    - ``window``: None | int | scalar array — dynamic sliding window, one
      predicated kernel for local AND global layers (DESIGN.md C2).
    """
    with scope("attn_proj"):
        q, k, v = _project_qkv(p, x, positions, cfg, kv_x, use_rope)
    page_table = k_sc = v_sc = new_cache = None
    if cache is not None:
        with scope("kv_write"):
            k, v, page_table, k_sc, v_sc, new_cache = _store_kv(
                cfg, cache, cache_pos, k, v)

    with scope("attn_core"):
        out = flash_attention(
            q, k, v, kv_lens=kv_lens, causal=causal, window=window,
            q_offset=q_offset, impl=cfg.attn_impl, page_table=page_table,
            k_scale=k_sc, v_scale=v_sc)
        out = shard_act(cfg, out, ("batch", "act_heads", None, None))
    with scope("attn_proj"):
        # the out-proj input: under training rules act_attn_in rides "model"
        # (Megatron row-parallel, psum after the dot); under SERVE_RULES it
        # replicates, gathering the merged heads BEFORE the dot so the
        # contraction runs whole and logits stay bitwise-identical to
        # 1-device
        merged = shard_act(cfg, _merge_heads(out).astype(cdt(cfg)),
                           ("batch", None, "act_attn_in"))
        out = merged @ p["wo"].astype(cdt(cfg))
        if cfg.use_bias:
            out = out + p["bo"].astype(cdt(cfg))
        out = shard_act(cfg, out, ("batch", None, None))
    return out.astype(x.dtype), new_cache


def _project_qkv(p, x, positions, cfg, kv_x, use_rope):
    """Q/K/V projections as (B, H, S, Dh), with qk-norm and rope."""
    hd = cfg.resolved_head_dim
    xc = x.astype(cdt(cfg))
    src = xc if kv_x is None else kv_x.astype(cdt(cfg))

    q = xc @ p["wq"].astype(cdt(cfg))
    k = src @ p["wk"].astype(cdt(cfg))
    v = src @ p["wv"].astype(cdt(cfg))
    if cfg.use_bias:
        q, k, v = (q + p["bq"].astype(cdt(cfg)), k + p["bk"].astype(cdt(cfg)),
                   v + p["bv"].astype(cdt(cfg)))
    q = shard_act(cfg, _split_heads(q, cfg.n_heads, hd),
                  ("batch", "act_heads", None, None))
    k = shard_act(cfg, _split_heads(k, cfg.n_kv_heads, hd),
                  ("batch", "act_kv_heads", None, None))
    v = shard_act(cfg, _split_heads(v, cfg.n_kv_heads, hd),
                  ("batch", "act_kv_heads", None, None))

    if cfg.qk_norm:
        q, k = _rms_headdim(q), _rms_headdim(k)
    if use_rope and kv_x is None:
        q = rope(q, positions, cfg.rope_theta, cfg.partial_rotary_factor)
        k = rope(k, positions, cfg.rope_theta, cfg.partial_rotary_factor)
    return q, k, v


def _store_kv(cfg, cache, cache_pos, k, v):
    """Store the new K/V at ``cache_pos`` into a dense or paged ``cache``.
    Returns the K/V attention reads (the cache, or the pools), the page
    table, the scale pools of a quantized cache, and the new cache."""
    k_sc = v_sc = None
    if len(cache) in (3, 5):
        # paged cache (k_pool, v_pool, page_table[, k_scale, v_scale]):
        # scatter-store the new token into the lane's tail page; attention
        # gathers K/V blocks through the page table (SVE §2.3.3).  The
        # 5-tuple is a QUANTIZED cache: the scatter truncates to the
        # narrow pool dtype (per-slot absmax scale) and the gather widens
        # in register.  Decode-only (Snew == 1).
        k_pool, v_pool, page_table = cache[:3]
        ps = k_pool.shape[2]
        page_col = jnp.clip(cache_pos // ps, 0, page_table.shape[1] - 1)
        page_ids = jnp.take_along_axis(page_table, page_col[:, None],
                                       axis=1)[:, 0]
        off = cache_pos % ps
        if len(cache) == 5:
            k_sc, v_sc = cache[3], cache[4]
            k_pool, k_sc = PG.scatter_page_q(k_pool, k_sc, page_ids, off,
                                             k[:, :, 0, :])
            v_pool, v_sc = PG.scatter_page_q(v_pool, v_sc, page_ids, off,
                                             v[:, :, 0, :])
            k_sc, v_sc = shard_scale(cfg, k_sc), shard_scale(cfg, v_sc)
            k_pool = shard_pool(cfg, k_pool)
            v_pool = shard_pool(cfg, v_pool)
            k, v = k_pool, v_pool            # narrow: widened in-gather
            new_cache = (k_pool, v_pool, k_sc, v_sc)
        else:
            k_pool = shard_pool(cfg, PG.scatter_page(
                k_pool, page_ids, off, k[:, :, 0, :]))
            v_pool = shard_pool(cfg, PG.scatter_page(
                v_pool, page_ids, off, v[:, :, 0, :]))
            k, v = k_pool.astype(cdt(cfg)), v_pool.astype(cdt(cfg))
            new_cache = (k_pool, v_pool)
        return k, v, page_table, k_sc, v_sc, new_cache
    k_cache, v_cache = cache
    k_cache, v_cache = update_kv_cache(k_cache, v_cache, k, v, cache_pos)
    k, v = k_cache.astype(cdt(cfg)), v_cache.astype(cdt(cfg))
    return k, v, None, None, None, (k_cache, v_cache)


# ---------------------------------------------------------------------------
# transformer block (pre-norm / cohere-parallel), dense MLP
# ---------------------------------------------------------------------------

def block_init(key, cfg, d_ff: Optional[int] = None):
    k1, k2 = jax.random.split(key)
    p = {"ln1": norm_init(cfg, cfg.d_model), "attn": attn_init(k1, cfg),
         "mlp": mlp_init(k2, cfg, d_ff)}
    if not cfg.parallel_block:
        p["ln2"] = norm_init(cfg, cfg.d_model)
    return p


def block_axes(cfg, d_ff: Optional[int] = None):
    ax = {"ln1": norm_axes(cfg), "attn": attn_axes(cfg),
          "mlp": mlp_axes(cfg, d_ff)}
    if not cfg.parallel_block:
        ax["ln2"] = norm_axes(cfg)
    return ax


def block_apply(p, x, positions, cfg, *, causal=True, window=None,
                kv_lens=None, q_offset=None, cache=None, cache_pos=None,
                kv_x=None, use_rope=True):
    x = shard_residual(cfg, x)
    with scope("attn_proj"):            # the input norm feeds the projections
        h = apply_norm(p["ln1"], x, cfg)
    attn_out, new_cache = attention(
        p["attn"], h, positions, cfg, kv_x=kv_x, causal=causal, window=window,
        kv_lens=kv_lens, q_offset=q_offset, cache=cache, cache_pos=cache_pos,
        use_rope=use_rope)
    if cfg.parallel_block:                      # cohere: one norm, two branches
        out = x + attn_out + mlp(p["mlp"], h, cfg)
    else:
        h2 = x + attn_out
        with scope("mlp"):
            out = h2 + mlp(p["mlp"], apply_norm(p["ln2"], h2, cfg), cfg)
    return shard_residual(cfg, out), new_cache


def remat_wrap(fn, cfg):
    """Activation checkpointing policy for scan-over-layers bodies."""
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def stack_init(key, n, init_one):
    """Stacked-layer init: vmap the per-layer init over n keys → leading L dim."""
    return jax.vmap(init_one)(jax.random.split(key, n))


def stack_axes(axes_one):
    return jax.tree.map(lambda ax: ("layers",) + tuple(ax), axes_one,
                        is_leaf=lambda x: isinstance(x, tuple))
