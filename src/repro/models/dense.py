"""Dense decoder-LM family: stablelm-3b/12b, command-r-plus, gemma3, and the
llama-3.2-vision backbone (grouped cross-attention layers).

Implementation notes
--------------------
* scan-over-layers with stacked params: HLO size is O(1) in depth.
* gemma3's 5:1 local:global pattern is ONE predicated attention with a
  *dynamic* per-layer window scalar (2**30 = global) — no duplicated branches
  (the SVE predication story: the mask changes, never the code).
* llama-vision: layers grouped in blocks of ``cross_attn_group`` (5); slot 3
  of each group is a cross-attention layer reading stub image embeddings
  (the modality frontend is a ShapeDtypeStruct stand-in per the task spec).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.obs.scopes import scope

from . import layers as L

NO_WINDOW = 2 ** 30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def axes(cfg):
    """Logical-axis tree mirroring init's params (cheap, array-free)."""
    ax = {"embed": L.embed_axes(cfg), "final_norm": L.norm_axes(cfg)}
    if cfg.cross_attn_group:
        ax["groups"] = {
            "self": L.stack_axes(L.stack_axes(L.block_axes(cfg))),
            "cross": L.stack_axes(L.block_axes(cfg)),
        }
    else:
        ax["blocks"] = L.stack_axes(L.block_axes(cfg))
    return ax


def init(key, cfg):
    k_emb, k_blocks, k_cross = jax.random.split(key, 3)
    params = {"embed": L.embed_init(k_emb, cfg),
              "final_norm": L.norm_init(cfg, cfg.d_model)}
    if cfg.cross_attn_group:
        g = cfg.cross_attn_group
        n_groups, n_self = cfg.n_layers // g, g - 1
        params["groups"] = {
            "self": L.stack_init(
                k_blocks, n_groups,
                lambda k: L.stack_init(k, n_self, lambda k2: L.block_init(k2, cfg))),
            "cross": L.stack_init(k_cross, n_groups,
                                  lambda k: L.block_init(k, cfg)),
        }
    else:
        params["blocks"] = L.stack_init(k_blocks, cfg.n_layers,
                                        lambda k: L.block_init(k, cfg))
    return params, axes(cfg)


def layer_windows(cfg):
    """(L,) int32 per-layer dynamic window (NO_WINDOW = global attention)."""
    idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    if cfg.local_window is None:
        return jnp.full((cfg.n_layers,), NO_WINDOW, jnp.int32)
    if cfg.local_global_period is None:
        return jnp.full((cfg.n_layers,), cfg.local_window, jnp.int32)
    is_global = (idx % cfg.local_global_period) == (cfg.local_global_period - 1)
    return jnp.where(is_global, NO_WINDOW, cfg.local_window).astype(jnp.int32)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _trunk_plain(params, cfg, x, positions, kv_lens):
    wins = layer_windows(cfg)

    def body(h, xs):
        lp, win = xs
        h, _ = L.block_apply(lp, h, positions, cfg, causal=True, window=win,
                             kv_lens=kv_lens)
        return h, None

    h, _ = jax.lax.scan(L.remat_wrap(body, cfg), x, (params["blocks"], wins))
    return h


def _trunk_vlm(params, cfg, x, positions, kv_lens, cross_emb):
    """Groups of (pre self layers, cross layer, 1 self layer): HF llama-3.2
    cross_attention_layers = [3, 8, 13, ...] with group size 5 and pre = 3."""
    g = cfg.cross_attn_group
    pre = g - 2

    def self_body(h, lp):
        h, _ = L.block_apply(lp, h, positions, cfg, causal=True, kv_lens=kv_lens)
        return h, None

    def group_body(h, gp):
        h, _ = jax.lax.scan(self_body, h,
                            jax.tree.map(lambda a: a[:pre], gp["self"]))
        h, _ = L.block_apply(gp["cross"], h, positions, cfg, kv_x=cross_emb,
                             causal=False, use_rope=False)
        h, _ = self_body(h, jax.tree.map(lambda a: a[pre], gp["self"]))
        return h, None

    h, _ = jax.lax.scan(L.remat_wrap(group_body, cfg), x, params["groups"])
    return h


def train_logits(params, cfg, batch):
    """batch: tokens (B, S) [+ lens (B,)] [+ cross_emb (B, N, d)]."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    kv_lens = batch.get("lens")
    x = L.embed(params["embed"], tokens, cfg)
    if cfg.cross_attn_group:
        h = _trunk_vlm(params, cfg, x, positions, kv_lens, batch["cross_emb"])
    else:
        h = _trunk_plain(params, cfg, x, positions, kv_lens)
    h = L.apply_norm(params["final_norm"], h, cfg)
    return L.unembed(params["embed"], h, cfg), {}


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode with KV caches
# ---------------------------------------------------------------------------

def make_cache(cfg, batch_size: int, max_len: int, dtype=None):
    """Allocate the decode cache pytree (zeros)."""
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    shp = (batch_size, hkv, max_len, hd)
    if cfg.cross_attn_group:
        g = cfg.cross_attn_group
        n_groups, n_self = cfg.n_layers // g, g - 1
        return {
            "k": jnp.zeros((n_groups, n_self) + shp, dtype),
            "v": jnp.zeros((n_groups, n_self) + shp, dtype),
            "cross_k": jnp.zeros((n_groups, batch_size, hkv, cfg.n_cross_tokens, hd), dtype),
            "cross_v": jnp.zeros((n_groups, batch_size, hkv, cfg.n_cross_tokens, hd), dtype),
            "pos": jnp.zeros((batch_size,), jnp.int32),
        }
    return {
        "k": jnp.zeros((cfg.n_layers,) + shp, dtype),
        "v": jnp.zeros((cfg.n_layers,) + shp, dtype),
        "pos": jnp.zeros((batch_size,), jnp.int32),
    }


def cache_batch_axes(cfg):
    """Which axis of each cache array is the request-lane (batch) axis.

    The serve scheduler treats a batch as a vector of request lanes (SVE
    §2.3.4); ``repro.models.gather_lanes``/``slot_update`` consume this map to
    permute or refill lanes as pure index gathers/scatters — no shape guessing.
    """
    if cfg.cross_attn_group:
        return {"k": 2, "v": 2, "cross_k": 1, "cross_v": 1, "pos": 0}
    return {"k": 1, "v": 1, "pos": 0}


# full prefix state lives in paged KV + pos, so prefix sharing is sound
PAGED_PREFIX_OK = True

# prefill() takes per-row pos0 start offsets with all state in the KV cache,
# so one prompt's prefill can be split into chunks (scheduler chunked prefill)
CHUNKED_PREFILL_OK = True
# decode has no cross-lane coupling: bursts may narrow to a lane prefix
LANE_INDEPENDENT_DECODE = True


def paged_decode_ok(cfg):
    """decode() accepts a paged cache directly (flash attention reads K/V
    through the page table instead of a gathered dense view).  Holds for the
    vlm variant too: self-attention K/V pages, cross K/V stays per-lane."""
    return True


def paged_cache_spec(cfg):
    """KV cache keys with a (max_len) token axis -> their leading layer dims.

    Cross-attention K/V (llama-vision) are per-request constants, not
    token-indexed, so they stay per-lane dense arrays.
    """
    if cfg.cross_attn_group:
        g = cfg.cross_attn_group
        return {"k": (cfg.n_layers // g, g - 1), "v": (cfg.n_layers // g, g - 1)}
    return {"k": (cfg.n_layers,), "v": (cfg.n_layers,)}


def make_paged_cache(cfg, batch_size: int, max_len: int, *, page_size: int,
                     pool_pages: int, dtype=None, page_dtype=None):
    """Paged decode cache: shared page pools + per-lane page table (+ the
    non-token-indexed remainder of make_cache).  ``page_dtype`` ("int8" /
    "fp8") stores pools narrow with per-slot scale pools riding alongside."""
    from repro.core import paging as PG
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    cache = PG.alloc_pools(paged_cache_spec(cfg), pool_pages, page_size,
                           hkv, hd, dtype, page_dtype=page_dtype)
    cache["page_table"] = jnp.zeros(
        (batch_size, PG.pages_needed(max_len, page_size)), jnp.int32)
    cache["pos"] = jnp.zeros((batch_size,), jnp.int32)
    if cfg.cross_attn_group:
        g = cfg.cross_attn_group
        n_groups = cfg.n_layers // g
        cache["cross_k"] = jnp.zeros(
            (n_groups, batch_size, hkv, cfg.n_cross_tokens, hd), dtype)
        cache["cross_v"] = jnp.zeros(
            (n_groups, batch_size, hkv, cfg.n_cross_tokens, hd), dtype)
    return cache


def _cross_kv(params_cross_attn, cross_emb, cfg):
    """Precompute cross K/V from (stub) image embeddings for one group."""
    hd = cfg.resolved_head_dim
    src = cross_emb.astype(L.cdt(cfg))
    k = L._split_heads(src @ params_cross_attn["wk"].astype(L.cdt(cfg)),
                       cfg.n_kv_heads, hd)
    v = L._split_heads(src @ params_cross_attn["wv"].astype(L.cdt(cfg)),
                       cfg.n_kv_heads, hd)
    return k, v


def prefill(params, cfg, batch, cache):
    """Run the prompt, fill caches, return (last-token logits, cache).

    batch: tokens (B, S), lens (B,) [+ cross_emb] [+ pos0 (B,)].  The cache
    must have max_len >= pos0 + S.  Per-row ragged lengths are first-class
    (whilelt masks).  ``pos0`` is the per-row start offset of a SUFFIX
    prefill: rows whose prompt prefix is already resident in the cache
    (prefix sharing) run only their suffix tokens, attending over the cached
    prefix K/V at positions [0, pos0) — per-row numerics are identical to a
    cold prefill of the full prompt because K/V blocking depends only on the
    cache length and each query row's mask depends only on its absolute
    position.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    lens = batch.get("lens")
    lens = jnp.full((b,), s, jnp.int32) if lens is None else jnp.asarray(lens, jnp.int32)
    pos0 = batch.get("pos0")
    pos0 = jnp.zeros((b,), jnp.int32) if pos0 is None else jnp.asarray(pos0, jnp.int32)
    positions = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    kv_lens = pos0 + lens
    x = L.embed(params["embed"], tokens, cfg)
    wins = layer_windows(cfg)

    if cfg.cross_attn_group:
        g = cfg.cross_attn_group
        pre = g - 2
        cross_emb = batch["cross_emb"]
        n_groups = cfg.n_layers // g
        h = x
        new_k, new_v, cks, cvs = [], [], [], []
        for gi in range(n_groups):                  # 8 groups: unrolled
            gp = jax.tree.map(lambda a, gi=gi: a[gi], params["groups"])
            ks_g, vs_g = [], []
            for si in range(g - 1):
                if si == pre:                       # cross before self slot `pre`
                    ck, cv = _cross_kv(gp["cross"]["attn"], cross_emb, cfg)
                    h, _ = L.block_apply(gp["cross"], h, positions, cfg,
                                         kv_x=cross_emb, causal=False,
                                         use_rope=False)
                    cks.append(ck)
                    cvs.append(cv)
                lp = jax.tree.map(lambda a, si=si: a[si], gp["self"])
                h, (kn, vn) = L.block_apply(
                    lp, h, positions, cfg, causal=True, kv_lens=kv_lens,
                    q_offset=pos0, cache=(cache["k"][gi, si], cache["v"][gi, si]),
                    cache_pos=pos0)
                ks_g.append(kn)
                vs_g.append(vn)
            new_k.append(jnp.stack(ks_g))
            new_v.append(jnp.stack(vs_g))
        cache = dict(cache)
        cache["k"], cache["v"] = jnp.stack(new_k), jnp.stack(new_v)
        cache["cross_k"], cache["cross_v"] = jnp.stack(cks), jnp.stack(cvs)
    else:
        def body(carry, xs):
            h, = carry
            lp, win, kc, vc = xs
            h, (kc, vc) = L.block_apply(
                lp, h, positions, cfg, causal=True, window=win, kv_lens=kv_lens,
                q_offset=pos0, cache=(kc, vc), cache_pos=pos0)
            return (h,), (kc, vc)

        (h,), (k_new, v_new) = jax.lax.scan(
            body, (x,), (params["blocks"], wins, cache["k"], cache["v"]))
        cache = dict(cache)
        cache["k"], cache["v"] = k_new, v_new

    cache["pos"] = pos0 + lens
    with scope("logits"):
        h = L.apply_norm(params["final_norm"], h, cfg)
        # logits at each row's last valid position (ragged gather)
        idx = jnp.clip(lens - 1, 0, s - 1)
        h_last = jnp.take_along_axis(
            h, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = L.unembed(params["embed"], h_last[:, None], cfg)[:, 0]
    return logits, cache


def decode(params, cfg, batch, cache):
    """One-token decode: batch = {"token": (B, 1)}.  Returns (logits, cache)."""
    token = batch["token"]
    b = token.shape[0]
    pos = cache["pos"]                              # (B,) current lengths
    positions = pos[:, None]
    x = L.embed(params["embed"], token, cfg)
    wins = layer_windows(cfg)

    if cfg.cross_attn_group:
        g = cfg.cross_attn_group
        pre = g - 2
        n_groups = cfg.n_layers // g
        h = x
        paged = "k_pages" in cache
        ksc = vsc = None
        if paged:
            # native paged vlm decode: self-attention K/V lives in page pools
            # (lead (n_groups, n_self)); cross K/V stays a per-lane constant
            kc, vc = cache["k_pages"], cache["v_pages"]
            ksc = cache.get("k_pages_scale")
            vsc = cache.get("v_pages_scale")
            table = cache["page_table"]
        else:
            kc, vc = cache["k"], cache["v"]
        for gi in range(n_groups):
            gp = jax.tree.map(lambda a, gi=gi: a[gi], params["groups"])
            for si in range(g - 1):
                if si == pre:                       # cross before self slot `pre`
                    h = _cross_decode(gp["cross"], h, positions, cfg,
                                      cache["cross_k"][gi], cache["cross_v"][gi])
                lp = jax.tree.map(lambda a, si=si: a[si], gp["self"])
                if not paged:
                    layer_cache = (kc[gi, si], vc[gi, si])
                elif ksc is None:
                    layer_cache = (kc[gi, si], vc[gi, si], table)
                else:
                    layer_cache = (kc[gi, si], vc[gi, si], table,
                                   ksc[gi, si], vsc[gi, si])
                h, new_kv = L.block_apply(
                    lp, h, positions, cfg, causal=False, kv_lens=pos + 1,
                    q_offset=pos, cache=layer_cache, cache_pos=pos)
                with scope("kv_write"):
                    kc = kc.at[gi, si].set(new_kv[0])
                    vc = vc.at[gi, si].set(new_kv[1])
                    if ksc is not None:
                        ksc = ksc.at[gi, si].set(new_kv[2])
                        vsc = vsc.at[gi, si].set(new_kv[3])
        cache = dict(cache)
        if paged:
            cache["k_pages"], cache["v_pages"] = kc, vc
            if ksc is not None:
                cache["k_pages_scale"], cache["v_pages_scale"] = ksc, vsc
        else:
            cache["k"], cache["v"] = kc, vc
    elif "k_pages" in cache:
        # native paged decode: each layer's attention scatter-stores the new
        # token into its page and gathers K/V blocks through the page table
        # (SVE §2.3.3) — the pool, not a per-lane dense cache, is the operand
        h = x
        kp, vp = cache["k_pages"], cache["v_pages"]     # (L, P, Hkv, ps, Dh)
        ksc = cache.get("k_pages_scale")                # (L, P, Hkv, ps) | None
        vsc = cache.get("v_pages_scale")
        table = cache["page_table"]
        dus = jax.lax.dynamic_update_slice_in_dim
        for li in range(cfg.n_layers):
            lp = _layer_params(params["blocks"], li)
            with scope("kv_write"):     # the layer's pools, out and back in
                layer_cache = ((kp[li], vp[li], table) if ksc is None else
                               (kp[li], vp[li], table, ksc[li], vsc[li]))
            h, new_kv = L.block_apply(
                lp, h, positions, cfg, causal=False, window=wins[li],
                kv_lens=pos + 1, q_offset=pos, cache=layer_cache,
                cache_pos=pos)
            with scope("kv_write"):
                kp = dus(kp, new_kv[0][None], li, axis=0)
                vp = dus(vp, new_kv[1][None], li, axis=0)
                if ksc is not None:
                    ksc = dus(ksc, new_kv[2][None], li, axis=0)
                    vsc = dus(vsc, new_kv[3][None], li, axis=0)
        cache = dict(cache)
        cache["k_pages"], cache["v_pages"] = kp, vp
        if ksc is not None:
            cache["k_pages_scale"], cache["v_pages_scale"] = ksc, vsc
    elif not cfg.scan_layers_decode:
        # unrolled decode: per-layer dynamic-update-slice on the STACKED cache
        # lets XLA alias in place — no scan-ys double buffer (EXPERIMENTS §Perf)
        h = x
        kc, vc = cache["k"], cache["v"]
        for li in range(cfg.n_layers):
            lp = _layer_params(params["blocks"], li)
            h, (kl, vl) = L.block_apply(
                lp, h, positions, cfg, causal=False, window=wins[li],
                kv_lens=pos + 1, q_offset=pos, cache=(kc[li], vc[li]),
                cache_pos=pos)
            with scope("kv_write"):
                kc = jax.lax.dynamic_update_slice_in_dim(kc, kl[None], li,
                                                         axis=0)
                vc = jax.lax.dynamic_update_slice_in_dim(vc, vl[None], li,
                                                         axis=0)
        cache = dict(cache)
        cache["k"], cache["v"] = kc, vc
    else:
        def body(carry, xs):
            h, = carry
            lp, win, kc, vc = xs
            h, (kc, vc) = L.block_apply(
                lp, h, positions, cfg, causal=False, window=win,
                kv_lens=pos + 1, q_offset=pos, cache=(kc, vc), cache_pos=pos)
            return (h,), (kc, vc)

        (h,), (k_new, v_new) = jax.lax.scan(
            body, (x,), (params["blocks"], wins, cache["k"], cache["v"]))
        cache = dict(cache)
        cache["k"], cache["v"] = k_new, v_new

    cache["pos"] = pos + 1
    with scope("logits"):
        h = L.apply_norm(params["final_norm"], h, cfg)
        logits = L.unembed(params["embed"], h, cfg)[:, 0]
    return logits, cache


_PART_OF = {"ln1": "attn_proj", "attn": "attn_proj", "mlp": "mlp",
            "ln2": "mlp"}


def _layer_params(blocks, li: int) -> dict:
    """Layer ``li``'s weights out of the stacked ``blocks``, each slice in
    the scope of the part that reads it (XLA may copy a slice out)."""
    lp = {}
    for key, stacked in blocks.items():
        with scope(_PART_OF[key]):
            lp[key] = jax.tree.map(lambda a: a[li], stacked)
    return lp


def _cross_decode(block_p, h, positions, cfg, ck, cv):
    """Cross-attention sub-block against precomputed cross K/V."""
    from repro.kernels.flash_attention import flash_attention
    hd = cfg.resolved_head_dim
    hin = L.apply_norm(block_p["ln1"], h, cfg)
    q = L._split_heads(hin.astype(L.cdt(cfg)) @ block_p["attn"]["wq"].astype(L.cdt(cfg)),
                       cfg.n_heads, hd)
    if cfg.qk_norm:
        q = L._rms_headdim(q)
    out = flash_attention(q, ck.astype(L.cdt(cfg)), cv.astype(L.cdt(cfg)),
                          causal=False, impl=cfg.attn_impl)
    out = L._merge_heads(out).astype(L.cdt(cfg)) @ block_p["attn"]["wo"].astype(L.cdt(cfg))
    if cfg.parallel_block:
        h = h + out + L.mlp(block_p["mlp"], hin, cfg)
    else:
        h2 = h + out
        h = h2 + L.mlp(block_p["mlp"], L.apply_norm(block_p["ln2"], h2, cfg), cfg)
    return h
