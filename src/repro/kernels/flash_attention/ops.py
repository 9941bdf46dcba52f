"""Public flash-attention op: VL-agnostic padding + kernel/XLA path switch."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import paging as _paging
from repro.core import vla

from . import ref as _ref
from .kernel import flash_attention_pallas, flash_attention_pallas_paged
from .xla_impl import flash_attention_xla, flash_attention_xla_paged


def _pick_blocks(sq: int, skv: int, d: int, dtype) -> tuple[int, int]:
    """Choose (bq, bk) MXU-aligned blocks that fit the VMEM budget.

    Working set ~ f32: q(bq,d) + k/v(bk,d)*2 + s(bq,bk) + acc(bq,d) + m/l(bq,128)*2.
    Policy: bq, bk in {128..512}, shrink to the problem when smaller.
    """
    bq = min(512, vla.pad_to_vl(sq, vla.LANE))
    bk = min(512, vla.pad_to_vl(skv, vla.LANE))
    budget = vla.VMEM_BYTES // 2
    while bq * bk * 4 + (bq + 2 * bk) * d * 4 + bq * (d + 256) * 4 > budget and bq > 128:
        bq //= 2
    while bq * bk * 4 + (bq + 2 * bk) * d * 4 + bq * (d + 256) * 4 > budget and bk > 128:
        bk //= 2
    return bq, bk


def _pick_pages_per_block(ps: int, n_pages: int, bq: int, hkv: int,
                          group: int, d: int) -> int:
    """Pages per kv block of the paged XLA walk, from shapes alone.

    One lane's block works on ``rows = hkv * group * bq`` query rows against
    ``bk = npb * ps`` keys of every KV head; under _pick_blocks' budget rule
    that is ~ f32: s(rows,bk) + q(rows,d) + k/v(hkv*bk,d)*2 + acc(rows,d) +
    m/l(rows,128)*2.  Policy: bk up to 512 tokens, halved (not below 128)
    until the block fits; the whole table when it is smaller; then the
    pages are spread evenly over the blocks, so the walk pads the table by
    fewer than one page a block.
    """
    rows = hkv * group * bq
    budget = vla.VMEM_BYTES // 2
    npb = max(1, min(n_pages, 512 // ps))
    while (rows * npb * ps * 4 + (rows + 2 * hkv * npb * ps) * d * 4
           + rows * (d + 256) * 4 > budget and npb * ps > 128 and npb > 1):
        npb //= 2
    n_blocks = -(-n_pages // npb)
    return -(-n_pages // n_blocks)


def flash_attention(
    q, k, v,
    *, kv_lens=None, causal: bool = False, window: int | None = None,
    q_offset=None, scale: float | None = None,
    impl: str = "kernel", bq: int | None = None, bk: int | None = None,
    page_table=None, k_scale=None, v_scale=None,
):
    """Predicated attention.  q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).

    - ``kv_lens``: (B,) valid KV lengths (ragged batches; defaults to Skv).
    - ``causal`` / ``window``: mask predicates (window = sliding local size).
    - ``q_offset``: (B,) absolute position of the first query row (decode);
      defaults to Skv - Sq under ``causal`` (suffix alignment) else 0.
    - ``impl``: "kernel" (Pallas TPU; interpreted on CPU), "xla" (chunked
      lax.scan flash with custom VJP — the introspectable O(S)-memory path the
      dry-run lowers), or "naive" (quadratic oracle; tests only).
    - ``page_table``: (B, n_pages) int32 — PAGED mode: ``k``/``v`` are page
      POOLS of shape (P, Hkv, page_size, D) and attention reads K/V through
      the table (SVE §2.3.3 gather-load).  Forward-only (serving).
    - ``k_scale`` / ``v_scale``: (P, Hkv, page_size) per-slot scale pools of a
      QUANTIZED paged cache; the gather widens ``q8 * scale`` in register (the
      extending gather-load).  Paged mode only.
    """
    if page_table is not None:
        return _flash_paged(q, k, v, page_table, kv_lens=kv_lens,
                            causal=causal, window=window, q_offset=q_offset,
                            scale=scale, impl=impl, bq=bq,
                            k_scale=k_scale, v_scale=v_scale)
    assert k_scale is None and v_scale is None, \
        "quantized K/V scales require page_table (paged mode)"
    b, hq, sq, d = q.shape
    skv = k.shape[2]
    if kv_lens is None:
        kv_lens = jnp.full((b,), skv, jnp.int32)
    else:
        kv_lens = jnp.asarray(kv_lens, jnp.int32)
    if q_offset is None:
        off = skv - sq if causal else 0
        q_offset = jnp.full((b,), off, jnp.int32)
    else:
        q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))

    if impl == "naive":
        return _ref.mha_ref(q, k, v, kv_lens=kv_lens, causal=causal,
                            window=window, q_offset=q_offset, scale=scale)

    if bq is None or bk is None:
        bq_d, bk_d = _pick_blocks(sq, skv, d, q.dtype)
        bq = bq_d if bq is None else bq
        bk = bk_d if bk is None else bk
    bq = min(bq, vla.pad_to_vl(sq, 8))
    # pad Sq / Skv to block multiples; predicates mask the tails (no recompile
    # per shape — the VLA contract)
    sq_p, skv_p = vla.pad_to_vl(sq, bq), vla.pad_to_vl(skv, bk)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    win = jnp.asarray(2 ** 30 if window is None else window,
                      jnp.int32).reshape((1,))
    if impl == "xla":
        scale_f = float(d ** -0.5) if scale is None else float(scale)
        out = flash_attention_xla(q, k, v, kv_lens, q_offset, win[0],
                                  causal=causal, scale=scale_f, bq=bq, bk=bk)
    else:
        out = flash_attention_pallas(
            q, k, v, kv_lens, q_offset, win, bq=bq, bk=bk, causal=causal,
            scale=scale)
    return out[:, :, :sq, :]


def _flash_paged(q, k_pool, v_pool, page_table, *, kv_lens, causal, window,
                 q_offset, scale, impl, bq, k_scale=None, v_scale=None):
    """Paged dispatch: pools + page table instead of dense K/V."""
    b, hq, sq, d = q.shape
    ps = k_pool.shape[2]
    n_pages = page_table.shape[1]
    skv = n_pages * ps                               # logical KV extent
    if kv_lens is None:
        kv_lens = jnp.full((b,), skv, jnp.int32)
    else:
        kv_lens = jnp.asarray(kv_lens, jnp.int32)
    if q_offset is None:
        off = skv - sq if causal else 0
        q_offset = jnp.full((b,), off, jnp.int32)
    else:
        q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    page_table = jnp.asarray(page_table, jnp.int32)
    # govern the table walk with the page-granular whilelt ONCE, for every
    # impl: out-of-strip entries may be stale (freed and reallocated ids),
    # so clamp them to page 0 before any gather / index_map chases them —
    # their contribution is masked by the element predicate regardless
    page_table = jnp.where(_paging.page_whilelt(kv_lens, n_pages, ps),
                           page_table, 0)

    if impl == "naive":
        # quadratic oracle over the gathered dense view (tests only) — the
        # extending gather widens quantized pools here too
        k = _paging.gather_pages(k_pool, page_table, scale=k_scale)
        v = _paging.gather_pages(v_pool, page_table, scale=v_scale)
        return _ref.mha_ref(q, k, v, kv_lens=kv_lens, causal=causal,
                            window=window, q_offset=q_offset, scale=scale)

    if bq is None:
        bq, _ = _pick_blocks(sq, skv, d, q.dtype)
    bq = min(bq, vla.pad_to_vl(sq, 8))
    sq_p = vla.pad_to_vl(sq, bq)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    win = jnp.asarray(2 ** 30 if window is None else window,
                      jnp.int32).reshape((1,))
    scale_f = float(d ** -0.5) if scale is None else float(scale)
    if impl == "xla":
        npb = _pick_pages_per_block(ps, n_pages, bq, k_pool.shape[1],
                                    hq // k_pool.shape[1], d)
        out = flash_attention_xla_paged(
            q, k_pool, v_pool, page_table, kv_lens, q_offset, win[0],
            causal=causal, scale=scale_f, bq=bq, npb=npb,
            k_scale=k_scale, v_scale=v_scale)
    else:
        out = flash_attention_pallas_paged(
            q, k_pool, v_pool, page_table, kv_lens, q_offset, win,
            bq=bq, causal=causal, scale=scale_f,
            k_scale=k_scale, v_scale=v_scale)
    return out[:, :, :sq, :]
