"""Memory-efficient flash attention in pure XLA (lax.scan blocks + custom VJP).

This is the execution path the dry-run lowers (``impl="xla"``): identical
online-softmax blocking to the Pallas kernel — so ``cost_analysis`` sees the
real FLOPs and ``memory_analysis`` sees the real O(S) working set — but built
from jnp ops, so it compiles for any backend and differentiates via a
hand-written flash backward (block-recomputed, two-pass dq / dkdv).

All masks are whilelt-predicates built from scalar bounds per block, exactly
as in kernel.py: causal, dynamic sliding window, ragged kv_lens, per-row
q_offset (decode) — one code path for every attention variant (SVE C2/C3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _block_pred(iq, ik, bq, bk, kv_lens, q_offset, window, causal):
    """(B, bq, bk) predicate for block (iq, ik).  Pure whilelt algebra."""
    qpos = (q_offset[:, None, None]
            + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq, bk), 1))
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bq, bk), 2)
    pred = kpos < kv_lens[:, None, None]
    if causal:
        pred &= qpos >= kpos
    pred &= kpos > (qpos - window)
    return pred


def _split_q(q, bq):
    b, h, sq, d = q.shape
    return q.reshape(b, h, sq // bq, bq, d).transpose(2, 0, 1, 3, 4)


def _split_kv(k, bk):
    b, hkv, skv, d = k.shape
    return k.reshape(b, hkv, skv // bk, bk, d).transpose(2, 0, 1, 3, 4)


def _merge_q(blocks):
    nq, b, h, bq, d = blocks.shape
    return blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, nq * bq, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash(q, k, v, kv_lens, q_offset, window, causal, scale, bq, bk):
    out, _ = _flash_fwd_impl(q, k, v, kv_lens, q_offset, window, causal,
                             scale, bq, bk)
    return out


def _flash_fwd_impl(q, k, v, kv_lens, q_offset, window, causal, scale, bq, bk):
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = h // hkv
    f32 = jnp.float32
    qs = _split_q(q.astype(f32), bq)                       # (nq,B,H,bq,D)
    qs = qs.reshape(qs.shape[0], b, hkv, g, bq, d)         # GQA: h-major groups
    ks = _split_kv(k.astype(f32), bk)                      # (nk,B,Hkv,bk,D)
    vs = _split_kv(v.astype(f32), bk)
    nk = ks.shape[0]

    def q_block(_, xs):
        qb, iq = xs                                        # (B,Hkv,G,bq,D)

        def kv_block(carry, xs2):
            m, l, acc = carry
            kb, vb, ik = xs2
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb) * scale
            pred = _block_pred(iq, ik, bq, bk, kv_lens, q_offset, window,
                               causal)[:, None, None]      # (B,1,1,bq,bk)
            s = jnp.where(pred, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(pred, jnp.exp(s - m_new[..., None]), 0.0)
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bhgqk,bhkd->bhgqd", p, vb)
            return (m_new, l, acc), None

        init = (jnp.full((b, hkv, g, bq), NEG_INF, f32),
                jnp.zeros((b, hkv, g, bq), f32),
                jnp.zeros((b, hkv, g, bq, d), f32))
        (m, l, acc), _ = jax.lax.scan(
            kv_block, init, (ks, vs, jnp.arange(nk, dtype=jnp.int32)))
        out_b = jnp.where(l[..., None] > 0.0,
                          acc / jnp.maximum(l[..., None], 1e-30), 0.0)
        lse_b = m + jnp.log(jnp.maximum(l, 1e-30))         # (B,Hkv,G,bq)
        return None, (out_b, lse_b)

    nq = qs.shape[0]
    _, (out_blocks, lse_blocks) = jax.lax.scan(
        q_block, None, (qs, jnp.arange(nq, dtype=jnp.int32)))
    out = out_blocks.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, sq, d)
    lse = lse_blocks.transpose(1, 2, 3, 0, 4).reshape(b, h, sq)
    return out.astype(q.dtype), lse


def _flash_fwd(q, k, v, kv_lens, q_offset, window, causal, scale, bq, bk):
    out, lse = _flash_fwd_impl(q, k, v, kv_lens, q_offset, window, causal,
                               scale, bq, bk)
    return out, (q, k, v, out, lse, kv_lens, q_offset, window)


def _flash_bwd(causal, scale, bq, bk, res, dout):
    q, k, v, out, lse, kv_lens, q_offset, window = res
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = h // hkv
    f32 = jnp.float32
    nq, nk = sq // bq, skv // bk

    qs = _split_q(q.astype(f32), bq).reshape(nq, b, hkv, g, bq, d)
    dos = _split_q(dout.astype(f32), bq).reshape(nq, b, hkv, g, bq, d)
    ls = _split_q(lse[..., None], bq)[..., 0].reshape(nq, b, hkv, g, bq)
    # delta = rowsum(dO * O)
    delta = jnp.sum(dout.astype(f32) * out.astype(f32), axis=-1)
    ds_blocks = _split_q(delta[..., None], bq)[..., 0].reshape(nq, b, hkv, g, bq)
    ks = _split_kv(k.astype(f32), bk)
    vs = _split_kv(v.astype(f32), bk)

    # ---- pass 1: dq (scan q blocks; inner scan kv) ----
    def q_block(_, xs):
        qb, dob, lb, db, iq = xs

        def kv_block(dqb, xs2):
            kb, vb, ik = xs2
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb) * scale
            pred = _block_pred(iq, ik, bq, bk, kv_lens, q_offset, window,
                               causal)[:, None, None]
            p = jnp.where(pred, jnp.exp(s - lb[..., None]), 0.0)
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", dob, vb)
            ds = p * (dp - db[..., None]) * scale
            dqb = dqb + jnp.einsum("bhgqk,bhkd->bhgqd", ds, kb)
            return dqb, None

        dqb, _ = jax.lax.scan(kv_block, jnp.zeros_like(qb),
                              (ks, vs, jnp.arange(nk, dtype=jnp.int32)))
        return None, dqb

    _, dq_blocks = jax.lax.scan(
        q_block, None, (qs, dos, ls, ds_blocks, jnp.arange(nq, dtype=jnp.int32)))
    dq = dq_blocks.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, sq, d)

    # ---- pass 2: dk, dv (scan kv blocks; inner scan q) ----
    def kv_block2(_, xs):
        kb, vb, ik = xs

        def q_block2(carry, xs2):
            dkb, dvb = carry
            qb, dob, lb, db, iq = xs2
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb) * scale
            pred = _block_pred(iq, ik, bq, bk, kv_lens, q_offset, window,
                               causal)[:, None, None]
            p = jnp.where(pred, jnp.exp(s - lb[..., None]), 0.0)
            dvb = dvb + jnp.einsum("bhgqk,bhgqd->bhkd", p, dob)
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", dob, vb)
            ds = p * (dp - db[..., None]) * scale
            dkb = dkb + jnp.einsum("bhgqk,bhgqd->bhkd", ds, qb)
            return (dkb, dvb), None

        init = (jnp.zeros((b, hkv, bk, d), f32), jnp.zeros((b, hkv, bk, d), f32))
        (dkb, dvb), _ = jax.lax.scan(
            q_block2, init,
            (qs, dos, ls, ds_blocks, jnp.arange(nq, dtype=jnp.int32)))
        return None, (dkb, dvb)

    _, (dk_blocks, dv_blocks) = jax.lax.scan(
        kv_block2, None, (ks, vs, jnp.arange(nk, dtype=jnp.int32)))
    dk = dk_blocks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, skv, d)
    dv = dv_blocks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, skv, d)

    zero_i = lambda t: jnp.zeros_like(t)  # int operands: symbolic zero grads
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zero_i(kv_lens), zero_i(q_offset), zero_i(window))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_xla(q, k, v, kv_lens, q_offset, window, *, causal,
                        scale, bq, bk):
    """Public entry (shapes already padded to block multiples by ops.py)."""
    return _flash(q, k, v, kv_lens, q_offset, window, causal, scale, bq, bk)


# ---------------------------------------------------------------------------
# Paged forward: a K/V block is npb pages, fetched through the page table
# ---------------------------------------------------------------------------

def flash_attention_xla_paged(q, k_pool, v_pool, page_table, kv_lens,
                              q_offset, window, *, causal, scale, bq, npb,
                              k_scale=None, v_scale=None):
    """Flash forward over a PAGED KV cache (SVE §2.3.3 gather-load).

    k_pool / v_pool: ``(P, Hkv, page_size, D)`` page pools; ``page_table``:
    ``(B, n_pages) int32``.  The kv-block scan walks LOGICAL pages ``npb`` at
    a time (ops._pick_pages_per_block chooses it from shapes) and fetches
    each lane's physical pages with one ``jnp.take`` on the pool — the index
    vector, not the layout, addresses memory, so the same kernel serves any
    physical placement (allocation order, prefix-shared pages, reuse).  The
    online-softmax math is identical to the dense path with ``bk == npb *
    page_size``; logical positions come from the page index, so masks are
    unchanged.  The table is padded to a multiple of ``npb`` with page 0:
    those columns lie past every ``kv_lens`` and are masked.  Serving/decode
    only — no VJP.  ``page_table`` arrives with out-of-strip (possibly
    stale) entries already clamped to page 0 under the page-granular
    whilelt — ops._flash_paged governs the walk once for every impl.

    ``k_scale`` / ``v_scale``: ``(P, Hkv, page_size)`` per-slot scale pools
    of a QUANTIZED cache — the same ``jnp.take`` that fetches a block of
    pages fetches their scales and widens the narrow elements in register
    (SVE §2.3.3 extending gather-load): ``kb = q8 * scale``.
    """
    b, h, sq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    g = h // hkv
    bk = npb * ps
    f32 = jnp.float32
    nq = sq // bq
    qs = _split_q(q.astype(f32), bq).reshape(nq, b, hkv, g, bq, d)
    n_pg = page_table.shape[1]
    nk = -(-n_pg // npb)
    table = jnp.pad(page_table, ((0, 0), (0, nk * npb - n_pg)))

    def fetch(pool, sc, pids):
        # (B, npb, Hkv, ps, D): kept page-major; the einsums contract over
        # (p, k) in place rather than transposing the block each step.
        # Every id is a pool page (stale ones clamped by ops), so "clip"
        # spares the block the NaN-fill select of the default mode
        blk = jnp.take(pool, pids, axis=0, mode="clip").astype(f32)
        if sc is not None:
            blk = blk * jnp.take(sc, pids, axis=0, mode="clip")[..., None]
        return blk

    def q_block(_, xs):
        qb, iq = xs

        def kv_block(carry, ik):
            m, l, acc = carry
            pids = jax.lax.dynamic_slice_in_dim(table, ik * npb, npb, axis=1)
            kb = fetch(k_pool, k_scale, pids)
            vb = fetch(v_pool, v_scale, pids)
            s = jnp.einsum("bhgqd,bphkd->bhgqpk", qb, kb)
            s = s.reshape(b, hkv, g, bq, bk) * scale
            pred = _block_pred(iq, ik, bq, bk, kv_lens, q_offset, window,
                               causal)[:, None, None]
            s = jnp.where(pred, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(pred, jnp.exp(s - m_new[..., None]), 0.0)
            l = alpha * l + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqpk,bphkd->bhgqd",
                            p.reshape(b, hkv, g, bq, npb, ps), vb)
            acc = acc * alpha[..., None] + pv
            return (m_new, l, acc), None

        init = (jnp.full((b, hkv, g, bq), NEG_INF, f32),
                jnp.zeros((b, hkv, g, bq), f32),
                jnp.zeros((b, hkv, g, bq, d), f32))
        (m, l, acc), _ = jax.lax.scan(kv_block, init,
                                      jnp.arange(nk, dtype=jnp.int32))
        out_b = jnp.where(l[..., None] > 0.0,
                          acc / jnp.maximum(l[..., None], 1e-30), 0.0)
        return None, out_b

    _, out_blocks = jax.lax.scan(q_block, None,
                                 (qs, jnp.arange(nq, dtype=jnp.int32)))
    out = out_blocks.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, sq, d)
    return out.astype(q.dtype)
