"""Predicated flash attention for TPU (Pallas).

The SVE story (DESIGN.md C1-C3) at lane scale: ONE kernel source handles
causal, sliding-window, cross- and ragged-length attention.  Every variant is
a *predicate* built inside the kernel from scalar bounds (``whilelt`` algebra
over broadcasted iotas) — never a separate shape-specialized kernel.  Tails
(Sq or Skv not multiples of the block) are predicated, not padded-and-wasted.

Blocking: grid (B, Hq, Sq/bq, Skv/bk); the KV axis is the innermost,
sequential ("arbitrary") dimension with the online-softmax running state
(m, l, acc) carried in VMEM scratch.  BlockSpecs keep one (bq, D) query tile,
one (bk, D) key tile and one (bk, D) value tile resident; with bq=bk=512 and
D=128 in f32 that is ~1.3 MiB of operand VMEM plus the (bq, bk) logits tile —
comfortably inside the ~16 MiB v5e budget and MXU-aligned (multiples of 128).

GQA is handled in the K/V index_map (head h reads KV head h // group), so KV
tiles are fetched once per group from HBM's point of view after XLA CSE.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30  # finite stand-in: keeps exp/where NaN-free in f32


def _flash_kernel(
    # scalar-prefetch operands (SMEM)
    kvlen_ref, qoff_ref, win_ref,
    # blocked operands
    q_ref, k_ref, v_ref,
    # blocked output
    o_ref,
    # VMEM scratch (persistent across the sequential KV grid axis)
    m_scr, l_scr, acc_scr,
    *, bq: int, bk: int, n_kv: int, causal: bool, scale: float,
):
    _flash_tile(kvlen_ref, qoff_ref, win_ref, q_ref,
                k_ref[0, 0].astype(jnp.float32),
                v_ref[0, 0].astype(jnp.float32),
                o_ref, m_scr, l_scr, acc_scr,
                bq=bq, bk=bk, n_kv=n_kv, causal=causal, scale=scale)


def _flash_tile(
    kvlen_ref, qoff_ref, win_ref, q_ref,
    k, v,                                          # (bk, D) f32 tiles, loaded
    o_ref, m_scr, l_scr, acc_scr,
    *, bq: int, bk: int, n_kv: int, causal: bool, scale: float,
    k_col=None, v_col=None,
):
    """The shared online-softmax tile body.  K/V tiles arrive as loaded f32
    arrays so callers may widen narrow (quantized) storage on the way in —
    the in-register half of SVE's extending load — without forking the math.
    ``k_col`` / ``v_col`` are optional (1, bk) per-token scales of such narrow
    tiles, applied to the logit / probability COLUMNS (q·(s·k)ᵀ = (q·kᵀ)·s),
    so the widening needs no lane-to-sublane relayout of the scale row.
    """
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (bq, bk)
    if k_col is not None:
        s = s * k_col

    # ---- the governing predicate (whilelt algebra; paper §2.3) ----
    qpos = (qoff_ref[b] + iq * bq
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    pred = kpos < kvlen_ref[b]                      # ragged KV tail: whilelt
    if causal:
        pred &= qpos >= kpos
    # dynamic sliding window (2**30 = "no window"): ONE kernel serves local
    # and global layers — the predicate, not the kernel, changes (SVE C2)
    pred &= kpos > (qpos - win_ref[0])

    s = jnp.where(pred, s, NEG_INF)

    m_prev = m_scr[:, :1]                           # (bq, 1)
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                 # <= 1; exp(-inf-(-inf)) avoided
    p = jnp.where(pred, jnp.exp(s - m_new), 0.0)    # zeroing predication
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

    pv = p if v_col is None else p * v_col
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = l_scr[:, :1]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0.0, out, 0.0)          # empty-predicate rows -> 0
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Paged variant: the page table drives the K/V BlockSpec index_map
# ---------------------------------------------------------------------------
#
# With scalar prefetch (PrefetchScalarGridSpec) the page table is available to
# the index_map itself, so the pipeline fetches physical page
# ``table[b, j]`` when the grid asks for lane b's logical block j — the SVE
# gather-load contract expressed at the block-fetch level: the kernel body is
# UNCHANGED from the dense path (same predicate algebra, same online softmax),
# only the address stream indirects through the index vector.

def _flash_kernel_paged(
    # scalar-prefetch operands (SMEM)
    table_ref, kvlen_ref, qoff_ref, win_ref,
    # blocked operands
    q_ref, k_ref, v_ref,
    # blocked output
    o_ref,
    # VMEM scratch
    m_scr, l_scr, acc_scr,
    *, bq: int, page_size: int, n_pages: int, causal: bool, scale: float,
):
    del table_ref                                  # consumed by the index_maps
    _flash_kernel(kvlen_ref, qoff_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, bq=bq, bk=page_size, n_kv=n_pages,
                  causal=causal, scale=scale)


def _flash_kernel_paged_quant(
    # scalar-prefetch operands (SMEM)
    table_ref, kvlen_ref, qoff_ref, win_ref,
    # blocked operands: narrow K/V page tiles + their per-slot scale rows
    q_ref, k_ref, v_ref, ks_ref, vs_ref,
    # blocked output
    o_ref,
    # VMEM scratch
    m_scr, l_scr, acc_scr,
    *, bq: int, page_size: int, n_pages: int, causal: bool, scale: float,
    group: int,
):
    """Quantized paged tile: the page's (Hkv, ps) scale slab arrives through
    the SAME table-driven index_map as the K/V page, the kernel picks its KV
    head's row, and the narrow elements widen in register (``q8 * scale``
    per token, folded into the logit / probability columns) around the
    unchanged softmax body — SVE §2.3.3's extending gather-load at the
    block-fetch level."""
    del table_ref                                  # consumed by the index_maps
    kvh = pl.program_id(1) // group
    _flash_tile(kvlen_ref, qoff_ref, win_ref, q_ref,
                k_ref[0, 0].astype(jnp.float32),
                v_ref[0, 0].astype(jnp.float32), o_ref,
                m_scr, l_scr, acc_scr, bq=bq, bk=page_size, n_kv=n_pages,
                causal=causal, scale=scale,
                k_col=ks_ref[pl.ds(kvh, 1), :].astype(jnp.float32),
                v_col=vs_ref[pl.ds(kvh, 1), :].astype(jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("bq", "causal", "scale", "interpret"))
def flash_attention_pallas_paged(
    q, k_pool, v_pool, page_table, kv_lens, q_offset, window,
    *, bq: int = 256, causal: bool = False,
    scale: float | None = None, interpret: bool | None = None,
    k_scale=None, v_scale=None,
):
    """q: (B, Hq, Sq, D) with Sq % bq == 0; k_pool/v_pool: (P, Hkv, ps, D);
    page_table: (B, n_pages) int32.  The KV grid axis walks LOGICAL pages;
    the BlockSpec index_map reads the prefetched page table to pick the
    PHYSICAL page, so block (b, j) fetches ``pool[table[b, j]]``.  The table
    arrives with out-of-strip (possibly stale) entries already clamped under
    the page-granular whilelt (ops._flash_paged), so the index_map never
    chases a freed id; the in-kernel predicate masks those blocks anyway.

    ``k_scale`` / ``v_scale``: ``(P, Hkv, ps)`` per-slot scale pools of a
    QUANTIZED cache; their (1, 1, ps) blocks ride the same table-driven
    index_map as whole-page ``(Hkv, ps)`` slabs (a legal (8, 128) tiling at
    any head count), and the kernel widens the narrow K/V in register."""
    bsz, hq, sq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    n_pages = page_table.shape[1]
    group = hq // hkv
    assert sq % bq == 0, (sq, bq)
    n_q = sq // bq
    scale = (d ** -0.5) if scale is None else scale
    quant = k_scale is not None

    kernel = functools.partial(
        _flash_kernel_paged, bq=bq, page_size=ps, n_pages=n_pages,
        causal=causal, scale=scale)
    if quant:
        kernel = functools.partial(
            _flash_kernel_paged_quant, bq=bq, page_size=ps, n_pages=n_pages,
            causal=causal, scale=scale, group=group)

    def q_map(b, h, i, j, table, kvl, qo, win):
        return (b, h, i, 0)

    def kv_map(b, h, i, j, table, kvl, qo, win):
        return (table[b, j], h // group, 0, 0)     # the gather: index vector

    def sc_map(b, h, i, j, table, kvl, qo, win):
        return (table[b, j], 0, 0)                 # scale slab: same walk

    in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, ps, d), kv_map),
        pl.BlockSpec((1, 1, ps, d), kv_map),
    ]
    operands = [q, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((None, hkv, ps), sc_map),
                     pl.BlockSpec((None, hkv, ps), sc_map)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                     # table, kv_lens, qoff, win
        grid=(bsz, hq, n_q, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(page_table.astype(jnp.int32), kv_lens, q_offset, window, *operands)


@functools.partial(
    jax.jit,
    static_argnames=("bq", "bk", "causal", "scale", "interpret"))
def flash_attention_pallas(
    q, k, v, kv_lens, q_offset, window,
    *, bq: int = 256, bk: int = 512, causal: bool = False,
    scale: float | None = None, interpret: bool | None = None,
):
    """q: (B, Hq, Sq, D) with Sq % bq == 0; k/v: (B, Hkv, Skv, D), Skv % bk == 0.

    kv_lens: (B,) int32 valid KV length per row; q_offset: (B,) int32 absolute
    position of q[:, :, 0] (decode against a cache).  The three scalars ride
    scalar prefetch into SMEM.  See ops.flash_attention for the padding/VL-
    selection wrapper.
    """
    bsz, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    n_q, n_kv = sq // bq, skv // bk
    scale = (d ** -0.5) if scale is None else scale

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=n_kv, causal=causal, scale=scale)

    def q_map(b, h, i, j, kvl, qo, win):
        return (b, h, i, 0)

    def kv_map(b, h, i, j, kvl, qo, win):
        return (b, h // group, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # kv_lens, q_offset, window
        grid=(bsz, hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),     # m (running max)
            pltpu.VMEM((bq, 128), jnp.float32),     # l (running denominator)
            pltpu.VMEM((bq, d), jnp.float32),       # acc (unnormalized output)
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(kv_lens, q_offset, window, q, k, v)
