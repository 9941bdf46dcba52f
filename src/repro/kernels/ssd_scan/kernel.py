"""Mamba2 SSD chunked scan for TPU (Pallas).

VLA mapping (DESIGN.md C1): the chunk length Q is this kernel's vector
length.  One kernel source runs at any Q; results are Q-invariant (tested),
exactly as SVE binaries are VL-invariant.  Ragged sequence tails are handled
by *predicating dt to zero* (decay=exp(0)=1, zero input, zero output
contribution) — predication, not shape specialization.

Blocking: grid (B, H, S/Q) with the chunk axis innermost and sequential; the
(P, N) state lives in VMEM scratch across chunks.  Per-chunk working set for
Q=128, P=64, N=128 in f32: x (Q,P) 32 KiB + B,C (Q,N) 64 KiB + L (Q,Q) 64 KiB
+ state (P,N) 32 KiB — far inside the v5e VMEM budget; matmul dims are
MXU-aligned multiples of 64/128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssd_kernel(a_head_ref,                       # (H,) SMEM: A per head
                x_ref, dt_ref, b_ref, c_ref,      # blocked inputs
                h0_ref,                           # (P, N) initial state
                y_ref, hout_ref,                  # blocked outputs
                h_scr,                            # (P, N) VMEM state
                *, q: int, n_chunks: int):
    h = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    x = x_ref[...].astype(jnp.float32)             # (Q, P)
    dt = dt_ref[...].astype(jnp.float32)           # (1, Q) row
    bm = b_ref[...].astype(jnp.float32)            # (Q, N)
    cm = c_ref[...].astype(jnp.float32)            # (Q, N)
    A = a_head_ref[h]

    iq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = iq >= jq                                 # causal predicate
    diag = iq == jq

    def col(row):
        # exact (1, Q) -> (Q, 1) transpose: one nonzero term per sum
        return jnp.sum(jnp.where(diag, jnp.broadcast_to(row, (q, q)), 0.0),
                       axis=1, keepdims=True)

    a = dt * A                                     # (1, Q) log-decay, <= 0
    # inclusive prefix sum as a predicated row reduction (whilelt k <= i):
    # Mosaic has no cumsum, and this is one (Q, Q) VPU pass
    cum = jnp.sum(jnp.where(tri, jnp.broadcast_to(a, (q, q)), 0.0),
                  axis=1, keepdims=True)           # (Q, 1)
    cum_row = jnp.sum(jnp.where(diag, jnp.broadcast_to(cum, (q, q)), 0.0),
                      axis=0, keepdims=True)       # (1, Q), exact transpose
    last = jnp.sum(jnp.where(jq[:1] == q - 1, cum_row, 0.0),
                   axis=1, keepdims=True)          # (1, 1) = cum[Q-1]
    # decay matrix L[i,j] = exp(cum_i - cum_j) for i>=j else 0
    L = jnp.where(tri, jnp.exp(cum - cum_row), 0.0)

    # intra-chunk (attention-like) term
    att = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (Q, Q)
    att = att * L * dt
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (Q, P)

    # inter-chunk term: y += exp(cum_i) * C_i @ h_prev^T
    hprev = h_scr[...]                             # (P, N)
    y_inter = jax.lax.dot_general(cm, hprev, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (Q, P)
    y = y + y_inter * jnp.exp(cum)

    # state update: h = exp(cum_Q) h_prev + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T
    w = jnp.exp(last - cum) * col(dt)              # (Q, 1)
    upd = jax.lax.dot_general(x * w, bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    h_scr[...] = jnp.exp(last) * hprev + upd

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        hout_ref[...] = h_scr[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, B, C, h0=None, *, chunk: int = 128,
                    interpret: bool | None = None):
    """x: (Bz, S, H, P); dt: (Bz, S, H); A: (H,); B, C: (Bz, S, N);
    h0: (Bz, H, P, N) f32 initial state or None (zeros) — chunked-prefill
    resume seeds the VMEM state scratch at chunk 0 instead of zeroing it.
    S % chunk == 0 (ops.py pads + predicates dt).  Returns (y, h_final).

    Heads are squeezed out of every block, so the tiled dims are always
    (chunk, P | N) or (P, N): x and y move head-major as (Bz, H, S, P) and
    dt as (Bz, H, 1, S) rows."""
    bz, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if h0 is None:
        h0 = jnp.zeros((bz, h, p, n), jnp.float32)
    x_h = jnp.transpose(x, (0, 2, 1, 3))
    dt_rows = jnp.transpose(dt, (0, 2, 1))[:, :, None, :]

    kernel = functools.partial(_ssd_kernel, q=chunk, n_chunks=nc)
    grid = (bz, h, nc)
    sq = None                                      # squeezed block dim
    y, hout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                   # A (H,)
            pl.BlockSpec((sq, sq, chunk, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((sq, sq, 1, chunk), lambda b, hh, c: (b, hh, 0, c)),
            pl.BlockSpec((sq, chunk, n), lambda b, hh, c: (b, c, 0)),
            pl.BlockSpec((sq, chunk, n), lambda b, hh, c: (b, c, 0)),
            pl.BlockSpec((sq, sq, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sq, sq, chunk, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((sq, sq, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x_h.shape, x.dtype),
            jax.ShapeDtypeStruct((bz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(A.astype(jnp.float32), x_h, dt_rows, B, C, h0)
    return jnp.transpose(y, (0, 2, 1, 3)), hout
