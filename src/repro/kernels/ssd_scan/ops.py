"""Public SSD ops: padding/predication wrapper + single-token decode step."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import vla

from .kernel import ssd_scan_pallas
from .ref import ssd_chunked_ref, ssd_ref  # noqa: F401  (oracle re-export)


def ssd_scan(x, dt, A, B, C, D=None, *, seq_lens=None, h0=None,
             chunk: int = 128, impl: str = "kernel"):
    """Chunk-size-agnostic SSD scan.

    x: (Bz, S, H, P); dt: (Bz, S, H) (positive; e.g. softplus upstream);
    A: (H,) negative; B, C: (Bz, S, N); D: (H,) skip or None;
    seq_lens: (Bz,) ragged valid lengths — implemented by *predicating dt to
    zero* past the end (SVE zeroing predication; state then carries unchanged
    and padded rows contribute nothing);
    h0: (Bz, H, P, N) initial state or None (zeros) — chunked-prefill resume:
    scanning a suffix from the carried state equals scanning the whole
    sequence bit-for-bit when the resume offset is a multiple of ``chunk``
    (the chunk_step sequence is then identical; padded tail steps are exact
    identities because dt=0 makes decay exp(0)=1 and the update exactly 0).

    Returns (y, h_final): y (Bz, S, H, P), h_final (Bz, H, P, N) f32.
    """
    bz, s, h, p = x.shape
    if seq_lens is not None:
        pos = jnp.arange(s, dtype=jnp.int32)[None, :, None]
        dt = jnp.where(pos < jnp.asarray(seq_lens, jnp.int32)[:, None, None], dt, 0.0)

    s_p = vla.pad_to_vl(s, chunk)
    if s_p != s:
        pad = [(0, 0), (0, s_p - s)]
        x = jnp.pad(x, pad + [(0, 0), (0, 0)])
        dt = jnp.pad(dt, pad + [(0, 0)])          # dt=0 => inert lanes
        B = jnp.pad(B, pad + [(0, 0)])
        C = jnp.pad(C, pad + [(0, 0)])

    if impl == "xla":
        y, hT = ssd_chunked_ref(x, dt, A, B, C, None, h0=h0, chunk=chunk)
    else:
        if h0 is None:
            h0 = jnp.zeros((bz, h, p, B.shape[-1]), jnp.float32)
        y, hT = ssd_scan_pallas(x, dt, A, B, C, h0.astype(jnp.float32),
                                chunk=chunk)

    y = y[:, :s]
    if D is not None:
        y = (y.astype(jnp.float32)
             + D.astype(jnp.float32)[None, None, :, None]
             * x[:, :s].astype(jnp.float32)).astype(y.dtype)
    return y, hT


def ssd_decode_step(x_t, dt_t, A, B_t, C_t, h, D=None):
    """One-token SSD recurrence for serving.

    x_t: (Bz, H, P); dt_t: (Bz, H); B_t, C_t: (Bz, N); h: (Bz, H, P, N).
    Returns (y_t, h_new).  This is the constant-memory long-context decode
    path (long_500k cells for SSM/hybrid archs).
    """
    f32 = jnp.float32
    decay = jnp.exp(dt_t.astype(f32) * A.astype(f32)[None, :])       # (Bz,H)
    upd = (dt_t.astype(f32)[..., None, None]
           * x_t.astype(f32)[..., :, None] * B_t.astype(f32)[:, None, None, :])
    h_new = decay[..., None, None] * h.astype(f32) + upd
    y = jnp.einsum("bhpn,bn->bhp", h_new, C_t.astype(f32))
    if D is not None:
        y = y + D.astype(f32)[None, :, None] * x_t.astype(f32)
    return y.astype(x_t.dtype), h_new
