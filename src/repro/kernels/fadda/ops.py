"""Public fadda op: VL-agnostic padding wrapper."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import vla

from .kernel import fadda_pallas


def fadda(x, n=None, *, block: int = 512):
    """Strictly-ordered f32 accumulation of x[:n] (paper §2.4)."""
    length = x.shape[0]
    n = length if n is None else n
    padded = vla.pad_to_vl(length, block)
    if padded != length:
        x = jnp.pad(x, (0, padded - length))
    return fadda_pallas(x.astype(jnp.float32), n, block=block)
