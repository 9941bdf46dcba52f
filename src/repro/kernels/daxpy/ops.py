"""Public daxpy op: VL-agnostic strip-mined call into the Pallas kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import vla

from .kernel import daxpy_pallas


def daxpy(x, y, a, n=None, *, block: int | None = None):
    """Vector-length-agnostic daxpy: pads to the chosen VL, runs the
    predicated kernel, returns the first len(x) elements.  ``n`` defaults to
    the full length; any n <= len(x) exercises the predicated tail."""
    length = x.shape[0]
    n = length if n is None else n
    if block is None:
        block = vla.choose_vl(length, x.dtype, operands=3).block
    padded = vla.pad_to_vl(length, block)
    if padded != length:
        x = jnp.pad(x, (0, padded - length))
        y = jnp.pad(y, (0, padded - length))
    out = daxpy_pallas(x, y, a, n, block=block)
    return out[:length]
