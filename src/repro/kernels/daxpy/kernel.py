"""Daxpy — the paper's Fig. 2 kernel, as a predicated VLA Pallas kernel.

``y[i] = a*x[i] + y[i]`` for i < n, where n need not divide the block size.
The tail is handled exactly the way SVE's ``whilelt`` handles it: the kernel
computes the governing predicate from the scalar bound and merges (``/m``)
only the active lanes — one kernel source for every (n, VL) combination.

TPU mapping: VL = block elements (sublane x lane tile); the grid strip-mines
the array; `i` below is the induction variable the `incd` of Fig. 2c advances.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _daxpy_kernel(n_ref, a_ref, x_ref, y_ref, o_ref, *, block: int):
    pid = pl.program_id(0)
    # whilelt(i, n): governing predicate for this strip of the loop
    i = pid * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    p = i < n_ref[0]
    a = a_ref[0]
    fused = a * x_ref[...] + y_ref[...]          # fmla z2, p0/m, z1, z0
    o_ref[...] = jnp.where(p, fused, y_ref[...])  # /m merging predication


@functools.partial(jax.jit, static_argnames=("block",))
def daxpy_pallas(x, y, a, n, *, block: int = 1024):
    """x, y: (padded_len,) arrays; a: scalar; n: active element count."""
    padded = x.shape[0]
    assert padded % block == 0, (padded, block)
    grid = (padded // block,)
    kernel = functools.partial(_daxpy_kernel, block=block)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),           # n (scalar prefetch-ish)
            pl.BlockSpec(memory_space=pl.ANY),           # a
            pl.BlockSpec((1, block), lambda i: (0, i)),  # x strip in VMEM
            pl.BlockSpec((1, block), lambda i: (0, i)),  # y strip in VMEM
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded), x.dtype),
        interpret=resolve_interpret(),
    )(
        jnp.asarray([n], jnp.int32),
        jnp.asarray([a], x.dtype),
        x.reshape(1, padded),
        y.reshape(1, padded),
    ).reshape(padded)
