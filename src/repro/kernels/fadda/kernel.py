"""Strictly-ordered floating-point accumulation (SVE ``fadda``) for TPU.

The paper's §2.4/§3.3: vectorizing a reduction must not change FP results
when ordering is semantically load-bearing.  The hardware instruction is
serial with cost proportional to VL; this kernel mirrors that honestly — a
sequential fori_loop over lanes inside each VL tile, with the scalar
accumulator carried across tiles in SMEM.  It exists for *correctness-
critical* reductions (loss auditing, deterministic eval), not throughput;
``core.reductions.pairwise_sum`` is the fast deterministic alternative.

The governing predicate (whilelt against n) zeroes inactive lanes, so the
padded tail never perturbs the accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _fadda_kernel(n_ref, x_ref, o_ref, acc_scr, *, block: int, n_tiles: int):
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        acc_scr[0, 0] = jnp.float32(0.0)

    i = pid * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    p = i < n_ref[0]                                   # whilelt(i, n)
    xm = jnp.where(p, x_ref[...].astype(jnp.float32), 0.0)

    def body(j, acc):
        return acc + xm[0, j]                          # strict element order

    acc_scr[0, 0] = jax.lax.fori_loop(0, block, body, acc_scr[0, 0])

    @pl.when(pid == n_tiles - 1)
    def _emit():
        o_ref[0, 0] = acc_scr[0, 0]


@functools.partial(jax.jit, static_argnames=("block",))
def fadda_pallas(x, n, *, block: int = 512):
    padded = x.shape[0]
    assert padded % block == 0
    n_tiles = padded // block
    kernel = functools.partial(_fadda_kernel, block=block, n_tiles=n_tiles)
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
        interpret=resolve_interpret(),
    )(jnp.asarray([n], jnp.int32), x.reshape(1, padded))
    return out[0, 0]
