"""MoE dispatch position-assignment kernel (Pallas TPU).

The serialized heart of capacity-based MoE routing is a running per-expert
counter: assignment (t, k) lands at position ``count_so_far[expert]`` within
its expert's buffer.  This kernel strip-mines tokens into VL-sized tiles
(grid axis sequential) and carries the (1, E) counter vector in VMEM scratch —
the cluster-scale cousin of the paper's ``incp`` (advance induction by the
predicate popcount).  Within a tile the ranks come from a one-hot matrix
cumsum, i.e. vectorized; across tiles the carry is the loop-carried scalar
state, exactly the split of paper Fig. 6 (vectorizable body + serial carry).

Capacity is NOT applied here — the kernel reports raw ranks; ops.py derives
the keep-predicate ``pos < capacity`` (the FFR partition) so callers can also
observe overflow statistics (aux losses need them).

Tile geometry: tokens_per_tile x E one-hot in int32; for E=64..128 and tile
512 that is a 512x128 i32 buffer = 256 KiB — VMEM-friendly, lane-aligned.

Off the chip path: Mosaic has no lowering for ``cumsum``, so this kernel
runs only interpreted on the CPU, and no model calls it (``models/moe.py``
routes through the XLA reference).  A dropless expert layer is to replace
capacity dispatch altogether (ROADMAP, Reach item 4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import vla
from repro.kernels import resolve_interpret


def _dispatch_kernel(ids_ref, pos_ref, counts_ref, counts_scr,
                     *, tile: int, k: int, e_pad: int, n_tiles: int):
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        counts_scr[...] = jnp.zeros_like(counts_scr[...])

    ids = ids_ref[...].reshape(tile * k, 1)                     # flattened order
    lanes = jax.lax.broadcasted_iota(jnp.int32, (tile * k, e_pad), 1)
    onehot = (ids == lanes).astype(jnp.int32)                   # invalid ids -> 0 row
    carry = counts_scr[0:1, :]                                  # (1, E)
    excl = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.sum((excl + carry) * onehot, axis=1)              # rank per assignment
    pos_ref[...] = pos.reshape(tile, k)
    counts_scr[0:1, :] = carry + jnp.sum(onehot, axis=0, keepdims=True)

    @pl.when(pid == n_tiles - 1)
    def _emit():
        counts_ref[...] = counts_scr[0:1, :]


@functools.partial(jax.jit, static_argnames=("n_experts", "tile"))
def moe_positions_pallas(expert_ids, *, n_experts: int, tile: int = 512):
    """expert_ids: (T, K) int32; T % tile == 0 (ops.py pads with -1).
    Returns pos (T, K) int32 and counts (E,) int32."""
    t, k = expert_ids.shape
    assert t % tile == 0, (t, tile)
    e_pad = vla.pad_to_vl(n_experts, vla.LANE)
    n_tiles = t // tile
    kernel = functools.partial(_dispatch_kernel, tile=tile, k=k, e_pad=e_pad,
                               n_tiles=n_tiles)
    pos, counts = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile, k), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tile, k), lambda i: (i, 0)),
            pl.BlockSpec((1, e_pad), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, k), jnp.int32),
            jax.ShapeDtypeStruct((1, e_pad), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((8, e_pad), jnp.int32)],
        interpret=resolve_interpret(),
    )(expert_ids)
    return pos, counts[0, :n_experts]
