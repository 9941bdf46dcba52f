"""Public MoE dispatch ops: position kernel + gather/scatter table builder.

The gather/scatter (SVE C8) happens here in XLA-land so pjit can turn it into
all-to-alls under expert parallelism; the Pallas kernel supplies the serial
counter ranks.  Overflowed assignments form the cleared lanes of the dispatch
partition (FFR analogue, see ref.py).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import vla

from .kernel import moe_positions_pallas
from .ref import moe_positions_ref


def moe_positions(expert_ids, n_experts: int, *, tile: int = 512,
                  impl: str = "kernel"):
    """Rank of each (token, slot) assignment within its expert + totals."""
    t, k = expert_ids.shape
    if impl == "xla":
        return moe_positions_ref(expert_ids, n_experts)
    t_pad = vla.pad_to_vl(t, tile)
    ids = expert_ids
    if t_pad != t:
        ids = jnp.pad(ids, ((0, t_pad - t), (0, 0)), constant_values=-1)
    pos, counts = moe_positions_pallas(ids, n_experts=n_experts, tile=tile)
    return pos[:t], counts


def build_dispatch(expert_ids, gates, n_experts: int, capacity: int,
                   *, impl: str = "kernel"):
    """Build the dispatch tables for a capacity-C MoE layer.

    Returns dict with:
      token_table: (E, C) int32 — source token for each expert slot, or T
                   (one-past-last, a zero row in the padded activations) for
                   empty slots;
      slot_of:     (T, K) int32 — e*C + pos for kept assignments, else E*C
                   (points at a zero row of the flattened expert outputs);
      keep:        (T, K) bool — the dispatch partition (pos < capacity);
      gates:       (T, K) f32  — combine weights, zeroed on dropped lanes;
      counts:      (E,) int32  — raw demand per expert (for aux losses);
      dropped:     ()  int32   — number of dropped assignments.
    """
    t, k = expert_ids.shape
    pos, counts = moe_positions(expert_ids, n_experts, impl=impl)
    valid = (expert_ids >= 0) & (expert_ids < n_experts)
    keep = valid & (pos < capacity)

    # scatter (token -> expert slot); dropped lanes go to the overflow slot
    flat_slot = jnp.where(keep, expert_ids * capacity + pos, n_experts * capacity)
    token_ids = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None], (t, k))
    token_table = jnp.full((n_experts * capacity + 1,), t, jnp.int32)
    token_table = token_table.at[flat_slot.reshape(-1)].set(
        token_ids.reshape(-1), mode="drop")
    token_table = token_table[:-1].reshape(n_experts, capacity)

    gates_kept = jnp.where(keep, gates, 0.0).astype(gates.dtype)
    slot_of = jnp.where(keep, expert_ids * capacity + pos, n_experts * capacity)
    return dict(
        token_table=token_table,
        slot_of=slot_of.astype(jnp.int32),
        keep=keep,
        gates=gates_kept,
        counts=counts,
        dropped=jnp.sum((valid & ~keep).astype(jnp.int32)),
    )
