"""The fixed names a profile of a served round is read by.

On the device, the serve programs wrap each part of the model in a
``jax.named_scope`` from ``PARTS``, and each phase of a fused round in one
from ``PHASES``.  XLA keeps the scope path in every instruction's
``op_name`` metadata (``jit(_fused_step_impl)/jit(main)/decode/while/body/
attn_core/...``), so a profiler trace's operations can be charged to a
(phase, part) through the compiled program's text (``scope_of``).

On the host, ``SPANS`` are the serve loop's ``Obs.span`` names; with
``Obs(xla_annotations=True)`` each also lands in a profiler trace as a
``jax.profiler.TraceAnnotation``, on the same clock as the device's
operations.
"""

from __future__ import annotations

import jax

__all__ = ["PHASES", "PARTS", "SCOPES", "SPANS", "scope", "scope_of"]

# a fused round: admission and chunk prefill, then the decode burst
PHASES = ("prefill", "decode")
# the model's parts: q/k/v and output projections (with the block's input
# norm and rope), attention over the cache (page gathers included), every
# K/V store (the decode step's page scatter, and each layer's pools taken
# out of the stacked pools and written back; the admission's page install
# and prefix seed), the MLP (with its norm), the final norm and unembed, the
# embedding lookup, and sampling
PARTS = ("attn_proj", "attn_core", "kv_write", "mlp", "logits", "embed",
         "sample")
SCOPES = PHASES + PARTS

# the serve loop's spans (scheduler rounds and their phases, and the engine's
# one-shot prefill/decode seams)
SPANS = ("round", "plan", "admit", "dispatch", "harvest", "sync", "compact",
         "swap_in", "swap_out", "preempt", "resume", "prefill_chunk", "burst",
         "prefill", "decode")


def scope(name: str):
    """``jax.named_scope(name)`` for a name of ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a serve scope ({SCOPES})")
    return jax.named_scope(name)


def scope_of(op_name: str) -> tuple:
    """``(phase, part)`` of an ``op_name`` path: its outermost phase scope
    and innermost part scope, each None where the path has none."""
    parts = op_name.split("/")
    phase = next((p for p in parts if p in PHASES), None)
    part = next((p for p in reversed(parts) if p in PARTS), None)
    return phase, part
