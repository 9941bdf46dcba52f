"""A round's operations and bytes split between the model's weight matmuls
and its attention, and each part's least time over its device time.

The split is of ``work.round_counts`` itself, so the two parts sum to it
exactly:

* ``matmul``: ``2 * N`` operations per token over the layers' matrices, and
  ``2 * hidden * vocab`` per row of logits; every weight read once (norms
  included) and the embedding rows of the pass's tokens;
* ``attention``: ``4 * heads * head_dim`` per key a token attends to; the
  K/V read and written.

On the device, ``matmul`` is the time of the ``attn_proj``, ``mlp``,
``logits`` and ``embed`` scopes, ``attention`` that of ``attn_core`` and
``kv_write`` (``repro.obs.scopes``).
"""

from __future__ import annotations

from . import work as W

DEVICE_PARTS = {"matmul": ("attn_proj", "mlp", "logits", "embed"),
                "attention": ("attn_core", "kv_write")}


def _decode_step(dims: W.Dims, live: int, ctx_sum: int) -> dict:
    attn = 4 * dims.heads * dims.head_dim * dims.layers
    return {
        "matmul": (live * (2 * dims.layers * dims.layer_matrix_params
                           + 2 * dims.hidden * dims.vocab),
                   dims.pass_weight_bytes
                   + live * dims.hidden * dims.dtype_bytes),
        "attention": (attn * ctx_sum,
                      (ctx_sum + live) * dims.kv_bytes_per_token)}


def _prefill_pass(dims: W.Dims, rows) -> dict:
    attn = 4 * dims.heads * dims.head_dim * dims.layers
    mm_f = mm_b = at_f = at_b = 0
    for n, p in rows:
        keys = n * p + n * (n + 1) // 2        # token i attends to p + i keys
        mm_f += (2 * dims.layers * dims.layer_matrix_params * n
                 + 2 * dims.hidden * dims.vocab)
        mm_b += n * dims.hidden * dims.dtype_bytes
        at_f += attn * keys
        at_b += (p + n) * dims.kv_bytes_per_token
    if rows:
        mm_b += dims.pass_weight_bytes
    return {"matmul": (mm_f, mm_b), "attention": (at_f, at_b)}


def round_parts(dims: W.Dims, rw) -> dict:
    """``{part: (operations, bytes)}`` of a whole round, for ``matmul`` and
    ``attention``."""
    out = {"matmul": [0.0, 0.0], "attention": [0.0, 0.0]}
    passes = ([_prefill_pass(dims, rows) for rows in rw.prefill]
              + [_decode_step(dims, live, ctx) for live, ctx in rw.decode])
    for counts in passes:
        for part, (f, b) in counts.items():
            out[part][0] += f
            out[part][1] += b
    return {part: (float(f), float(b)) for part, (f, b) in out.items()}


def roofline_shares(dims: W.Dims, work: list, peak: dict,
                    part_s: dict) -> dict:
    """``{part: %}``: the least time of the rounds ``work`` for the part over
    the device time of its scopes (``part_s``: seconds per scope), or None
    where its scopes took no time."""
    out = {}
    for part, scopes in DEVICE_PARTS.items():
        least = sum(W.least_seconds(*round_parts(dims, w)[part], peak)
                    for w in work)
        t = sum(part_s.get(s, 0.0) for s in scopes)
        out[part] = 100.0 * least / t if t > 0 else None
    return out
