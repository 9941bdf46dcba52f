"""Operations and bytes a round needs, counted from the algorithm alone.

The count reads only the configuration file and the round's work as the
schedule replay gives it (``schedule.RoundWork``); it never reads HLO, so it
stays the same whatever implements attention or fuses what.  Per forward
pass (one decode step of all live lanes, or one prefill pass):

* every weight is read once, except the embedding table, of which only the
  rows of the pass's tokens are read;
* a decode lane at context ``c`` (its new token included) reads the K/V of
  ``c`` tokens and writes one token's K/V; a prefill row of ``n`` tokens at
  offset ``p`` reads the K/V of its ``p`` earlier tokens and writes ``n``;
* each token costs ``2 * N`` operations over the layers' matrices (``N``
  their weights), plus ``4 * heads * head_dim`` per key it attends to, plus
  ``2 * hidden * vocab`` where the pass computes its logits: every decode
  lane, and the last token of each prefill row.

Padding rows and lanes that are not live are the program's waste: not
counted.  Activations, norms and sampling are left out: they are small
beside the weights and the K/V at these sizes.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    dtype_bytes: int

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        d, h = conf["hidden_size"], conf["num_attention_heads"]
        return cls(conf["num_hidden_layers"], d, h,
                   conf["num_key_value_heads"], d // h,
                   conf["intermediate_size"], conf["vocab_size"],
                   {"bfloat16": 2, "float16": 2, "float32": 4}[conf["dtype"]])

    @property
    def layer_matrix_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return (2 * self.hidden * q + 2 * self.hidden * kv
                + 3 * self.hidden * self.ffn)

    @property
    def kv_bytes_per_token(self) -> int:
        return (2 * self.layers * self.kv_heads * self.head_dim
                * self.dtype_bytes)

    @property
    def pass_weight_bytes(self) -> int:
        """Weights a forward pass reads whole: every layer, the final norm
        and the output head (norms of the layers included)."""
        per_layer = self.layer_matrix_params + 4 * self.hidden
        return self.dtype_bytes * (self.layers * per_layer + 2 * self.hidden
                                   + self.hidden * self.vocab)


def decode_step(dims: Dims, live: int, ctx_sum: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step of ``live`` lanes whose
    contexts sum to ``ctx_sum`` tokens."""
    attn = 4 * dims.heads * dims.head_dim * dims.layers
    flops = (live * (2 * dims.layers * dims.layer_matrix_params
                     + 2 * dims.hidden * dims.vocab)
             + attn * ctx_sum)
    nbytes = (dims.pass_weight_bytes
              + live * dims.hidden * dims.dtype_bytes
              + (ctx_sum + live) * dims.kv_bytes_per_token)
    return float(flops), float(nbytes)


def prefill_pass(dims: Dims, rows) -> tuple[float, float]:
    """(operations, bytes) of one prefill pass over ``rows`` of
    ``(tokens, offset)``."""
    attn = 4 * dims.heads * dims.head_dim * dims.layers
    flops = nbytes = 0
    for n, p in rows:
        keys = n * p + n * (n + 1) // 2        # token i attends to p + i keys
        flops += (2 * dims.layers * dims.layer_matrix_params * n
                  + attn * keys + 2 * dims.hidden * dims.vocab)
        nbytes += ((p + n) * dims.kv_bytes_per_token
                   + n * dims.hidden * dims.dtype_bytes)
    if rows:
        nbytes += dims.pass_weight_bytes
    return float(flops), float(nbytes)


def round_counts(dims: Dims, rw) -> tuple[float, float]:
    """(operations, bytes) of a whole round."""
    flops = nbytes = 0.0
    for rows in rw.prefill:
        f, b = prefill_pass(dims, rows)
        flops, nbytes = flops + f, nbytes + b
    for live, ctx in rw.decode:
        f, b = decode_step(dims, live, ctx)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
