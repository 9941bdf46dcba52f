"""The plain reference: a StableLM forward pass in float32 at the highest
matmul precision, written from the published architecture and the
configuration file alone.  It imports nothing of the program under test.

Per layer: LayerNorm (with bias) before attention; q/k/v projections; a
GPT-NeoX rotary embedding on the first ``partial_rotary_factor`` of each
head's dimensions (the two halves of that part rotated against each other);
causal softmax attention with ``1/sqrt(head_dim)`` scaling, each key/value
head serving ``heads / kv_heads`` consecutive query heads; the output
projection; a gated SiLU MLP behind its own LayerNorm, or, with
``use_parallel_residual``, beside attention on the same normed input.  Then
the final LayerNorm and the output head.

The weights are the ones the benchmark served, made again from the seed by
``model.weight_init_fn`` and read leaf by leaf, one layer at a time, so the
pass fits next to nothing else on the chip.  ``quantize`` gives the control:
the same pass with every matrix rounded to float8 (e4m3) with one scale per
output column, the precision step below the bf16 the configuration serves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _rope(x, theta, frac):
    """x: (H, T, D) at positions 0..T-1."""
    d = x.shape[-1]
    rd = int(d * frac)
    rd -= rd % 2
    half = rd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rd:]], axis=-1)


def _matrix(w, quantize):
    w = w.astype(jnp.float32)
    if not quantize:
        return w
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("conf", "quantize"))
def _layer(x, lp, *, conf, quantize):
    conf = dict(conf)
    t = x.shape[0]
    h, hk = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["hidden_size"] // h
    eps = conf["layer_norm_eps"]
    a = lp["attn"]
    xn = _ln(x, lp["ln1"], eps)
    q = (xn @ _matrix(a["wq"], quantize)).reshape(t, h, hd).transpose(1, 0, 2)
    k = (xn @ _matrix(a["wk"], quantize)).reshape(t, hk, hd).transpose(1, 0, 2)
    v = (xn @ _matrix(a["wv"], quantize)).reshape(t, hk, hd).transpose(1, 0, 2)
    q = _rope(q, conf["rope_theta"], conf["partial_rotary_factor"])
    k = _rope(k, conf["rope_theta"], conf["partial_rotary_factor"])
    k = jnp.repeat(k, h // hk, axis=0)
    v = jnp.repeat(v, h // hk, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v)
    attn = o.transpose(1, 0, 2).reshape(t, h * hd) @ _matrix(a["wo"], quantize)
    m = lp["mlp"]

    def mlp(y):
        g = jax.nn.silu(y @ _matrix(m["w_gate"], quantize))
        return (g * (y @ _matrix(m["w_up"], quantize))) @ _matrix(
            m["w_down"], quantize)

    if conf["use_parallel_residual"]:
        return x + attn + mlp(xn)
    x = x + attn
    return x + mlp(_ln(x, lp["ln2"], eps))


@functools.partial(jax.jit, static_argnames=("conf", "quantize"))
def _head(x, params, *, conf, quantize):
    conf = dict(conf)
    x = _ln(x, params["final_norm"], conf["layer_norm_eps"])
    return x @ _matrix(params["embed"]["unembed"], quantize)


def logits(conf: dict, params, tokens: np.ndarray,
           quantize: bool = False) -> jax.Array:
    """(T, vocab) float32 logits of the sequence ``tokens``."""
    key = tuple(sorted((k, v) for k, v in conf.items()
                       if isinstance(v, (int, float, bool, str))))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][jnp.asarray(tokens)].astype(jnp.float32)
        for i in range(conf["num_hidden_layers"]):
            lp = jax.tree.map(lambda w: w[i], params["blocks"])
            x = _layer(x, lp, conf=key, quantize=quantize)
        return _head(x, params, conf=key, quantize=quantize)


def served_gap(ref: np.ndarray, served: np.ndarray) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position (``ref`` rows predict ``served``)."""
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return float((best - got).max())
