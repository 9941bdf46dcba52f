"""The model a cell serves: its configuration file turned into the program's
``ModelConfig``, and its weights made from the seed.

A configuration file keeps the published config's own key names
(``hidden_size``, ``num_hidden_layers``, ...) and values, as run.  ``arch``
names the program's preset for the family; every size of the file is set
over it, so the cell runs what the file says whatever the preset holds.
"""

from __future__ import annotations

# published key -> the program's ModelConfig field
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "partial_rotary_factor": "partial_rotary_factor",
    "rope_theta": "rope_theta",
    "layer_norm_eps": "norm_eps",
    "use_parallel_residual": "parallel_block",
    "tie_word_embeddings": "tie_embeddings",
}


def program_config(conf: dict):
    """The program's ``ModelConfig`` for configuration file ``conf``."""
    from repro.configs import get_config

    if conf["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {conf['hidden_act']!r}: the program's "
                         "StableLM MLP is the gated SiLU one")
    for key in ("use_qkv_bias", "qk_layernorm"):
        if conf.get(key):
            raise ValueError(f"{key}=true: the program has no such path; "
                             "the file must state the config as run")
    over = {field: conf[key] for key, field in PROGRAM_KEYS.items()}
    over["head_dim"] = conf["hidden_size"] // conf["num_attention_heads"]
    over.update(norm="layernorm", activation="swiglu", use_bias=False,
                qk_norm=False, param_dtype=conf["dtype"],
                compute_dtype=conf["dtype"])
    return get_config(conf["arch"]).replace(**over)


def weight_shapes(conf: dict):
    """The served weights' layout, as the dense family lays out a StableLM
    checkpoint: stacked layers, bf16 leaves.  The reference reads weights by
    these names; ``check_layout`` holds the program to the same layout."""
    import jax
    import jax.numpy as jnp

    d, f = conf["hidden_size"], conf["intermediate_size"]
    v, n = conf["vocab_size"], conf["num_hidden_layers"]
    hd = d // conf["num_attention_heads"]
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    dt = jnp.dtype(conf["dtype"])

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    def norm(*lead):
        return {"bias": sds(*lead, d), "scale": sds(*lead, d)}

    block = {"attn": {"wq": sds(n, d, q), "wk": sds(n, d, kv),
                      "wv": sds(n, d, kv), "wo": sds(n, q, d)},
             "ln1": norm(n),
             "mlp": {"w_gate": sds(n, d, f), "w_up": sds(n, d, f),
                     "w_down": sds(n, f, d)}}
    if not conf["use_parallel_residual"]:
        block["ln2"] = norm(n)
    return {"blocks": block,
            "embed": {"tok": sds(v, d), "unembed": sds(d, v)},
            "final_norm": norm()}


def check_layout(cfg, shapes):
    """Raise unless the program's parameters have exactly ``shapes``."""
    import jax
    from repro.models import get_model

    model = get_model(cfg)
    prog = jax.eval_shape(lambda k: model.init(k, cfg)[0],
                          jax.random.PRNGKey(0))
    a = jax.tree_util.tree_flatten_with_path(prog)
    b = jax.tree_util.tree_flatten_with_path(shapes)
    if [(p, x.shape, x.dtype) for p, x in a[0]] != \
            [(p, x.shape, x.dtype) for p, x in b[0]]:
        raise ValueError("the program's parameter layout is not the one the "
                         "benchmark's weights and reference use")


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def weight_init_fn(shapes):
    """A jitted ``key -> weights`` that makes every leaf of ``shapes`` on the
    device, in one call, in the dtype it is served in.

    Matrices are normal with standard deviation ``fan_in ** -0.5`` (the
    embedding table ``hidden_size ** -0.5``), so logits come out at about
    unit scale; norm scales are ``1 + 0.1 * normal`` and biases
    ``0.1 * normal``, so the check also sees the norms' parameters.  Leaf
    ``i`` of the tree, in flattening order, draws from ``fold_in(key, i)``.
    """
    import jax
    import jax.numpy as jnp

    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, sds) in enumerate(flat):
            z = jax.random.normal(jax.random.fold_in(key, i), sds.shape,
                                  jnp.float32)
            name = _leaf_name(path)
            if name == "scale":
                x = 1.0 + 0.1 * z
            elif name == "bias":
                x = 0.1 * z
            elif name == "tok":
                x = z * sds.shape[-1] ** -0.5
            else:
                x = z * sds.shape[-2] ** -0.5
            out.append(x.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)
