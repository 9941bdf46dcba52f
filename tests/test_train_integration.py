"""End-to-end integration: loss decreases; microbatching is exact; elastic
checkpoint restore re-shards across meshes."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import SyntheticLM
from repro.models import ModelConfig
from repro.train.step import init_state, make_train_step

CFG = ModelConfig(name="it", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  param_dtype="float32", compute_dtype="float32")


def _batch(data, step, b=8):
    tokens, labels, lens = data.batch(step, b)
    return {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels),
            "lens": jnp.asarray(lens)}


def test_loss_decreases():
    state, _ = init_state(jax.random.PRNGKey(0), CFG)
    step = jax.jit(make_train_step(CFG, peak_lr=2e-3, warmup=5, total=30))
    data = SyntheticLM(CFG.vocab_size, 64, seed=0)
    losses = []
    for s in range(25):
        state, m = step(state, _batch(data, s))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.9, losses


def test_microbatched_grads_match_full_batch():
    """mb=4 accumulation == one full-batch step (same init, same data)."""
    data = SyntheticLM(CFG.vocab_size, 32, seed=1)
    batch = _batch(data, 0, b=8)
    s1, _ = init_state(jax.random.PRNGKey(2), CFG)
    s2 = jax.tree.map(lambda x: x, s1)
    full = jax.jit(make_train_step(CFG, microbatch=1))
    micro = jax.jit(make_train_step(CFG, microbatch=4))
    out1, m1 = full(s1, batch)
    out2, m2 = micro(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(out1["params"]),
                    jax.tree.leaves(out2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_elastic_restore_across_meshes():
    """Save unsharded -> restore onto a 4-device mesh with NamedShardings."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save_checkpoint, restore_checkpoint
from repro.launch.mesh import make_mesh

tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        "b": jnp.arange(8, dtype=jnp.float32)}
d = tempfile.mkdtemp()
save_checkpoint(d, 3, tree)

mesh = make_mesh((2, 2), ("data", "model"))
sh = {"w": NamedSharding(mesh, P("data", "model")),
      "b": NamedSharding(mesh, P("model"))}
like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
out, step = restore_checkpoint(d, like, shardings=sh)
assert step == 3
np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
assert out["w"].sharding.spec == P("data", "model")
print("ELASTIC-OK")
"""
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # tests run on the CPU backend
                            "JAX_PLATFORMS": "cpu",
                            "HOME": "/root"})
    assert "ELASTIC-OK" in r.stdout, r.stdout + r.stderr
