"""Cluster-scale collectives on a multi-device CPU submesh (subprocess so the
forced device count never leaks into other tests)."""

import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
from repro.dist import collectives as C
from repro.dist.collectives import (ordered_psum, pairwise_psum,
                                    compressed_psum, psum, set_psum_mode)
from repro.launch.mesh import make_mesh

def smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

mesh = make_mesh((8,), ("data",))
rng = np.random.RandomState(0)
x = rng.randn(8, 16).astype(np.float32)

# ---- ordered_psum: bit-identical to the sequential loop over shards ----
def f(xs):
    return ordered_psum(xs, "data")
out = jax.jit(smap(f, mesh=mesh, in_specs=P("data"), out_specs=P()))(
    jnp.asarray(x).reshape(8, 1, 16))
want = np.zeros((1, 16), np.float32)
for i in range(8):
    want = want + x[i:i+1]                      # strict shard order
np.testing.assert_array_equal(np.asarray(out).reshape(1, 16), want)
print("ordered OK")

# ---- pairwise_psum: deterministic and close to f64 ----
out2 = jax.jit(smap(lambda xs: pairwise_psum(xs, "data"), mesh=mesh,
                    in_specs=P("data"), out_specs=P()))(
    jnp.asarray(x).reshape(8, 1, 16))
np.testing.assert_allclose(np.asarray(out2).reshape(1, 16),
                           x.sum(0, keepdims=True), rtol=1e-5, atol=1e-5)
print("pairwise OK")

# ---- compressed_psum: int8 + error feedback converges like exact mean ----
def step(g_local, err):
    return compressed_psum(g_local, "data", err)
jstep = jax.jit(smap(step, mesh=mesh,
                     in_specs=(P("data"), P("data")),
                     out_specs=(P(), P("data"))))
err = jnp.zeros((8, 1, 16), jnp.float32)
# single round: quantization error bounded by scale
g = jnp.asarray(x).reshape(8, 1, 16)
mean, err = jstep(g, err)
exact = x.mean(0, keepdims=True)
amax = np.abs(x).max()
assert np.abs(np.asarray(mean).reshape(1, 16) - exact).max() <= amax / 127.0 + 1e-6
# error feedback: accumulated mean over T rounds of the SAME grad converges
acc = np.zeros((1, 16), np.float32)
err = jnp.zeros((8, 1, 16), jnp.float32)
T = 50
for _ in range(T):
    m, err = jstep(g, err)
    acc += np.asarray(m).reshape(1, 16)
np.testing.assert_allclose(acc / T, exact, atol=amax / 127.0 / 10, rtol=0)
print("compressed OK")

# ---- psum choice point: mode dispatch (fast/ordered/pairwise) ----
def run_psum(mode):
    set_psum_mode(mode)
    try:
        return np.asarray(jax.jit(smap(
            lambda xs: psum(xs, "data"), mesh=mesh,
            in_specs=P("data"), out_specs=P()))(
            jnp.asarray(x).reshape(8, 1, 16))).reshape(1, 16)
    finally:
        set_psum_mode("fast")

np.testing.assert_array_equal(run_psum("ordered"), want)   # == sequential
np.testing.assert_allclose(run_psum("fast"), x.sum(0, keepdims=True),
                           rtol=1e-5, atol=1e-5)
np.testing.assert_array_equal(run_psum("pairwise"),
                              np.asarray(out2).reshape(1, 16))
# explicit mode argument overrides the process-wide choice
out3 = jax.jit(smap(lambda xs: psum(xs, "data", mode="ordered"), mesh=mesh,
                    in_specs=P("data"), out_specs=P()))(
    jnp.asarray(x).reshape(8, 1, 16))
np.testing.assert_array_equal(np.asarray(out3).reshape(1, 16), want)
try:
    C.set_psum_mode("nope")
except ValueError:
    pass
else:
    raise AssertionError("bad psum mode accepted")
print("psum choice OK")
"""


def test_collectives_on_submesh():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # tests run on the CPU backend
                            "JAX_PLATFORMS": "cpu",
                            "HOME": "/root"})
    assert r.returncode == 0, r.stdout + r.stderr
    for tag in ("ordered OK", "pairwise OK", "compressed OK",
                "psum choice OK"):
        assert tag in r.stdout
