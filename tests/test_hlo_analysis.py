"""Trip-count-aware HLO analyzer: the roofline's measurement instrument."""

import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from benchmarks.hlo_analysis import analyze  # noqa: E402


def _compiled(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_flat_scan_trip_count():
    def body(x, _):
        return x @ x, None

    def f(x):
        return jax.lax.scan(body, x, None, length=10)[0]

    a = analyze(_compiled(f, jnp.zeros((128, 128))))
    want = 10 * 2 * 128 ** 3
    assert abs(a["flops"] - want) / want < 0.01


def test_nested_scan_trip_product():
    def f(x):
        def outer(xx, _):
            def inner(y, _):
                return y @ y, None
            return jax.lax.scan(inner, xx, None, length=4)[0], None
        return jax.lax.scan(outer, x, None, length=3)[0]

    a = analyze(_compiled(f, jnp.zeros((64, 64))))
    want = 12 * 2 * 64 ** 3
    assert abs(a["flops"] - want) / want < 0.01


def test_xla_cost_analysis_undercounts_loops():
    """The motivating bug: XLA counts while bodies once (documents why the
    custom analyzer exists).  If XLA ever fixes this, this test will flag it
    and the roofline can switch back."""
    def body(x, _):
        return x @ x, None

    def f10(x):
        return jax.lax.scan(body, x, None, length=10)[0]

    c = jax.jit(f10).lower(jnp.zeros((128, 128))).compile()
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    xla_flops = ca.get("flops", 0.0)
    ours = analyze(c.as_text())["flops"]
    assert ours > 5 * xla_flops          # XLA ~1 body, ours ~10 bodies


def test_gqa_dot_flops_counted_from_operands():
    """einsum with batch dims + contraction: flops derived from shapes."""
    def f(q, k):
        return jnp.einsum("bhqd,bhkd->bhqk", q, k)

    q = jnp.zeros((2, 4, 64, 32))
    k = jnp.zeros((2, 4, 96, 32))
    a = analyze(_compiled(f, q, k))
    want = 2 * 2 * 4 * 64 * 96 * 32
    assert abs(a["flops"] - want) / want < 0.05


def test_collective_bytes_with_trip_multiplier():
    """psum inside a scan must be charged per-iteration."""
    import subprocess
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from benchmarks.hlo_analysis import analyze
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("data",))

def inner(x):
    def body(c, _):
        return jax.lax.psum(c, "data"), None
    return jax.lax.scan(body, x, None, length=7)[0]

f = jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                  check_vma=False)
c = jax.jit(f).lower(jnp.zeros((64, 64))).compile()
a = analyze(c.as_text())
per = 64 * 64 * 4
total = a["collective_bytes"]["total"]
assert 6 * per <= total <= 9 * per, (total, per)
print("COLLECTIVE-TRIPS-OK")
"""
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # tests run on the CPU backend
                            "JAX_PLATFORMS": "cpu",
                            "HOME": "/root"})
    assert "COLLECTIVE-TRIPS-OK" in r.stdout, r.stdout + r.stderr
