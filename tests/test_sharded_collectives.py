"""Collective-traffic gate for the mesh-sharded decode step: lower the serve
engine's decode program on a forced 8-device (data=4, model=2) mesh and count
the collectives XLA actually emitted (``benchmarks.hlo_analysis``).

The decode step must stay ACTIVATION-shaped: serving runs column-parallel
TP (contractions whole, small activation gathers before the row-parallel
dots — see dist.sharding.SERVE_RULES), so all-reduces are bounded by one
per attention layer plus a constant sampling overhead, and NO collective
may move anything approaching a full KV page pool.  The second gate is the
one with teeth — a
missing logical-axis rule makes GSPMD silently materialize replicated
operands by all-gathering a weight or a pool, which "works" (tokens stay
byte-identical) while multiplying per-step network traffic.  Counting ops in
the compiled HLO catches that regression at test time instead of in a fleet
profile.

Subprocess test: the forced device count must never leak into other tests.
"""

import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, ".")
import jax
from benchmarks.hlo_analysis import analyze
from repro.models import ModelConfig, get_model
from repro.serve import ContinuousBatchingScheduler, ServeEngine
from repro.launch.mesh import make_mesh

BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab_size=64, param_dtype="float32", compute_dtype="float32")
cfg = ModelConfig(name="gate", family="dense", **BASE)
model = get_model(cfg)
params, _ = model.init(jax.random.PRNGKey(0), cfg)
# model=2 divides n_kv_heads=2, so the page pools are GENUINELY kv-head
# sharded here (on a model=4 mesh they would replicate via the divisibility
# fallback and the pool-gather gate below would be vacuous)
mesh = make_mesh((4, 2), ("data", "model"))
eng = ServeEngine(cfg, params, max_new_tokens=6, stop_token=7, mesh=mesh)
sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=24, chunk=3,
                                    compact_threshold=0.5, page_size=4,
                                    pool_pages=14)
rep = analyze(eng._decode_chunk_serve.lower(
    eng.params, sched.cache, sched.out_buf, sched.tok, sched.p,
    sched.n_gen, sched.budget, sched.sstate,
    n_steps=1, stochastic=False).compile().as_text())
counts = rep["collective_counts"]
maxes = rep["collective_max_bytes"]
print("counts:", counts)
print("max bytes:", maxes)

# gate 1: no per-layer reduction creep.  Serving TP is column-parallel
# (SERVE_RULES): layer dots run whole after small activation gathers, so
# the only all-reduces left are sampling/head overhead.  Bound: one per
# attention layer + 4 slack.  2 layers -> cap 8; measured today: 2 total.
n_layers = cfg.n_layers
ar = counts.get("all-reduce", 0)
assert ar <= n_layers + 4, (
    f"decode step emits {ar} all-reduces for {n_layers} layers — more than "
    f"one per attention layer plus head overhead; a split-contraction "
    f"resolution has crept into the column-parallel serve path")

# gate 2: nothing resembling a pool (or a weight matrix) crosses the wire.
# The smallest \"bad\" collective is a full page pool all-gather; gate at
# half a pool so even a single-pool gather (15360 B here) trips it.
# Measured today: max single collective is 512 B (a gathered activation
# row), ~4 KB total per step.
pool_bytes = min(v.nbytes for k, v in sched.cache.items()
                 if k.endswith("_pages"))
worst = max(maxes.values(), default=0.0)
assert worst < pool_bytes / 2, (
    f"largest single collective moves {worst} B — vs {pool_bytes} B for a "
    f"full KV page pool; something (pool or weight) is being all-gathered "
    f"on the decode hot path")
print("collective gate OK")
"""


def test_sharded_decode_collective_budget():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=580,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # tests run on the CPU backend
                            "JAX_PLATFORMS": "cpu",
                            "HOME": "/root"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "collective gate OK" in r.stdout
