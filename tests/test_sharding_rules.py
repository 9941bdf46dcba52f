"""Mesh-agnostic sharding resolution: divisibility fallbacks, axis reuse,
and the kv_heads -> kv_seq flash-decode fallback."""

import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax
from jax.sharding import PartitionSpec as P
from repro.dist import sharding as SH
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))

# TP weight: heads divisible by model -> sharded
assert SH.spec_for((256, 512), ("embed", "heads"), mesh) == P("data", "model")
# fused kv out dim not divisible by model=4 -> replicate (fallback)
assert SH.spec_for((64, 6), ("embed", "kv_heads"), mesh) == P("data", None)
# same mesh axis never reused within one array
assert SH.spec_for((8, 8), ("mlp", "heads"), mesh) == P("model", None)
# batch folds pod x data when present
mesh3 = make_mesh((2, 2, 4), ("pod", "data", "model"))
assert SH.spec_for((8, 128), ("batch", None), mesh3) == P(("pod", "data"), None)
# batch=1 (long_500k) -> fully replicated
assert SH.spec_for((1, 128), ("batch", None), mesh3) == P(None, None)
# kv cache: kv_heads=2 can't take model=4 => SEQ takes it (flash-decode)
spec = SH.spec_for((4, 2, 64, 32), ("batch", "act_kv_heads", "kv_seq", None), mesh)
assert spec == P("data", None, "model", None), spec
# kv_heads=4 divisible => heads take model, seq replicated
spec = SH.spec_for((4, 4, 64, 32), ("batch", "act_kv_heads", "kv_seq", None), mesh)
assert spec == P("data", "model", None, None), spec
# mesh-agnosticism: same logical axes resolve on ANY mesh shape
for shape, names in [((4,), ("data",)), ((2, 2), ("data", "model")),
                     ((2, 2, 2), ("pod", "data", "model"))]:
    m = make_mesh(shape, names)
    sp = SH.spec_for((16, 256, 512), ("layers", "embed", "mlp"), m)
    assert sp[0] is None

# ---- serve-shaped arrays (dist.serve: the scheduler's state layouts) ----
import numpy as np
from repro.dist import serve as DSRV
from repro.models.config import ModelConfig

# page pool (P, Hkv, ps, D): Hkv=2 does NOT divide model=4 -> the pool
# REPLICATES (divisibility fallback) — never an error, never a seq split
# (pools are gathered by table; their page dims must stay whole)
assert SH.spec_for((15, 2, 4, 16), (None, "kv_heads", None, None), mesh,
                   SH.SERVE_RULES) == P(None, None, None, None)
# Hkv=4 divides -> heads sharded over model
assert SH.spec_for((15, 4, 4, 16), (None, "kv_heads", None, None), mesh,
                   SH.SERVE_RULES) == P(None, "model", None, None)
# SERVE_RULES: no FSDP weight split over "data" while serving
assert SH.spec_for((256, 512), ("embed", "heads"), mesh,
                   SH.SERVE_RULES) == P(None, "model")

# full serve-cache resolution through dist.serve.cache_axes: a paged dense
# cache with GQA (Hkv=2 vs model=4) must replicate its pools but engage the
# kv_seq flash-decode fallback on the admission sub-cache's dense lane KV
cfg = ModelConfig(family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab_size=64)
paged = {"k_pages": np.zeros((2, 15, 2, 4, 16), np.float32),
         "v_pages": np.zeros((2, 15, 2, 4, 16), np.float32),
         "page_table": np.zeros((4, 6), np.int32),
         "pos": np.zeros((4,), np.int32)}
sh = DSRV.cache_shardings(cfg, paged, mesh)
assert sh["k_pages"].spec == P(None, None, None, None, None), sh["k_pages"].spec
assert sh["page_table"].spec == P("data", None)
assert sh["pos"].spec == P("data")
dense = {"k": np.zeros((2, 4, 2, 64, 16), np.float32),
         "v": np.zeros((2, 4, 2, 64, 16), np.float32),
         "pos": np.zeros((4,), np.int32)}
sh = DSRV.cache_shardings(cfg, dense, mesh)
# (L, B, Hkv, S, D): lanes over data; Hkv=2 can't take model=4 -> SEQ does
assert sh["k"].spec == P(None, "data", None, "model", None), sh["k"].spec
# Hkv=4 divides: heads take model, seq stays whole
dense4 = dict(dense, k=np.zeros((2, 4, 4, 64, 16), np.float32),
              v=np.zeros((2, 4, 4, 64, 16), np.float32))
sh = DSRV.cache_shardings(cfg.replace(n_kv_heads=4), dense4, mesh)
assert sh["k"].spec == P(None, "data", "model", None, None), sh["k"].spec
# ---- make_production_mesh degrades instead of raising on a dev box ----
import warnings
from repro.launch import mesh as M

with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    prod = M.make_production_mesh()
# 16 forced devices here: (16,16) halves largest-first down to (4,4)
assert dict(zip(prod.axis_names, prod.devices.shape)) == {"data": 4, "model": 4}, prod
assert any(issubclass(x.category, RuntimeWarning) for x in w), w
assert any("degraded" in str(x.message) for x in w), w
print("sharding rules OK")
"""


def test_sharding_rules_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # tests run on the CPU backend
                            "JAX_PLATFORMS": "cpu",
                            "HOME": "/root"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharding rules OK" in r.stdout
