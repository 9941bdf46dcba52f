"""The serving launcher as ``chip_smoke.py`` drives it: in-process, through
``repro.launch.serve.main(argv)``, rehearsed here on the CPU at reduced size;
``chip_smoke.py`` itself refusing to run without a TPU; and where the
persistent compilation cache lands."""

from __future__ import annotations

import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import serve
from repro.serve import FinishReason

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_env(monkeypatch, tmp_path):
    """Point the entry points' compile cache at a temporary directory and
    give the process back its own cache settings afterwards."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
    compilation_cache.reset_cache()


def test_serve_main_reduced_in_process(cache_env):
    """The chip smoke's phase-1 flags, CPU-sized: paged, chunked prefill,
    overlapped host loop.  Every request finishes with tokens."""
    out = serve.main(["--reduce", "--page-size", "8", "--prefill-chunk", "8",
                      "--overlap", "--requests", "6", "--prompt-len", "24"])
    res = out["results"]
    assert sorted(res) == sorted(out["prompts"]) and len(res) == 6
    for r in res.values():
        assert r["finish_reason"] is FinishReason.DONE
        assert r["n_generated"] >= 1 and len(r["tokens"]) == r["n_generated"]
    assert out["stats"]["prefill_chunks"] > 0
    assert out["engine"].cfg.n_layers == 2            # --reduce took effect
    assert os.listdir(cache_env)                      # the cache landed there


def test_chip_smoke_refuses_cpu(cache_env, capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err


def test_compile_cache_location(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins; without it the cache is the fixed
    ``<checkout>/.jax_cache``.  A fresh process, so nothing compiled before."""
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.runtime.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(4)).block_until_ready()\n")
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           # tests run on the CPU backend
           "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)}
    cache = tmp_path / "cache"
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=120,
                       env=dict(env, JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(cache) and os.listdir(cache)
    r = subprocess.run([sys.executable, "-c", script.splitlines()[1] + "\n"
                        "print(enable_compile_cache())"],
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.stdout.strip() == str(ROOT / ".jax_cache"), r.stderr
