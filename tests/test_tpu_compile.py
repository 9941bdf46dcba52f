"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode runs a kernel on the CPU but checks none of Mosaic's rules
(block tiling, memory spaces, lowerable primitives).  These tests lower and
compile each kernel of the serving path for a v5e chip that is described, not
attached, at real widths: stablelm_3b attention (32 KV heads x 80) and the
mamba2_130m SSD scan (24 heads x 64, state 128), and the XLA paged walk at the
stablelm_3b decode shape.  A compile that passes here is not a chip run; a
compile that fails here would fail on the chip.

The topology is described inside a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.kernel import (flash_attention_pallas,
                                                  flash_attention_pallas_paged)
from repro.kernels.flash_attention.ops import _pick_pages_per_block
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas

# stablelm_3b serving shapes: 8 lanes, 576-token rows in 16-slot pages
LANES, HEADS, HEAD_DIM, PAGE, POOL, ROW_PAGES = 8, 32, 80, 16, 512, 36


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on the described chip 0, with the persistent compilation
    cache off: a described-chip compile is written to it but can never be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text              # the Mosaic kernel is in


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16", "int8",
                                        "float8_e4m3fn"])
def test_paged_flash_compiles(one_chip, pool_dtype):
    dt = jnp.dtype(pool_dtype)
    quant = dt in (jnp.int8, jnp.float8_e4m3fn)
    q_dt = jnp.float32 if dt == jnp.float32 else jnp.bfloat16

    def S(shape, d):
        return jax.ShapeDtypeStruct(shape, d, sharding=one_chip)

    def f(q, kp, vp, table, kv_lens, q_off, win, ks=None, vs=None):
        return flash_attention_pallas_paged(
            q, kp, vp, table, kv_lens, q_off, win, bq=8, interpret=False,
            k_scale=ks, v_scale=vs)

    pool = S((POOL, HEADS, PAGE, HEAD_DIM), dt)
    args = [S((LANES, HEADS, 8, HEAD_DIM), q_dt), pool, pool,
            S((LANES, ROW_PAGES), jnp.int32), S((LANES,), jnp.int32),
            S((LANES,), jnp.int32), S((1,), jnp.int32)]
    if quant:
        args += [S((POOL, HEADS, PAGE), jnp.float32)] * 2
    _compile(f, *args)


def test_dense_flash_compiles(one_chip):
    qkv = jax.ShapeDtypeStruct((2, HEADS, 512, HEAD_DIM), jnp.bfloat16,
                               sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    win = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)

    def f(q, k, v, kv_lens, q_off, w):
        return flash_attention_pallas(q, k, v, kv_lens, q_off, w, bq=128,
                                      bk=512, causal=True, interpret=False)

    _compile(f, qkv, qkv, qkv, i32, i32, win)


def test_ssd_scan_compiles(one_chip):
    bz, s, h, p, n = 2, 256, 24, 64, 128

    def S(shape, d):
        return jax.ShapeDtypeStruct(shape, d, sharding=one_chip)

    def f(x, dt, a, b, c, h0):
        return ssd_scan_pallas(x, dt, a, b, c, h0, chunk=128, interpret=False)

    _compile(f, S((bz, s, h, p), jnp.bfloat16), S((bz, s, h), jnp.float32),
             S((h,), jnp.float32), S((bz, s, n), jnp.bfloat16),
             S((bz, s, n), jnp.bfloat16), S((bz, h, p, n), jnp.float32))


def _loop_trip_counts(text):
    """Trip count of every while loop of a compiled HLO text, read from the
    bound its condition compares the induction variable against."""
    counts = []
    for cond in re.findall(r" while\(.*?condition=(%[\w.\-]+)", text):
        body = text.split("\n" + cond + " ", 1)[1].split("\n}", 1)[0]
        bound = re.search(r"constant\((\d+)\)", body)
        assert bound and "direction=LT" in body, body
        counts.append(int(bound.group(1)))
    return counts


def test_xla_paged_decode_walks_blocks_of_pages(one_chip):
    """The stablelm_3b decode step's paged attention (4 lanes, 32 heads of
    80, 16-token pages, 80-page table of a 1280-token row) compiles for the
    chip, and its kv loop walks ceil(80 / npb) blocks, not 80 pages."""
    lanes, n_pages, pool = 4, 80, 255

    def S(shape, d):
        return jax.ShapeDtypeStruct(shape, d, sharding=one_chip)

    def f(q, kp, vp, table, kv_lens):
        return flash_attention(q, kp, vp, page_table=table, kv_lens=kv_lens,
                               q_offset=kv_lens - 1, causal=True, impl="xla")

    kv = S((pool, HEADS, PAGE, HEAD_DIM), jnp.bfloat16)
    text = jax.jit(f).lower(
        S((lanes, HEADS, 1, HEAD_DIM), jnp.bfloat16), kv, kv,
        S((lanes, n_pages), jnp.int32), S((lanes,), jnp.int32)
    ).compile().as_text()
    npb = _pick_pages_per_block(PAGE, n_pages, 8, HEADS, 1, HEAD_DIM)
    assert npb > 1
    assert _loop_trip_counts(text) == [-(-n_pages // npb)]
