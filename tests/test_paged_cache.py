"""Paged KV cache (SVE §2.3.3 gather/scatter): core helpers, bit-identity of
paged decode against the dense engine on ragged stop patterns, prefix sharing
(refcount bump + suffix-only prefill + identical tokens), and the paged flash
attention paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import paging as PG
from repro.kernels.flash_attention import flash_attention
from repro.models import ModelConfig, get_model, paged_view, to_paged
from repro.serve import ContinuousBatchingScheduler, ServeEngine

BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab_size=64, param_dtype="float32", compute_dtype="float32")
MAX_LEN = 24

_NOL = {k: v for k, v in BASE.items() if k != "n_layers"}


def _family_cfg(family):
    """Tiny config per family for the native-vs-gather decode matrix."""
    if family == "dense":
        return ModelConfig(name="t", family="dense", **BASE)
    if family == "moe":
        # capacity_factor high enough that nothing drops: MoE is then
        # per-token deterministic and bit-comparable across cache layouts
        return ModelConfig(name="t", family="moe", first_k_dense=1,
                           n_experts=4, top_k=2, capacity_factor=4.0, **BASE)
    if family == "hybrid":
        return ModelConfig(name="t", family="hybrid", n_layers=3,
                           shared_attn_period=2, ssm_state=16, ssm_headdim=16,
                           ssm_chunk=16, **_NOL)
    if family == "encdec":
        return ModelConfig(name="t", family="encdec", n_enc_layers=2,
                           n_dec_layers=2, **BASE)
    if family == "vlm":
        return ModelConfig(name="t", family="dense", n_layers=10,
                           cross_attn_group=5, n_cross_tokens=4, **_NOL)
    raise ValueError(family)


def _family_batch(cfg, rng, b, s):
    batch = {"tokens": jnp.asarray(rng.randint(1, 64, (b, s))),
             "lens": jnp.asarray(rng.randint(3, s + 1, b), jnp.int32)}
    if cfg.family == "encdec":
        batch["src_emb"] = jnp.asarray(
            rng.randn(b, s, cfg.d_model).astype(np.float32))
        batch["src_lens"] = jnp.asarray(rng.randint(2, s + 1, b), jnp.int32)
    if cfg.cross_attn_group:
        batch["cross_emb"] = jnp.asarray(
            rng.randn(b, cfg.n_cross_tokens, cfg.d_model).astype(np.float32))
    return batch


@pytest.fixture(scope="module")
def dense_setup():
    cfg = ModelConfig(name="t", family="dense", **BASE)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, model, params


def _fresh_reference(eng, prompt, budget=None):
    res = eng.generate({"tokens": jnp.asarray(prompt)[None, :]},
                       max_len=MAX_LEN)
    n = int(res["n_generated"][0])
    if budget is not None:
        n = min(n, budget)
    return np.asarray(res["tokens"][0, :n]), n


# ---------------------------------------------------------------------------
# core paging helpers
# ---------------------------------------------------------------------------

def test_gather_pages_reproduces_dense_layout():
    rng = np.random.RandomState(0)
    P, hkv, ps, d, b, npg = 10, 2, 4, 8, 3, 2
    pool = jnp.asarray(rng.randn(P, hkv, ps, d).astype(np.float32))
    table = jnp.asarray(rng.randint(0, P, (b, npg)), jnp.int32)
    view = PG.gather_pages(pool, table)
    assert view.shape == (b, hkv, npg * ps, d)
    for i in range(b):
        for j in range(npg):
            np.testing.assert_array_equal(
                view[i, :, j * ps:(j + 1) * ps, :], pool[int(table[i, j])])


def test_gather_pages_with_lead_axes():
    rng = np.random.RandomState(1)
    L, P, hkv, ps, d = 3, 6, 2, 4, 8
    pool = jnp.asarray(rng.randn(L, P, hkv, ps, d).astype(np.float32))
    table = jnp.asarray([[5, 0], [1, 3]], jnp.int32)
    view = PG.gather_pages(pool, table, n_lead=1)
    assert view.shape == (L, 2, hkv, 2 * ps, d)
    np.testing.assert_array_equal(view[:, 0, :, :ps, :], pool[:, 5])
    np.testing.assert_array_equal(view[:, 1, :, ps:, :], pool[:, 3])


def test_scatter_page_roundtrip():
    rng = np.random.RandomState(2)
    P, hkv, ps, d, b = 8, 2, 4, 8, 3
    pool = jnp.zeros((P, hkv, ps, d), jnp.float32)
    page_ids = jnp.asarray([6, 1, 3], jnp.int32)
    offsets = jnp.asarray([0, 2, 3], jnp.int32)
    vals = jnp.asarray(rng.randn(b, hkv, d).astype(np.float32))
    pool = PG.scatter_page(pool, page_ids, offsets, vals)
    for i in range(b):
        np.testing.assert_array_equal(
            pool[int(page_ids[i]), :, int(offsets[i]), :], vals[i])
    # every other slot untouched
    assert float(jnp.abs(pool).sum()) == pytest.approx(
        float(jnp.abs(vals).sum()), rel=1e-6)


def test_scatter_block_and_gather_block_inverse():
    rng = np.random.RandomState(3)
    L, P, hkv, ps, d = 2, 7, 2, 4, 8
    pool = jnp.zeros((L, P, hkv, ps, d), jnp.float32)
    ids = jnp.asarray([4, 2], jnp.int32)
    blocks = jnp.asarray(rng.randn(2, L, hkv, ps, d).astype(np.float32))
    pool = PG.scatter_block(pool, ids, blocks, n_lead=1)
    got = PG.gather_block(pool, ids, n_lead=1)
    np.testing.assert_array_equal(got, blocks)


def test_page_whilelt():
    lens = jnp.asarray([0, 1, 8, 9, 24])
    live = PG.page_whilelt(lens, n_pages=3, page_size=8)
    np.testing.assert_array_equal(
        np.asarray(live),
        [[False, False, False], [True, False, False], [True, False, False],
         [True, True, False], [True, True, True]])


# ---------------------------------------------------------------------------
# paged flash attention reads through the page table
# ---------------------------------------------------------------------------

# Lanes of 1 (one live slot), 21 (ragged, mid-page) and 48 (the whole row)
# tokens over a 6-page table of 8-token pages, GQA group 2.  The XLA walk is
# forced to 1, 2, 4 (does not divide 6) and 6 (the whole table) pages a
# block; the default cases run the shape-chosen block.
PF_B, PF_HQ, PF_HKV, PF_D, PF_PS, PF_NPG, PF_P = 3, 4, 2, 16, 8, 6, 20
PF_LENS = (1, 21, 48)


def _walks(default_ids, *extra):
    """(impl, npb, sq, *extra) cases: ``default_ids`` name the decode cases
    of every impl at the chosen block; the rest force the XLA walk's
    block."""
    impls = ("naive", "xla", "kernel")
    cases = [pytest.param(impl, None, sq, *extra,
                          id=tid + ("-sq3" if sq > 1 else ""))
             for sq in (1, 3) for impl, tid in zip(impls, default_ids)]
    cases += [pytest.param("xla", npb, sq, *extra,
                           id=f"{default_ids[1]}-npb{npb}"
                           + ("-sq3" if sq > 1 else ""))
              for npb in (1, 2, 4, 6) for sq in (1, 3)]
    return cases


def _paged_problem(seed, sq):
    """Dense K/V, q, and the same K/V scattered into pools under a permuted
    page table whose entries past each lane's kv_len are STALE: they name
    live pages of other lanes, which the clamp + predicate must ignore."""
    rng = np.random.RandomState(seed)
    S = PF_NPG * PF_PS
    kd = rng.randn(PF_B, PF_HKV, S, PF_D).astype(np.float32)
    vd = rng.randn(PF_B, PF_HKV, S, PF_D).astype(np.float32)
    q = rng.randn(PF_B, PF_HQ, sq, PF_D).astype(np.float32)
    table = rng.permutation(PF_P)[:PF_B * PF_NPG].reshape(PF_B, PF_NPG)
    table = table.astype(np.int32)
    kv_lens = np.asarray(PF_LENS, np.int32)
    live = np.asarray(PG.page_whilelt(jnp.asarray(kv_lens), PF_NPG, PF_PS))
    live_ids = table[live]
    stale = table.copy()
    stale[~live] = rng.choice(live_ids, size=int((~live).sum()))
    q_off = np.maximum(kv_lens - sq, 0).astype(np.int32)
    return kd, vd, q, table, stale, jnp.asarray(kv_lens), jnp.asarray(q_off)


def _force_npb(monkeypatch, npb):
    if npb is not None:
        from repro.kernels.flash_attention import ops
        monkeypatch.setattr(ops, "_pick_pages_per_block",
                            lambda *shape: npb)


@pytest.mark.parametrize("impl,npb,sq", _walks(("naive", "xla", "kernel")))
def test_paged_flash_matches_dense(impl, npb, sq, monkeypatch):
    _force_npb(monkeypatch, npb)
    kd, vd, q, table, stale, kv_lens, q_off = _paged_problem(0, sq)
    pool_k = np.zeros((PF_P, PF_HKV, PF_PS, PF_D), np.float32)
    pool_v = np.zeros((PF_P, PF_HKV, PF_PS, PF_D), np.float32)
    for b in range(PF_B):
        for j in range(PF_NPG):
            sl = slice(j * PF_PS, (j + 1) * PF_PS)
            pool_k[table[b, j]] = kd[b, :, sl, :]
            pool_v[table[b, j]] = vd[b, :, sl, :]
    ref = flash_attention(jnp.asarray(q), jnp.asarray(kd), jnp.asarray(vd),
                          kv_lens=kv_lens, q_offset=q_off, causal=True,
                          impl="xla")
    out = flash_attention(jnp.asarray(q), jnp.asarray(pool_k),
                          jnp.asarray(pool_v), page_table=jnp.asarray(stale),
                          kv_lens=kv_lens, q_offset=q_off, causal=True,
                          impl=impl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pick_pages_per_block_follows_shapes():
    """Pages per block of the paged XLA walk depend on shapes alone: the
    whole table for tiny shapes; for the stablelm_3b decode (32 KV heads of
    80) and the StableLM-2-12B decode (8 KV heads of 160, group 4) over
    16-token pages, blocks of 256-512 tokens inside _pick_blocks' working-
    set budget, spread so the walk pads the table by under a page a block."""
    from repro.core import vla
    from repro.kernels.flash_attention.ops import _pick_pages_per_block

    def working_set(npb, ps, bq, hkv, group, d):
        rows, bk = hkv * group * bq, npb * ps
        return (rows * bk * 4 + (rows + 2 * hkv * bk) * d * 4
                + rows * (d + 256) * 4)

    assert _pick_pages_per_block(8, 3, 8, 2, 2, 16) == 3
    assert _pick_pages_per_block(8, PF_NPG, 8, PF_HKV, 2, PF_D) == PF_NPG
    assert _pick_pages_per_block(16, 1, 8, 32, 1, 80) == 1
    for shape in [(16, 80, 8, 32, 1, 80), (16, 248, 8, 8, 4, 160)]:
        ps, n_pages = shape[:2]
        npb = _pick_pages_per_block(*shape)
        assert npb == _pick_pages_per_block(*shape)
        assert 256 <= npb * ps <= 512
        assert working_set(npb, ps, *shape[2:]) <= vla.VMEM_BYTES // 2
        n_blocks = -(-n_pages // npb)
        assert n_blocks * npb - n_pages < n_blocks
    assert _pick_pages_per_block(16, 80, 8, 32, 1, 80) == 16      # 5 blocks
    assert _pick_pages_per_block(16, 248, 8, 8, 4, 160) == 31     # 8 blocks


# ---------------------------------------------------------------------------
# paged scheduler: bit-identity on ragged stop patterns
# ---------------------------------------------------------------------------

def test_paged_decode_bit_identical_to_dense_engine(dense_setup):
    """Acceptance criterion: streamed requests through the PAGED scheduler —
    ragged prompts, ragged budgets, natural stop tokens, lane recycling and
    page reuse — decode bit-identically to fresh dense-engine batches."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=8, stop_token=7)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 64, rng.randint(4, 12)) for _ in range(10)]
    budgets = [int(rng.randint(2, 9)) for _ in prompts]
    sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=MAX_LEN,
                                        chunk=4, compact_threshold=0.5,
                                        page_size=8)
    rids = [sched.submit(p, max_new_tokens=bud)
            for p, bud in zip(prompts, budgets)]
    results = sched.run()
    assert sorted(results) == sorted(rids)
    for rid, prompt, bud in zip(rids, prompts, budgets):
        want, n = _fresh_reference(eng, prompt, budget=bud)
        got = results[rid]
        assert got["n_generated"] == n
        np.testing.assert_array_equal(got["tokens"], want)
    # no page leaked and no refcount survived the drain
    assert sched.allocator.free_pages == sched.pool_pages
    assert (sched.allocator.refcount == 0).all()
    assert len(sched.prefix_index) == 0


def test_paged_matches_dense_scheduler_under_memory_pressure(dense_setup):
    """A pool HALF the dense footprint gates admission on pages (waits occur)
    yet still serves every request bit-identically."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=8, stop_token=7)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 64, rng.randint(4, 12)) for _ in range(8)]
    dense_pages = 4 * (MAX_LEN // 8)
    sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=MAX_LEN,
                                        chunk=4, page_size=8,
                                        pool_pages=dense_pages // 2)
    rids = [sched.submit(p) for p in prompts]
    results = sched.run()
    assert sched.stats["page_waits"] > 0      # admission was page-gated
    for rid, prompt in zip(rids, prompts):
        want, n = _fresh_reference(eng, prompt)
        assert results[rid]["n_generated"] == n
        np.testing.assert_array_equal(results[rid]["tokens"], want)
    assert sched.allocator.free_pages == sched.pool_pages


def test_paged_compaction_moves_tables_not_pools(dense_setup):
    """Lane compaction on a paged cache permutes page-table rows; the pools
    are untouched (same buffers' contents), and results stay bit-identical."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=12, stop_token=7)
    rng = np.random.RandomState(2)
    wave1 = [rng.randint(1, 64, rng.randint(4, 10)) for _ in range(4)]
    wave2 = [rng.randint(1, 64, rng.randint(4, 10)) for _ in range(3)]
    sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=MAX_LEN,
                                        chunk=2, compact_threshold=0.75,
                                        page_size=8)
    rids1 = [sched.submit(p, max_new_tokens=(12 if i == 2 else 1))
             for i, p in enumerate(wave1)]
    rids2 = [sched.submit(p, arrival=2.0) for p in wave2]
    results = sched.run()
    assert sched.stats["compactions"] >= 1
    for rid, prompt in zip(rids1 + rids2, wave1 + wave2):
        budget = 1 if (rid in rids1 and rid != rids1[2]) else 12
        want, n = _fresh_reference(eng, prompt, budget=budget)
        assert results[rid]["n_generated"] == n
        np.testing.assert_array_equal(results[rid]["tokens"], want)


def test_kernel_paged_decode_matches_dense(dense_setup):
    """paged_attn="kernel": flash reads K/V through the page table inside the
    model's decode (no gathered view) — tokens match the dense engine."""
    cfg, _, params = dense_setup
    ref_eng = ServeEngine(cfg, params, max_new_tokens=8, stop_token=7)
    eng = ServeEngine(cfg, params, max_new_tokens=8, stop_token=7,
                      paged_attn="kernel")
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 64, rng.randint(4, 12)) for _ in range(6)]
    sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=MAX_LEN,
                                        chunk=4, page_size=8)
    rids = [sched.submit(p) for p in prompts]
    results = sched.run()
    for rid, prompt in zip(rids, prompts):
        want, n = _fresh_reference(ref_eng, prompt)
        assert results[rid]["n_generated"] == n
        np.testing.assert_array_equal(results[rid]["tokens"], want)


# ---------------------------------------------------------------------------
# prefix sharing
# ---------------------------------------------------------------------------

def test_prefix_sharing_refcount_bump_and_identical_tokens(dense_setup):
    """Acceptance criterion: a second request sharing a prompt prefix admits
    WITHOUT re-prefilling the shared pages — observable as a refcount bump on
    the donor's pages and a suffix-sized prefill — and still produces tokens
    identical to a cold decode of its full prompt."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=12, stop_token=7)
    rng = np.random.RandomState(3)
    ps = 4
    donor = rng.randint(1, 64, 11)                   # 2 full pages of 4
    sharer = np.concatenate([donor[:8], rng.randint(1, 64, 5)])
    sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=32,
                                        chunk=2, page_size=ps)
    rid_a = sched.submit(donor, max_new_tokens=12)   # long-lived donor
    sched.step()                                     # admit donor
    assert sched.stats["prefix_hits"] == 0
    donor_pages = list(sched.lane_pages[0][:2])
    prefill_before = sched.stats["prefill_tokens"]
    assert (sched.allocator.refcount[donor_pages] == 1).all()

    rid_b = sched.submit(sharer)
    sched.step()                                     # admit sharer (hit)
    assert sched.stats["prefix_hits"] == 1
    assert sched.stats["prefix_hit_tokens"] == 8
    # refcount bump observed on the shared pages while both are resident
    assert (sched.allocator.refcount[donor_pages] == 2).all()
    # the sharer prefilled ONLY its suffix (13 - 8 tokens), not the prefix
    assert sched.stats["prefill_tokens"] - prefill_before == len(sharer) - 8

    results = sched.run()
    for rid, prompt in ((rid_a, donor), (rid_b, sharer)):
        res = eng.generate({"tokens": jnp.asarray(prompt)[None, :]},
                           max_len=32)
        n = int(res["n_generated"][0])
        want = np.asarray(res["tokens"][0, :n])
        assert results[rid]["n_generated"] == n
        np.testing.assert_array_equal(results[rid]["tokens"], want)
    assert sched.allocator.free_pages == sched.pool_pages


def test_prefix_never_shares_the_whole_prompt(dense_setup):
    """A prompt fully covered by resident pages still re-prefills its last
    block: the suffix prefill must produce the next-token logits."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=8, stop_token=7)
    rng = np.random.RandomState(4)
    ps = 4
    donor = rng.randint(1, 64, 8)                    # exactly 2 pages
    sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=32,
                                        chunk=2, page_size=ps)
    rid_a = sched.submit(donor, max_new_tokens=8)
    sched.step()
    rid_b = sched.submit(donor.copy())               # identical prompt
    sched.step()
    # only ONE page may be shared (the final block re-prefills)
    assert sched.stats["prefix_hit_tokens"] <= len(donor) - 1
    results = sched.run()
    want, n = _fresh_reference(eng, donor)
    for rid in (rid_a, rid_b):
        assert results[rid]["n_generated"] == n
        np.testing.assert_array_equal(results[rid]["tokens"], want)


def test_prefix_pages_outlive_the_donor(dense_setup):
    """The DONOR retiring while the sharer still decodes must not free the
    shared pages: the sharer's references keep them resident."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=12, stop_token=7)
    rng = np.random.RandomState(5)
    ps = 4
    donor = rng.randint(1, 64, 9)
    sharer = np.concatenate([donor[:8], rng.randint(1, 64, 4)])
    sched = ContinuousBatchingScheduler(eng, capacity=2, max_len=32,
                                        chunk=2, page_size=ps)
    rid_a = sched.submit(donor, max_new_tokens=6)    # donor retires early
    sched.step()                                     # admit donor
    shared = list(sched.lane_pages[0][:2])
    rid_b = sched.submit(sharer, max_new_tokens=12)
    sched.step()                                     # admit sharer (hit)
    assert sched.stats["prefix_hits"] == 1
    assert (sched.allocator.refcount[shared] == 2).all()
    while rid_a not in sched.results:                # run until donor retires
        sched.step()
    assert rid_b not in sched.results                # sharer still decoding
    # donor's references dropped; the sharer's keep the pages resident
    assert (sched.allocator.refcount[shared] == 1).all()
    results = sched.run()
    res = eng.generate({"tokens": jnp.asarray(sharer)[None, :]}, max_len=32)
    n = int(res["n_generated"][0])
    np.testing.assert_array_equal(results[rid_b]["tokens"],
                                  np.asarray(res["tokens"][0, :n]))
    assert results[rid_a]["n_generated"] == 6
    assert sched.allocator.free_pages == sched.pool_pages


def test_prefix_hit_coadmitted_with_longer_cold_request(dense_setup):
    """Regression: a prefix-shared row (pos0 > 0) co-admitted with a longer
    cold request must not have its padded suffix write clamp-shifted over its
    seeded prefix K/V (the admission group-fit guard defers the mismatch).
    Both orders of arrival must produce tokens identical to cold decode."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=8, stop_token=7)
    rng = np.random.RandomState(8)
    ps, ml = 8, 32
    donor = rng.randint(1, 64, 19)                   # 2 full pages shared
    sharer = np.concatenate([donor[:16], rng.randint(1, 64, 3)])
    cold = rng.randint(1, 64, 24)                    # forces plen_pad 32
    for first, second in ((sharer, cold), (cold, sharer)):
        sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=ml,
                                            chunk=2, page_size=ps)
        rid_d = sched.submit(donor, max_new_tokens=8)
        sched.step()                                 # donor resident
        rid_1 = sched.submit(first)
        rid_2 = sched.submit(second)
        results = sched.run()
        assert sched.stats["prefix_hits"] == 1
        for rid, prompt in ((rid_d, donor), (rid_1, first), (rid_2, second)):
            res = eng.generate({"tokens": jnp.asarray(prompt)[None, :]},
                               max_len=ml)
            n = int(res["n_generated"][0])
            assert results[rid]["n_generated"] == n
            np.testing.assert_array_equal(results[rid]["tokens"],
                                          np.asarray(res["tokens"][0, :n]))
        assert sched.allocator.free_pages == sched.pool_pages
        assert (sched.allocator.refcount == 0).all()


# ---------------------------------------------------------------------------
# paged view bridge + other families
# ---------------------------------------------------------------------------

def test_paged_view_roundtrips_prefill_state(dense_setup):
    """Admitting through pages and gathering the view reproduces the dense
    sub-cache contents for every valid position."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=4, stop_token=-1)
    rng = np.random.RandomState(6)
    prompt = rng.randint(1, 64, 9)
    sched = ContinuousBatchingScheduler(eng, capacity=2, max_len=16,
                                        chunk=1, page_size=8)
    sched.submit(prompt)
    sched._maybe_compact()
    sched._admit()                                   # prefill + page copy
    view = paged_view(cfg, sched.cache)
    dense = eng.make_cache(1, 16)
    logits, dense = eng._prefill(
        eng.params, {"tokens": jnp.asarray(prompt)[None, :],
                     "lens": jnp.asarray([9]),
                     "pos0": jnp.asarray([0], jnp.int32)}, dense)
    plen = len(prompt)
    np.testing.assert_array_equal(view["k"][:, 0, :, :plen, :],
                                  dense["k"][:, 0, :, :plen, :])
    np.testing.assert_array_equal(view["v"][:, 0, :, :plen, :],
                                  dense["v"][:, 0, :, :plen, :])
    assert int(view["pos"][0]) == plen


def test_hybrid_family_paged_bit_identity():
    cfg = ModelConfig(name="t", family="hybrid", n_layers=3,
                      shared_attn_period=2, ssm_state=16, ssm_headdim=16,
                      ssm_chunk=16, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, vocab_size=64, param_dtype="float32",
                      compute_dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_new_tokens=6, stop_token=7)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 64, rng.randint(4, 10)) for _ in range(4)]
    sched = ContinuousBatchingScheduler(eng, capacity=2, max_len=16,
                                        chunk=3, page_size=8)
    assert not sched.prefix_sharing          # SSM carry is not paged
    rids = [sched.submit(p) for p in prompts]
    results = sched.run()
    for rid, prompt in zip(rids, prompts):
        res = eng.generate({"tokens": jnp.asarray(prompt)[None, :]},
                           max_len=16)
        n = int(res["n_generated"][0])
        np.testing.assert_array_equal(results[rid]["tokens"],
                                      np.asarray(res["tokens"][0, :n]))
    assert sched.allocator.free_pages == sched.pool_pages


# ---------------------------------------------------------------------------
# native paged decode: per-family bit-identity vs the gather oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["dense", "moe", "hybrid", "encdec", "vlm"])
def test_native_paged_decode_matches_gather_oracle(family):
    """Acceptance criterion: EVERY family decodes a paged cache natively
    (flash attention through the page table, tail-page scatter-stores) with
    token streams identical to both the dense engine and the gather-bridge
    oracle (paged_attn="gather"), on ragged prompt lengths and natural
    stops.  The one-shot ``generate(page_size=)`` road covers the families
    the scheduler does not manage (encdec, vlm)."""
    cfg = _family_cfg(family)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(11)
    batch = _family_batch(cfg, rng, b=3, s=9)
    native = ServeEngine(cfg, params, max_new_tokens=6, stop_token=7)
    oracle = ServeEngine(cfg, params, max_new_tokens=6, stop_token=7,
                         paged_attn="gather")
    dense = native.generate(batch, max_len=MAX_LEN)
    paged = native.generate(batch, max_len=MAX_LEN, page_size=8)
    gathered = oracle.generate(batch, max_len=MAX_LEN, page_size=8)
    np.testing.assert_array_equal(np.asarray(dense["tokens"]),
                                  np.asarray(gathered["tokens"]))
    np.testing.assert_array_equal(np.asarray(dense["tokens"]),
                                  np.asarray(paged["tokens"]))
    np.testing.assert_array_equal(np.asarray(dense["n_generated"]),
                                  np.asarray(paged["n_generated"]))


def test_to_paged_view_roundtrip(dense_setup):
    """to_paged (identity tables) then paged_view reproduces the dense cache
    bit-exactly — the converter is the inverse of the gather bridge."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=4)
    rng = np.random.RandomState(12)
    batch = {"tokens": jnp.asarray(rng.randint(1, 64, (2, 9)))}
    cache = eng.make_cache(2, MAX_LEN, batch)
    _, cache = eng._prefill(eng.params, dict(batch, lens=jnp.asarray([9, 5])),
                            cache)
    view = paged_view(cfg, to_paged(cfg, cache, page_size=8))
    for key in ("k", "v", "pos"):
        np.testing.assert_array_equal(np.asarray(view[key]),
                                      np.asarray(cache[key]))


def test_native_paged_never_materializes_view(dense_setup, monkeypatch):
    """Acceptance criterion: with the default (native) engine, no
    ``paged_view`` materialization happens inside the jitted decode step —
    the monkeypatched bridge would raise at trace time."""
    import repro.serve.engine as E

    def boom(*a, **k):
        raise AssertionError("gather bridge used on the native hot path")

    monkeypatch.setattr(E, "paged_view", boom)
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=6, stop_token=7)
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, 64, rng.randint(4, 12)) for _ in range(4)]
    sched = ContinuousBatchingScheduler(eng, capacity=2, max_len=MAX_LEN,
                                        chunk=4, page_size=8)
    rids = [sched.submit(p) for p in prompts]
    results = sched.run()
    assert sorted(results) == sorted(rids)


def test_moe_family_paged_native_bit_identity():
    """MoE through the PAGED scheduler (native decode over the dense-stack
    and expert-stack pools) matches fresh dense generation."""
    cfg = _family_cfg("moe")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_new_tokens=6, stop_token=7)
    rng = np.random.RandomState(14)
    prompts = [rng.randint(1, 64, rng.randint(4, 10)) for _ in range(5)]
    sched = ContinuousBatchingScheduler(eng, capacity=2, max_len=16,
                                        chunk=3, page_size=8)
    assert not sched.prefix_sharing            # capacity dropping forbids it
    rids = [sched.submit(p) for p in prompts]
    results = sched.run()
    for rid, prompt in zip(rids, prompts):
        res = eng.generate({"tokens": jnp.asarray(prompt)[None, :]},
                           max_len=16)
        n = int(res["n_generated"][0])
        assert results[rid]["n_generated"] == n
        np.testing.assert_array_equal(results[rid]["tokens"],
                                      np.asarray(res["tokens"][0, :n]))
    assert sched.allocator.free_pages == sched.pool_pages


def test_gather_fallback_raises(monkeypatch):
    """A family without native paged decode under the native default is an
    error: the gather oracle never quietly stands in for the native path."""
    cfg = _family_cfg("dense")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_new_tokens=4, stop_token=7)
    import repro.models.dense as D
    monkeypatch.setattr(D, "paged_decode_ok", lambda cfg: False)
    with pytest.raises(NotImplementedError, match="no native paged decode"):
        eng.generate({"tokens": jnp.asarray([[3, 4, 5, 6]])},
                     max_len=16, page_size=8)


def test_ssm_family_refuses_paging():
    cfg = ModelConfig(name="t", family="ssm", n_layers=2, ssm_state=16,
                      ssm_headdim=16, ssm_chunk=16, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=64,
                      param_dtype="float32", compute_dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_new_tokens=4)
    with pytest.raises(ValueError, match="paging does not apply"):
        ContinuousBatchingScheduler(eng, capacity=2, max_len=16, page_size=8)


# ---------------------------------------------------------------------------
# quantized pages: narrow pools widened in the gather (SVE extending loads)
# ---------------------------------------------------------------------------

def _quant_dtype_or_skip(name):
    try:
        return PG.resolve_page_dtype(name)
    except ValueError as e:                          # fp8-less jax build
        pytest.skip(str(e))


@pytest.mark.parametrize("page_dtype", ["int8", "fp8"])
def test_quantize_block_roundtrip_bounded(page_dtype):
    """quantize_block -> dequantize stays within the per-row absmax step:
    int8 rounds to absmax/127 steps (max error half a step), fp8 e4m3 keeps
    ~4 bits of relative precision.  All-zero rows decode to exactly zero."""
    dt = _quant_dtype_or_skip(page_dtype)
    rng = np.random.RandomState(0)
    v = rng.randn(5, 3, 16).astype(np.float32) * 4.0
    v[2, 1] = 0.0                                   # an all-zero row
    q, scale = PG.quantize_block(jnp.asarray(v), dt)
    assert q.dtype == dt and scale.shape == (5, 3)
    deq = np.asarray(PG.dequantize(q, scale))
    absmax = np.abs(v).max(-1, keepdims=True)
    tol = absmax * ((0.5 / 127.0) if page_dtype == "int8" else (1.0 / 16.0))
    assert (np.abs(deq - v) <= tol + 1e-7).all()
    np.testing.assert_array_equal(deq[2, 1], np.zeros(16, np.float32))
    assert float(scale[2, 1]) == 0.0


def test_gather_pages_scale_is_extending_load():
    """gather_pages(scale=...) widens narrow pool elements at the point of
    use: the view equals gathering an explicitly dequantized pool, and stays
    within quantization tolerance of the original f32 pages."""
    rng = np.random.RandomState(1)
    P, hkv, ps, d = 6, 2, 4, 8
    blocks = rng.randn(3, hkv, ps, d).astype(np.float32)
    ids = jnp.asarray([5, 0, 2], jnp.int32)
    pool = jnp.zeros((P, hkv, ps, d), jnp.int8)
    scale = jnp.zeros((P, hkv, ps), jnp.float32)
    pool, scale = PG.scatter_block_q(pool, scale, ids, jnp.asarray(blocks))
    table = jnp.asarray([[5, 0], [2, 5]], jnp.int32)
    view = PG.gather_pages(pool, table, scale=scale)
    assert view.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(view),
        np.asarray(PG.gather_pages(PG.dequantize(pool, scale), table)))
    # lane 0 reads blocks 0 then 1; bounded by the absmax step per token row
    want = np.concatenate([blocks[0], blocks[1]], axis=1)
    tol = np.abs(want).max(-1, keepdims=True) * (0.5 / 127.0) + 1e-7
    assert (np.abs(np.asarray(view[0]) - want) <= tol).all()


def test_scatter_page_q_exact_single_token():
    """The decode-step quantizing write: per-(page, slot) scale granularity
    makes a single-token store quantize EXACTLY (same bytes+scale as
    quantize_block alone), with every other slot's bytes and scales
    untouched — no read-modify-write of neighbours."""
    rng = np.random.RandomState(2)
    P, hkv, ps, d = 5, 2, 4, 8
    pool = jnp.asarray(rng.randint(-127, 128, (P, hkv, ps, d)), jnp.int8)
    scale = jnp.asarray(rng.rand(P, hkv, ps).astype(np.float32))
    before_p, before_s = np.asarray(pool).copy(), np.asarray(scale).copy()
    vals = jnp.asarray(rng.randn(2, hkv, d).astype(np.float32))
    page_ids = jnp.asarray([3, 1], jnp.int32)
    offsets = jnp.asarray([2, 0], jnp.int32)
    pool2, scale2 = PG.scatter_page_q(pool, scale, page_ids, offsets, vals)
    q_want, s_want = PG.quantize_block(vals, jnp.int8)
    after_p, after_s = np.asarray(pool2).copy(), np.asarray(scale2).copy()
    for i in range(2):
        pid, off = int(page_ids[i]), int(offsets[i])
        np.testing.assert_array_equal(after_p[pid, :, off], q_want[i])
        np.testing.assert_array_equal(after_s[pid, :, off], s_want[i])
        after_p[pid, :, off] = before_p[pid, :, off]
        after_s[pid, :, off] = before_s[pid, :, off]
    np.testing.assert_array_equal(after_p, before_p)   # neighbours untouched
    np.testing.assert_array_equal(after_s, before_s)


@pytest.mark.parametrize(
    "impl,npb,sq,page_dtype",
    _walks(("naive", "xla", "kernel"), "int8")
    + _walks(("naive-fp8", "xla-fp8", "kernel-fp8"), "fp8"))
def test_paged_flash_quantized_close_to_dense(impl, npb, sq, page_dtype,
                                              monkeypatch):
    """Paged flash attention over int8 / fp8 pools + scale pools stays within
    quantization tolerance of dense f32 flash — every impl widens the same
    narrow bytes through the same page walk — and matches dense flash over
    the widened pages exactly as the f32 walk does."""
    dt = _quant_dtype_or_skip(page_dtype)
    _force_npb(monkeypatch, npb)
    kd, vd, q, table, stale, kv_lens, q_off = _paged_problem(3, sq)
    ids = jnp.asarray(table.reshape(-1))
    blk = lambda a: jnp.asarray(np.stack(
        [a[b, :, j * PF_PS:(j + 1) * PF_PS, :] for b in range(PF_B)
         for j in range(PF_NPG)]))
    pool = jnp.zeros((PF_P, PF_HKV, PF_PS, PF_D), dt)
    sc = jnp.zeros((PF_P, PF_HKV, PF_PS), jnp.float32)
    pool_k, sc_k = PG.scatter_block_q(pool, sc, ids, blk(kd))
    pool_v, sc_v = PG.scatter_block_q(pool, sc, ids, blk(vd))
    out = flash_attention(jnp.asarray(q), pool_k, pool_v,
                          page_table=jnp.asarray(stale), kv_lens=kv_lens,
                          q_offset=q_off, causal=True, impl=impl,
                          k_scale=sc_k, v_scale=sc_v)
    ref = flash_attention(jnp.asarray(q), jnp.asarray(kd), jnp.asarray(vd),
                          kv_lens=kv_lens, q_offset=q_off, causal=True,
                          impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0.05, atol=0.05)
    t = jnp.asarray(table)
    widened = flash_attention(
        jnp.asarray(q), PG.gather_pages(pool_k, t, scale=sc_k),
        PG.gather_pages(pool_v, t, scale=sc_v), kv_lens=kv_lens,
        q_offset=q_off, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(widened),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("family", ["dense", "moe", "hybrid", "encdec", "vlm"])
def test_quantized_native_decode_matches_gather_oracle(family):
    """Acceptance criterion: EVERY family decodes an int8 paged cache
    natively with token streams identical to the gather oracle, which
    dequantizes the same pool bytes into a dense view — the oracle bounds
    quantization error to exactly what quantize_block introduced, so any
    native/oracle divergence is a widening bug, not noise."""
    cfg = _family_cfg(family)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(21)
    batch = _family_batch(cfg, rng, b=3, s=9)
    native = ServeEngine(cfg, params, max_new_tokens=6, stop_token=7,
                         page_dtype="int8")
    oracle = ServeEngine(cfg, params, max_new_tokens=6, stop_token=7,
                         paged_attn="gather", page_dtype="int8")
    paged = native.generate(batch, max_len=MAX_LEN, page_size=8)
    gathered = oracle.generate(batch, max_len=MAX_LEN, page_size=8)
    np.testing.assert_array_equal(np.asarray(paged["tokens"]),
                                  np.asarray(gathered["tokens"]))
    np.testing.assert_array_equal(np.asarray(paged["n_generated"]),
                                  np.asarray(gathered["n_generated"]))


def test_quantized_scheduler_matches_quantized_generate(dense_setup):
    """Streamed int8-paged requests (admission scatter_block_q writes +
    decode scatter_page_q writes, lane recycling, prefix sharing) produce
    the same tokens as fresh one-shot quantized generation — the scheduler
    introduces no quantization of its own."""
    cfg, _, params = dense_setup
    eng = ServeEngine(cfg, params, max_new_tokens=8, stop_token=7,
                      page_dtype="int8")
    rng = np.random.RandomState(22)
    prompts = [rng.randint(1, 64, rng.randint(4, 12)) for _ in range(8)]
    sched = ContinuousBatchingScheduler(eng, capacity=4, max_len=MAX_LEN,
                                        chunk=4, page_size=8)
    assert "k_pages_scale" in sched.cache            # scale pools allocated
    rids = [sched.submit(p) for p in prompts]
    results = sched.run()
    for rid, prompt in zip(rids, prompts):
        res = eng.generate({"tokens": jnp.asarray(prompt)[None, :]},
                           max_len=MAX_LEN, page_size=8)
        n = int(res["n_generated"][0])
        assert results[rid]["n_generated"] == n
        np.testing.assert_array_equal(results[rid]["tokens"],
                                      np.asarray(res["tokens"][0, :n]))
    assert sched.allocator.free_pages == sched.pool_pages
