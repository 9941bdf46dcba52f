"""Batched serving engine with a fully-jitted vector-partitioned decode loop.

A batch of requests is a VECTOR (paper §2.3.4): each lane is one request.
Prefill uses ragged whilelt lengths; the decode loop is ONE jitted XLA while
loop over a shrinking active partition (§2.3.4) — per-lane stop conditions
retire lanes inside the compiled loop, so there is no per-token Python
dispatch and no cache rewriting: the model's own ``dynamic_update_slice``
writes are the only cache mutation (XLA aliases them in place).

Inactive lanes keep decoding architecturally but their effects are not
observed: sampled tokens are merging-predicated to the stop token, output
slots are write-masked, and their cache slots become garbage-beyond-pos —
harmless, because a finished lane is always refilled through
``repro.models.slot_update`` (a fresh prefill) before it is reused.  That is
the contract that makes continuous batching (see ``serve.scheduler``) a pure
lane-permutation problem.

Sampling is per-lane predicated (``repro.sample``): every lane carries its
own SamplingParams row (temperature/top-k/top-p/min-p/penalties/seed/greedy
flag) and PRNG key inside the decode carry, so heterogeneous stochastic
decoding runs in the SAME jitted while-loop — greedy lanes select the
bit-exact raw argmax under a merging predicate, and a request's stream is a
function of (seed, prompt, params) only, never of batch composition.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro import sample as S
from repro.core import paging as PG
from repro.core import predicate as P
from repro.dist import serve as DS
from repro.dist import sharding as SH
from repro.models import (gather_lanes, get_model, is_paged, merge_lanes,
                          paged_decode_ok, paged_view, paged_writeback,
                          slot_update, to_paged)
from repro.obs import Obs
from repro.obs.scopes import scope
from repro.sample.processors import ban_pred, mask_logits


@dataclasses.dataclass
class ServeEngine:
    """Family-agnostic generation engine: jitted prefill / decode-burst /
    fused-serve-step programs over one model config + params.

    The engine owns everything that touches the device — cache allocation
    (dense, paged, or quantized paged via ``page_dtype``), the decode burst
    with per-lane predication, the fused one-dispatch serve step, and the
    mesh-sharded variants — while ``ContinuousBatchingScheduler`` owns all
    host-side traffic state (lanes, pages, prefixes, the swap tier).  Entry
    points: ``generate`` (static batch), ``make_paged_cache`` /
    ``_fused_step`` and friends (driven by the scheduler).  All jitted
    programs are shape-bucketed so ragged traffic compiles a bounded set of
    executables; see docs/ARCHITECTURE.md for the round anatomy.
    """

    cfg: object
    params: object
    max_new_tokens: int = 32
    stop_token: int = 0
    # engine-wide default sampling spec for requests/batches that don't carry
    # their own (None = greedy argmax, the bit-exact legacy behavior)
    default_sampling: Optional[S.SamplingParams] = None
    # constrained decoding: token ids masked out of EVERY lane's vocab
    # partition (greedy lanes included) before sampling
    banned_tokens: Optional[Sequence[int]] = None
    # paged decode: "native" (the default; "kernel" is a legacy alias) reads
    # K/V directly through the page table inside flash attention and
    # scatter-stores each new token into the lane's tail page — no dense-view
    # materialization on the decode hot path.  "gather" is the reference
    # oracle: materialize the dense view through the table, run the unchanged
    # family decode, scatter the one new token back (bitwise identical to the
    # dense cache BY CONSTRUCTION; tests pin the native path against it).
    paged_attn: str = "native"
    # quantized KV pages: None (full precision) or "int8" / "fp8" — pools
    # store narrow elements with per-slot absmax scale pools riding alongside
    # (``<key>_pages_scale``); flash attention widens them in the gather
    # (SVE §2.3.3 extending loads).  Applies to every paged cache this engine
    # allocates (make_paged_cache / generate(page_size=...)).
    page_dtype: Optional[str] = None
    # mesh-sharded serving: a jax Mesh with "model" (TP) and/or "data" (lane)
    # axes.  Params commit to their TP placement, every jitted entry point
    # traces under SERVE_RULES so the model's activation constraints resolve,
    # and the scheduler commits its serve state through ``dist.serve`` —
    # model code itself never sees the mesh (the VL-agnostic contract).
    mesh: Optional[object] = None
    # observability handle (repro.obs.Obs): one-shot ``generate`` records its
    # prefill/decode seams here.  The scheduler does NOT inherit this — it
    # defaults to its own registry; pass the same handle to both when one
    # combined timeline is wanted (launch --trace-out does).
    obs: Optional[object] = None

    def __post_init__(self):
        if self.obs is None:
            self.obs = Obs()
        if self.paged_attn not in ("native", "kernel", "gather"):
            raise ValueError(
                f"paged_attn must be 'native' ('kernel' alias) or 'gather', "
                f"got {self.paged_attn!r}")
        if self.mesh is not None and getattr(self.cfg, "act_shard",
                                             "none") == "none":
            # activation constraints are what steer GSPMD away from
            # all-gathering pools/heads; enable them unless the caller
            # pinned a specific mode
            self.cfg = dataclasses.replace(self.cfg, act_shard="tp")
        self.model = get_model(self.cfg)
        # logits run over the PADDED vocab (the model already predicates the
        # pad lanes to -1e30, so leaving them "allowed" here is inert)
        v = getattr(self.cfg, "padded_vocab", self.cfg.vocab_size)
        self._ban = (ban_pred(v, tuple(self.banned_tokens))
                     if self.banned_tokens else None)
        self._prefill = jax.jit(
            lambda p, b, c: self.model.prefill(p, self.cfg, b, c))
        # donate the mutable decode state (cache/out_buf/tok/p/n_gen and the
        # sampler lane state) so XLA updates it in place instead of copying
        # the KV cache every burst; the CPU backend has no donation (it
        # would only warn), so gate it
        donate = (1, 2, 3, 4, 5, 7) if jax.default_backend() != "cpu" else ()
        self._decode_chunk = jax.jit(self._decode_chunk_impl,
                                     static_argnames=("n_steps", "stochastic",
                                                      "width"),
                                     donate_argnums=donate)
        # serve-mode variants for the scheduler's async host loop: out_buf /
        # p / n_gen must NOT be donated (the overlap harvest still holds the
        # previous round's handles to them), so only the cache, the sampler
        # state and (for the fused program) the per-round inputs go in place
        serve_donate = (1, 7) if jax.default_backend() != "cpu" else ()
        self._decode_chunk_serve = jax.jit(
            self._decode_chunk_impl,
            static_argnames=("n_steps", "stochastic", "width"),
            donate_argnums=serve_donate)
        fused_donate = ((1, 6, 7, 8, 9)
                        if jax.default_backend() != "cpu" else ())
        self._fused_step = jax.jit(
            self._fused_step_impl,
            static_argnames=("n_steps", "stochastic", "admit_stoch",
                            "part_final", "part_stoch", "max_len", "width"),
            donate_argnums=fused_donate)
        if self.page_dtype is not None:
            PG.resolve_page_dtype(self.page_dtype)   # validate eagerly
        # host-swap page movers: batched whole-page reads/writes used by the
        # scheduler's eviction tier (device -> host spill, host -> device
        # page-in).  Eager jitted calls outside the fused program; the
        # scheduler pads the page-id vectors to pow2 buckets.
        self._gather_blocks = jax.jit(self._gather_blocks_impl)
        self._scatter_blocks = jax.jit(self._scatter_blocks_impl)
        # preemption lane movers: one-dispatch gather of a lane's dense
        # carries + decode rows (spill half) and the splice that puts them
        # back (resume half) — the scheduler's bit-exact preempt/resume path
        self._spill_lane = jax.jit(self._spill_lane_impl)
        self._resume_lane = jax.jit(self._resume_lane_impl)
        if self.mesh is not None:
            # commit params to their TP placement and trace every entry
            # point under the ambient serve rules so the model's logical-
            # axis constraints resolve against THIS mesh
            self.params = DS.shard_params(self.model, self.cfg, self.params,
                                          self.mesh)
            for name in ("_prefill", "_decode_chunk", "_decode_chunk_serve",
                         "_fused_step", "_gather_blocks", "_scatter_blocks",
                         "_spill_lane", "_resume_lane"):
                setattr(self, name, self._with_mesh(getattr(self, name)))

    def _with_mesh(self, fn):
        def run(*args, **kwargs):
            with SH.use_mesh_rules(self.mesh, SH.SERVE_RULES):
                return fn(*args, **kwargs)

        def lower(*args, **kwargs):
            # introspection path (HLO collective audits): same ambient rules
            with SH.use_mesh_rules(self.mesh, SH.SERVE_RULES):
                return fn.lower(*args, **kwargs)
        run.lower = lower
        return run

    @scope("sample")
    def _sample(self, logits, sstate=None, out_buf=None, n_gen=None):
        """Sample one token per lane through ``repro.sample`` (the single
        sampler entry point).  With no state: bit-exact greedy argmax."""
        # gather the (tiny) logit row off the vocab-sharded unembed output:
        # the sampler's ordered scans (sort, FADDA cumsum, Gumbel) must run
        # on a whole vocab row or their FP association order — and thus the
        # sampled token — would differ from the 1-device engine
        logits = SH.constrain(logits, ("batch",) + (None,) * (logits.ndim - 1))
        if sstate is None:
            return S.greedy_tokens(logits if self._ban is None else
                                   mask_logits(logits, self._ban[None, :]))
        return S.sample(logits, sstate, out_tokens=out_buf, n_out=n_gen,
                        ban=self._ban)

    def make_state(self, b: int, sampling=None) -> dict:
        """Batched sampler lane state for ``b`` lanes (falls back to the
        engine's ``default_sampling``, then to greedy)."""
        if isinstance(sampling, dict):
            return sampling
        return S.lane_state(self.default_sampling if sampling is None
                            else sampling, b)

    # ------------------------------------------------------------------
    # jitted decode loop
    # ------------------------------------------------------------------

    def _decode_chunk_impl(self, params, cache, out_buf, tok, p, n_gen,
                           lane_budget, sstate, *, n_steps: int,
                           stochastic: bool = True,
                           width: Optional[int] = None):
        """The decode hot loop as ONE XLA while: §2.3.4 dynamic exits.

        Every iteration decodes all lanes, but only the active partition
        commits tokens; a lane leaves the partition when it emits the stop
        token or its per-lane budget runs out.  ``n_steps`` caps the burst so
        the continuous-batching scheduler can admit queued requests between
        calls; ``generate`` passes n_steps = max_new_tokens and uniform
        budgets so the same loop serves both paths (bit-identity between the
        one-shot and scheduled engines follows by construction).

        ``sstate`` is the per-lane sampler state (``repro.sample``): keys
        split once per iteration for EVERY lane — a live lane's chain
        position therefore equals its committed token count, independent of
        chunk boundaries and co-scheduled traffic — and the whole processor
        pipeline (penalty gathers over the lane's own out_buf, top-k/top-p
        predicates, the ordered top-p cumsum) traces into this while-loop:
        no per-token host dispatch.  ``stochastic=False`` (a static flag the
        caller derives host-side: no live lane samples) compiles the legacy
        argmax-only body — greedy traffic pays zero pipeline cost and the
        sampler state passes through untouched, which is sound because a
        stochastic lane's key chain only needs to advance on steps it is
        live for, and every such step runs a stochastic=True chunk.
        Returns (cache, out_buf, tok, p, n_gen, sstate, steps_run).
        """
        return self._burst(params, cache, out_buf, tok, p, n_gen,
                           lane_budget, sstate, n_steps=n_steps,
                           stochastic=stochastic, width=width)

    @scope("decode")
    def _burst(self, params, cache, out_buf, tok, p, n_gen, lane_budget,
               sstate, *, n_steps: int, stochastic: bool,
               width: Optional[int]):
        """Run the decode burst, optionally NARROWED to the first ``width``
        lanes (a static pow2 bucket the scheduler derives from its host-side
        occupancy view: compaction keeps live lanes at the low indices, so
        the burst executes at the smallest bucket covering them — SVE
        predicate-narrowing applied to the batch axis).  Lanes at or above
        ``width`` are guaranteed inactive (p False) for the whole burst and
        pass through untouched, so per-lane results are bit-identical to the
        full-width burst — the scheduler only narrows families whose decode
        is lane-independent (``lane_independent_decode``).  jit-safe."""
        if width is None or width >= out_buf.shape[0]:
            return self._decode_loop(params, cache, out_buf, tok, p, n_gen,
                                     lane_budget, sstate, n_steps=n_steps,
                                     stochastic=stochastic)
        w = jnp.arange(width, dtype=jnp.int32)
        sub_cache = gather_lanes(self.cfg, cache, w)
        sub_state = S.gather_lanes(sstate, w)
        (sub_cache, sub_out, sub_tok, sub_p, sub_ngen, sub_state,
         steps) = self._decode_loop(
            params, sub_cache, out_buf[:width], tok[:width], p[:width],
            n_gen[:width], lane_budget[:width], sub_state,
            n_steps=n_steps, stochastic=stochastic)
        # merge_lanes (not slot_update): a narrowed PAGED burst scatter-
        # stored its tokens into the shared pools riding sub_cache
        cache = merge_lanes(self.cfg, cache, w, sub_cache)
        sstate = S.slot_update(sstate, w, sub_state)
        out_buf = out_buf.at[:width].set(sub_out)
        tok = tok.at[:width].set(sub_tok)
        p = p.at[:width].set(sub_p)
        n_gen = n_gen.at[:width].set(sub_ngen)
        return cache, out_buf, tok, p, n_gen, sstate, steps

    def _decode_loop(self, params, cache, out_buf, tok, p, n_gen,
                     lane_budget, sstate, *, n_steps: int, stochastic: bool):
        """The while-loop body shared by ``_decode_chunk`` and the fused
        serve step (identical trace, so the two compile the same loop)."""
        stop = self.stop_token
        b, max_out = out_buf.shape
        rows = jnp.arange(b)

        def loop_cond(carry):
            _, _, _, p, _, _, step = carry
            return jnp.any(p) & (step < n_steps)

        def loop_body(carry):
            cache, out_buf, tok, p, n_gen, sstate, step = carry
            logits, cache = self._cached_decode(params, {"token": tok[:, None]},
                                                cache)
            if stochastic:
                nxt, sstate = self._sample(logits, sstate, out_buf, n_gen)
            else:
                nxt = self._sample(logits)
            nxt = P.merging(p, nxt, jnp.full_like(nxt, stop))
            col = jnp.clip(n_gen, 0, max_out - 1)
            out_buf = out_buf.at[rows, col].set(
                jnp.where(p, nxt, out_buf[rows, col]))
            n_gen = n_gen + p.astype(jnp.int32)
            p = p & (nxt != stop) & (n_gen < lane_budget)
            return cache, out_buf, nxt, p, n_gen, sstate, step + 1

        cache, out_buf, tok, p, n_gen, sstate, steps = jax.lax.while_loop(
            loop_cond, loop_body,
            (cache, out_buf, tok, p, n_gen, sstate, jnp.int32(0)))
        return cache, out_buf, tok, p, n_gen, sstate, steps

    def _cached_decode(self, params, batch, cache):
        """One decode step against a dense OR paged cache.

        Paged "native" (default; "kernel" accepted as a legacy alias): the
        family's decode reads K/V through the page table inside flash
        attention and scatter-stores its new token straight into the lane's
        tail page — no dense-view materialization on the hot path.  Paged
        "gather" (the reference oracle): gather-load the dense view through
        the table, run the family's unchanged decode, scatter-store the new
        token back — bitwise equal to the dense engine because the view IS
        the dense cache.  All of it traces into the jitted decode loop.
        """
        if not is_paged(cache):
            return self.model.decode(params, self.cfg, batch, cache)
        if self.paged_attn != "gather":
            if not paged_decode_ok(self.cfg):
                # every pageable family decodes natively: a reference path
                # quietly standing in for the kernel path is a bug, not a
                # fallback (raised at trace time)
                raise NotImplementedError(
                    f"family '{self.cfg.family}' has no native paged decode; "
                    "select the gather oracle explicitly with "
                    "paged_attn='gather'")
            return self.model.decode(params, self.cfg, batch, cache)
        view = paged_view(self.cfg, cache)
        pos = view["pos"]
        logits, view = self.model.decode(params, self.cfg, batch, view)
        return logits, paged_writeback(self.cfg, cache, view, pos)

    # ------------------------------------------------------------------
    # fused serve step: prefill chunk(s) + admission + decode burst in ONE
    # dispatch (the scalar-loop-tail elimination applied to the host loop)
    # ------------------------------------------------------------------

    @scope("kv_write")
    def _seed_pages(self, cache, sub_cache, seed_tab, seed_len, max_len: int):
        """Gather resident shared-prefix pages of the live paged ``cache``
        into a dense prefill ``sub_cache`` (positions [0, seed_len) per row),
        so suffix rows attend over the donor's K/V.  jit-safe."""
        spec = self.model.paged_cache_spec(self.cfg)
        m = seed_tab.shape[0]
        mask = jnp.arange(max_len, dtype=jnp.int32)[None, :] < seed_len[:, None]
        sub_cache = dict(sub_cache)
        for key, lead in spec.items():
            # extending gather: a quantized cache's seed widens through the
            # scale pool, so the dense prefill sub-cache is full precision
            view = PG.gather_pages(cache[key + "_pages"], seed_tab,
                                   n_lead=len(lead),
                                   scale=cache.get(key + "_pages_scale"))
            mm = mask.reshape((1,) * len(lead) + (m, 1, max_len, 1))
            sub_cache[key] = jnp.where(mm, view.astype(sub_cache[key].dtype),
                                       sub_cache[key])
        return sub_cache

    @scope("kv_write")
    def _install_pages(self, cache, sub_cache, rows, cols, dsts, tab_rows,
                       lanes):
        """Scatter freshly prefilled K/V blocks ``(rows, cols)`` of the dense
        ``sub_cache`` into physical pages ``dsts`` of the live paged
        ``cache`` and install the page-table rows at ``lanes``.  Padding
        entries aim at the trash page / out-of-range lanes, which JAX
        scatters drop.  jit-safe."""
        spec = self.model.paged_cache_spec(self.cfg)
        cache = dict(cache)
        n_pages = cache["page_table"].shape[1]
        for key, lead in spec.items():
            dn = sub_cache[key]                     # lead+(m,Hkv,S,Dh)
            nl = len(lead)
            shp = dn.shape
            ps = shp[-2] // n_pages
            dnp = dn.reshape(shp[:nl + 2] + (n_pages, ps, shp[-1]))
            dnp = jnp.moveaxis(dnp, nl, 0)          # (m,)+lead+(Hkv,n,ps,D)
            dnp = jnp.moveaxis(dnp, nl + 2, 1)      # (m,n_pages)+lead+...
            blocks = dnp[rows, cols]                # (K,)+lead+(Hkv,ps,D)
            sc = cache.get(key + "_pages_scale")
            if sc is not None:                      # truncating store
                (cache[key + "_pages"],
                 cache[key + "_pages_scale"]) = PG.scatter_block_q(
                    cache[key + "_pages"], sc, dsts, blocks, n_lead=nl)
            else:
                cache[key + "_pages"] = PG.scatter_block(
                    cache[key + "_pages"], dsts, blocks, n_lead=nl)
        cache["page_table"] = cache["page_table"].at[lanes].set(tab_rows)
        return cache

    # ------------------------------------------------------------------
    # host-swap page movers (the scheduler's eviction tier)
    # ------------------------------------------------------------------

    def _gather_blocks_impl(self, cache, pids):
        """Batched whole-page read: for each pool (and scale pool) of the
        paged ``cache``, gather pages ``pids (K,)`` as ``(K,) + lead +
        (Hkv, ps[, D])`` blocks — the device->host half of a spill.  A
        quantized cache spills its NARROW bytes plus scales, so a later
        page-in restores the pool rows bit-exactly."""
        spec = self.model.paged_cache_spec(self.cfg)
        out = {}
        for key, lead in spec.items():
            for suffix in ("_pages", "_pages_scale"):
                pk = key + suffix
                if pk in cache:
                    out[pk] = PG.gather_block(cache[pk], pids,
                                              n_lead=len(lead))
        return out

    def _scatter_blocks_impl(self, cache, pids, blocks):
        """Batched whole-page write: scatter host-held ``blocks`` (the dict
        ``_gather_blocks`` produced) into pages ``pids`` — the page-in half
        of a swap.  Padding entries aim at the trash page."""
        spec = self.model.paged_cache_spec(self.cfg)
        cache = dict(cache)
        for key, lead in spec.items():
            for suffix in ("_pages", "_pages_scale"):
                pk = key + suffix
                if pk in cache:
                    cache[pk] = PG.scatter_block(cache[pk], pids, blocks[pk],
                                                 n_lead=len(lead))
        return cache

    # ------------------------------------------------------------------
    # preemption lane movers (the scheduler's bit-exact preempt/resume)
    # ------------------------------------------------------------------

    def _spill_lane_impl(self, cache, out_buf, tok, n_gen, budget, sstate,
                         lane):
        """Gather ONE lane's host-spillable state in a single dispatch: its
        dense per-lane cache carries (every key with a declared lane axis —
        page pools and the page table are excluded; their content moves
        through ``_gather_blocks``), its decode rows (out_buf/tok/n_gen/
        budget) and its sampler-state row.  Together with the lane's page
        blocks this is the complete request state: splicing it back resumes
        the token stream byte-exactly (the per-lane PRNG chain position is
        the committed token count, which rides ``n_gen``)."""
        lane = jnp.asarray(lane, jnp.int32)
        axes = self.model.cache_batch_axes(self.cfg)
        lc = gather_lanes(self.cfg, cache, lane)
        dense = {k: v for k, v in lc.items() if k in axes}
        row = {"out": out_buf[lane], "tok": tok[lane],
               "ngen": n_gen[lane], "budget": budget[lane]}
        return dense, row, S.gather_lanes(sstate, lane)

    def _resume_lane_impl(self, cache, out_buf, tok, p, n_gen, budget, sstate,
                          lane, dense, row, srow, table_row):
        """Splice a spilled lane back (the resume half of preemption): the
        dense carries slot_update into the lane (pool keys absent from
        ``dense`` pass through untouched), the rebuilt page-table row is
        installed when paged, and the decode/sampler rows are restored
        exactly as spilled — the lane continues as if never interrupted."""
        lane = jnp.asarray(lane, jnp.int32)
        cache = slot_update(self.cfg, cache, lane, dense)
        if table_row is not None:
            cache = dict(cache)
            cache["page_table"] = cache["page_table"].at[lane].set(table_row)
        sstate = S.slot_update(sstate, lane, srow)
        out_buf = out_buf.at[lane].set(row["out"])
        tok = tok.at[lane].set(row["tok"])
        n_gen = n_gen.at[lane].set(row["ngen"])
        budget = budget.at[lane].set(row["budget"])
        p = p.at[lane].set(True)
        return cache, out_buf, tok, p, n_gen, budget, sstate

    def _splice_admission(self, cache, out_buf, tok, p, n_gen, budget, sstate,
                          lanes, first_tok, sub_cache, sub_state, budgets,
                          info):
        """Replay the scheduler's admission tail inside the fused trace:
        page installs, cache/sampler slot_update, and the per-lane decode
        seeds.  ``lanes`` may carry out-of-range entries for padded rows —
        every ``.at[]`` scatter drops them, which is how dummy-row trimming
        happens without a host round-trip."""
        if "copy_dsts" in info:
            cache = self._install_pages(cache, sub_cache, info["copy_rows"],
                                        info["copy_cols"], info["copy_dsts"],
                                        info["tab_rows"], lanes)
        cache = slot_update(self.cfg, cache, lanes, sub_cache)
        sstate = S.slot_update(sstate, lanes, sub_state)
        tok = tok.at[lanes].set(first_tok)
        out_buf = out_buf.at[lanes].set(0)
        out_buf = out_buf.at[lanes, 0].set(first_tok)
        n_gen = n_gen.at[lanes].set(1)
        budget = budget.at[lanes].set(budgets)
        alive = (first_tok != self.stop_token) & (budgets > 1)
        p = p.at[lanes].set(alive)
        return cache, out_buf, tok, p, n_gen, budget, sstate

    def _fused_step_impl(self, params, cache, out_buf, tok, p, n_gen, budget,
                         sstate, admit, parts, *, n_steps: int,
                         stochastic: bool, admit_stoch: bool,
                         part_final: tuple, part_stoch: tuple, max_len: int,
                         width: Optional[int] = None):
        """ONE dispatch for a whole scheduling round: the round's chunked-
        prefill chunk(s), the admission sub-batch (zero-init -> prefix seed
        -> prefill -> first-token sample -> page install -> lane splice), and
        an ``n_steps`` decode burst — the same ops the legacy host loop
        issued as separate dispatches, in the same order, now fused so the
        host touches the device once per round.

        ``admit`` is None or a dict of device arrays assembled host-side
        (batch / lanes / budgets / sampler rows / page-copy plan); ``parts``
        is a tuple of per-partial dicts (batch + accumulating sub-cache,
        plus splice data when the chunk is final).  ``part_final`` /
        ``part_stoch`` are static per-partial flags.  Returns
        (cache, out_buf, tok, p, n_gen, budget, sstate, steps_run,
        new_caches-of-non-final-partials).
        """
        (cache, out_buf, tok, p, n_gen, budget, sstate,
         new_part_caches) = self._fused_prefill(
            params, cache, out_buf, tok, p, n_gen, budget, sstate, admit,
            parts, admit_stoch=admit_stoch, part_final=part_final,
            part_stoch=part_stoch, max_len=max_len)
        cache, out_buf, tok, p, n_gen, sstate, steps = self._burst(
            params, cache, out_buf, tok, p, n_gen, budget, sstate,
            n_steps=n_steps, stochastic=stochastic, width=width)
        return (cache, out_buf, tok, p, n_gen, budget, sstate, steps,
                tuple(new_part_caches))

    @scope("prefill")
    def _fused_prefill(self, params, cache, out_buf, tok, p, n_gen, budget,
                       sstate, admit, parts, *, admit_stoch, part_final,
                       part_stoch, max_len):
        """The fused round's prefill phase: the chunked-prefill chunks, then
        the admission sub-batch, each spliced into its lanes."""
        new_part_caches = []
        for i, part in enumerate(parts):
            sub_in = part["cache"]
            if "seed_tab" in part:
                # first chunk of a prefix-shared partial: the donor's page
                # install has executed by this point in the trace
                sub_in = self._seed_pages(cache, sub_in, part["seed_tab"],
                                          part["seed_len"], max_len)
            logits, sub = self.model.prefill(params, self.cfg, part["batch"],
                                             sub_in)
            if not part_final[i]:
                new_part_caches.append(sub)
                continue
            if part_stoch[i]:
                first, sub_state = self._sample(logits, part["sub_state"])
            else:
                first = self._sample(logits)
                sub_state = part["sub_state"]
            (cache, out_buf, tok, p, n_gen, budget,
             sstate) = self._splice_admission(
                cache, out_buf, tok, p, n_gen, budget, sstate, part["lane"],
                first, sub, sub_state, part["budget"], part)
        if admit is not None:
            batch = admit["batch"]
            m = batch["tokens"].shape[0]
            # fresh zeros inside the trace: pin their serve placement so
            # GSPMD doesn't materialise them replicated (identity unsharded)
            sub_cache = DS.constrain_cache(self.cfg,
                                           self.make_cache(m, max_len, batch))
            if "seed_tab" in admit:
                sub_cache = self._seed_pages(cache, sub_cache,
                                             admit["seed_tab"],
                                             admit["seed_len"], max_len)
            logits, sub_cache = self.model.prefill(params, self.cfg, batch,
                                                   sub_cache)
            if admit_stoch:
                first, sub_state = self._sample(logits, admit["sub_state"])
            else:
                first = self._sample(logits)
                sub_state = admit["sub_state"]
            (cache, out_buf, tok, p, n_gen, budget,
             sstate) = self._splice_admission(
                cache, out_buf, tok, p, n_gen, budget, sstate,
                admit["lanes"], first, sub_cache, sub_state,
                admit["budgets"], admit)
        return (cache, out_buf, tok, p, n_gen, budget, sstate,
                new_part_caches)

    # ------------------------------------------------------------------
    # one-shot batch API
    # ------------------------------------------------------------------

    def make_paged_cache(self, b: int, max_len: int, *, page_size: int,
                         pool_pages: int, batch: Optional[dict] = None,
                         src_len: Optional[int] = None):
        """Allocate a paged cache: shared page pools + per-lane page table
        (narrow pools + scale pools when the engine has a ``page_dtype``)."""
        if self.cfg.family == "encdec":
            sl = src_len if src_len is not None else batch["src_emb"].shape[1]
            return self.model.make_paged_cache(
                self.cfg, b, max_len, src_len=sl,
                page_size=page_size, pool_pages=pool_pages,
                page_dtype=self.page_dtype)
        return self.model.make_paged_cache(self.cfg, b, max_len,
                                           page_size=page_size,
                                           pool_pages=pool_pages,
                                           page_dtype=self.page_dtype)

    def make_cache(self, b: int, max_len: int, batch: Optional[dict] = None,
                   src_len: Optional[int] = None):
        """Allocate a cache for ``b`` request lanes (family-dispatched).
        encdec sizes its cross-attention memory from ``batch["src_emb"]`` or
        an explicit ``src_len`` (the scheduler's batch-free allocations)."""
        if self.cfg.family == "encdec":
            sl = src_len if src_len is not None else batch["src_emb"].shape[1]
            return self.model.make_cache(self.cfg, b, max_len, src_len=sl)
        if self.cfg.family == "ssm":
            return self.model.make_cache(self.cfg, b)
        return self.model.make_cache(self.cfg, b, max_len)

    def generate(self, batch, *, max_len: Optional[int] = None,
                 sampling=None, page_size: Optional[int] = None,
                 pool_pages: Optional[int] = None):
        """batch: {"tokens": (B, S) prompts, "lens": (B,)} (+ modality extras).

        ``sampling`` is None (engine default / greedy), one ``SamplingParams``
        broadcast over lanes, a per-lane sequence of them, or a pre-built
        lane state dict.  With ``page_size`` set the prefilled cache is
        converted to the PAGED layout (identity page tables) before the
        decode loop runs — the one-shot road into native paged decode for
        families the scheduler does not manage (encdec, vlm); the prefill
        itself stays dense, so this is a decode-path bridge, not a
        memory-saving admission path.  Returns dict with tokens (B, max_new),
        n_generated (B,), and the final active partition (all-False when
        every lane exited).
        """
        tokens = batch["tokens"]
        b, s = tokens.shape
        lens = jnp.asarray(batch.get("lens", jnp.full((b,), s)), jnp.int32)
        max_len = max_len or (s + self.max_new_tokens)
        cache = self.make_cache(b, max_len, batch)
        sstate = self.make_state(b, sampling)

        with self.obs.span("prefill", b=b, s=s):
            logits, cache = self._prefill(self.params,
                                          dict(batch, lens=lens), cache)
        if page_size is not None:
            cache = to_paged(self.cfg, cache, page_size=page_size,
                             pool_pages=pool_pages,
                             page_dtype=self.page_dtype)
        # all-greedy batches skip the stochastic pipeline here too (keys of
        # greedy lanes are never read, so not splitting them is inert)
        if S.is_all_greedy(sstate):
            first_tok = self._sample(logits)
        else:
            first_tok, sstate = self._sample(logits, sstate)

        max_new = self.max_new_tokens
        out = jnp.zeros((b, max_new), jnp.int32)
        out = out.at[:, 0].set(first_tok)
        budget = jnp.full((b,), max_new, jnp.int32)
        p0 = (first_tok != self.stop_token) & (budget > 1)
        # ---- single dispatch: the whole decode loop runs inside XLA ----
        with self.obs.span("decode", b=b, n_steps=max_new):
            cache, out, tok, _, n_gen, _, _ = self._decode_chunk(
                self.params, cache, out, first_tok, p0,
                jnp.ones((b,), jnp.int32), budget, sstate, n_steps=max_new,
                stochastic=not S.is_all_greedy(sstate))
        p = tok != self.stop_token                  # lanes that never exited
        return {"tokens": out, "n_generated": n_gen, "active": p,
                "cache": cache}
