"""The model's parts and phases named on the device: every scope reaches the
compiled round programs' metadata, and the scope map the profile is read by
covers every instruction that carries one."""

import json
import pathlib
import re

import jax
import numpy as np
import pytest

from bench import model as M
from bench import program as P
from bench import scope_profile as SPF
from bench import scopes as S
from repro.models import get_model
from repro.obs.scopes import PARTS, PHASES, SCOPES, scope_of
from repro.serve import ServeEngine

CONFIGS = pathlib.Path(__file__).resolve().parents[2] / "bench" / "configs"
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            vocab_size=256)
SERVER = {"capacity": 2, "page_size": 8, "pool_pages": 24, "decode_chunk": 3,
          "prefill_chunk": None, "max_len": 48}


def _round_program_texts(config, heads, kv_heads):
    """Compiled texts of every round program a short served backlog calls,
    for a tiny cut of ``config``."""
    conf = dict(json.loads((CONFIGS / f"{config}.json").read_text()),
                num_attention_heads=heads, num_key_value_heads=kv_heads,
                **TINY)
    conf["dtype"] = "float32"
    cfg = M.program_config(conf)
    params, _ = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_new_tokens=8, stop_token=-1)
    watch = SPF.Watch()
    watch.watch(eng)
    watch.recording = True
    sched = P.make_scheduler(eng, SERVER)
    rng = np.random.default_rng(0)
    for i in range(5):
        sched.submit(rng.integers(1, 200, int(rng.integers(5, 20))),
                     max_new_tokens=int(rng.integers(3, 9)), arrival=2.0 * i)
    sched.run()
    fns = {fn for fn, _ in watch.calls}
    assert fns == set(SPF.ROUND_FNS)            # both round programs ran
    return [watch.compiled_text(k) for k in watch.signatures]


@pytest.fixture(scope="module", params=[("stablelm_3b", 4, 4),
                                        ("stablelm_12b_pp4", 4, 2)],
                ids=["mha", "gqa_parallel"])
def texts(request):
    return _round_program_texts(*request.param)


def _instructions(text):
    """``(name, op_name)`` of each instruction line with an op_name."""
    out = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and " = " in line:
            name = line.split(" = ")[0].split()[-1].lstrip("%")
            out.append((name, m.group(1)))
    return out


def test_every_scope_reaches_the_compiled_metadata(texts):
    seen = {c for t in texts for _, op in _instructions(t)
            for c in op.split("/")}
    assert set(SCOPES) <= seen


def test_scope_map_covers_every_scoped_instruction(texts):
    for text in texts:
        smap = S.scope_map(text)
        scoped = [(n, op) for n, op in _instructions(text)
                  if set(op.split("/")) & set(SCOPES)]
        assert scoped
        for name, op in scoped:
            assert smap[name] == list(scope_of(op)), (name, op)


def test_scope_of_takes_outer_phase_and_inner_part():
    assert scope_of("jit(f)/decode/while/body/mlp/dot_general") == (
        "decode", "mlp")
    assert scope_of("jit(f)/prefill/while/body/attn_proj/x/attn_core/y") == (
        "prefill", "attn_core")
    assert scope_of("jit(f)/while/body/add") == (None, None)
    assert set(PHASES).isdisjoint(PARTS)


def test_innermost_charges_each_stretch_once():
    # a while (0..100) holding two ops and a gap; an op that outlasts it;
    # two ops with the same interval
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 40, 60),
           ("late", 90, 120), ("x", 200, 210), ("y", 200, 210)]
    got = S.innermost(ops)
    assert got == {"while": 100 - 20 - 20 - 10, "a": 20, "b": 20,
                   "late": 30, "y": 10, "x": 0}
    assert sum(got.values()) == 120 + 10            # the union


# ----------------------------------------------------------------------
# a small trace recorded on a v5e chip: the tests' tiny cell run traced by
# bench/scope_profile.py, with the scope maps of its program runs
# ----------------------------------------------------------------------

SCOPED = pathlib.Path(__file__).resolve().parents[2] / "bench" / \
    "fixtures" / "scoped"


@pytest.fixture(scope="module")
def scoped():
    trace = S.load(SCOPED)
    maps = json.loads((SCOPED / "scoped.maps.json").read_text())
    return trace, maps, S.reduce(trace, maps)


def test_scoped_parts_sum_to_busy(scoped):
    _, _, red = scoped
    total = sum(t for _, _, t in red["scopes"])
    assert total == pytest.approx(red["busy_s"], rel=0.01)
    assert sum(red["part_s"].values()) == pytest.approx(total, rel=1e-9)
    # a 2-layer toy: the decode loop's own bookkeeping (lane predicates,
    # output buffer updates) holds a sixth; the model's parts the rest
    assert 0.0 < red["uncovered_share"] < 0.25
    assert {pa for _, pa, _ in red["scopes"]} >= {"attn_proj", "attn_core",
                                                  "kv_write", "mlp"}


def test_scoped_idle_gaps_are_named_by_program_spans(scoped):
    trace, _, red = scoped
    gaps = dict(red["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    assert set(gaps) & {"round", "plan", "admit", "dispatch", "harvest",
                        "sync"}
    assert {n for n, _, _ in trace["spans"]} >= {"round", "dispatch", "sync"}
    assert 0.0 < red["host_round_share"] < 1.0


def test_scoped_maps_name_the_round_programs(scoped):
    trace, maps, _ = scoped
    runs = {n for ms in trace["modules"].values() for n, _, _ in ms}
    assert maps and set(maps) <= runs
    assert all(n.split("(")[0] in P.ROUND_PROGRAMS for n in maps)


def test_compiler_added_instructions_count_with_their_user():
    text = "\n".join([
        '  %copy-start.1 = (bf16[8]) copy-start(bf16[8] %w.1)',
        '  %copy-done.1 = bf16[8] copy-done((bf16[8]) %copy-start.1)',
        '  %fusion.2 = bf16[8] fusion(bf16[8] %copy-done.1), kind=kLoop, '
        'calls=%fc.2, metadata={op_name="jit(f)/decode/while/body/mlp/dot" '
        'stack_frame_id=3}',
        '  %copy.3 = f32[4] copy(f32[4] %x.1)',
        '  %while.4 = (f32[4]) while(f32[4] %copy.3), condition=%c, body=%b, '
        'metadata={op_name="jit(f)/decode/while"}',
        '  %add.5 = f32[4] add(f32[4] %y.1, f32[4] %y.1), '
        'metadata={op_name="jit(f)/while/body/add"}',
    ])
    assert S.scope_map(text) == {
        "copy-start.1": ["decode", "mlp"], "copy-done.1": ["decode", "mlp"],
        "fusion.2": ["decode", "mlp"], "copy.3": ["decode", None],
        "while.4": ["decode", None]}
