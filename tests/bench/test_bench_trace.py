"""The trace reduction on a small trace recorded on a v5e chip: two program
runs of a toy model inside a ``bench:window`` span, one before it."""

import pathlib

import pytest

from bench import trace as T

FIXTURE = pathlib.Path(__file__).resolve().parents[2] / "bench" / \
    "fixtures" / "trace"


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(T.load(FIXTURE))


def test_busy_is_the_union_of_ops_inside_the_window(reduced):
    # the ten operations that start inside the window, by hand: the fused
    # step's six and the second decode chunk's four (ns); none overlap.  The
    # window runs from the first one's start (53824496 ns) to the last one's
    # end (60319122 + 6348), not over the whole bench:window span
    inside = [13, 3, 6459, 5780, 5781, 6099, 13, 3, 6455, 6348]
    window = 60319122 + 6348 - 53824496
    assert reduced["busy_s"] == pytest.approx(sum(inside) / 1e9, abs=1e-12)
    assert reduced["window_s"] == pytest.approx(window / 1e9, abs=1e-12)
    assert reduced["idle_share"] == pytest.approx(
        1 - sum(inside) / window, rel=1e-12)


def test_idle_share_of_each_half_of_the_window(reduced):
    # first half: the fused step's six ops; second: the decode chunk's four
    window = 60319122 + 6348 - 53824496
    first, second = 13 + 3 + 6459 + 5780 + 5781 + 6099, 13 + 3 + 6455 + 6348
    assert reduced["idle_share_halves"] == pytest.approx(
        [1 - first / (window / 2), 1 - second / (window / 2)], rel=1e-9)


def test_top_ops_sum_device_time_per_name(reduced):
    top = dict(reduced["top_ops"])
    assert reduced["top_ops"][0] == ["fusion.7", pytest.approx(6459e-9)]
    # two copy-start events of 13 ns each inside the window
    assert top["copy-start"] == pytest.approx(26e-9)
    assert "jit__decode_chunk_impl" not in top


def test_idle_gaps_are_named_by_the_host_span_around_them(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)
    # the long waits fall while the host was in its observe spans
    assert max(gaps, key=gaps.get) == "bench:observe"
    start, length, name = reduced["longest_gaps"][0]
    assert name == "bench:observe"
    assert start == pytest.approx((53824496 + 24138 - 53824496) / 1e9)
    assert length == pytest.approx((60312647 - 53848634) / 1e9)


def test_program_runs_inside_the_window_in_order(reduced):
    names = [m[0] for m in reduced["modules"]]
    assert names == ["jit__fused_step_impl", "jit__decode_chunk_impl"]
    assert reduced["modules"][0][2] == pytest.approx(24147e-9)


def test_union_merges_overlaps_only():
    assert T.union([(0, 5), (3, 8), (9, 10)]) == [[0, 8], [9, 10]]
    assert T.union([]) == []


def test_trace_checks_catch_an_unread_metric_and_a_renamed_program():
    from types import SimpleNamespace as NS

    from bench import check as CK
    from bench import schedule as SC

    work = [SC.RoundWork("decode", [(4, 100)], [])]
    red = {"modules": [("jit__decode_chunk_impl", 0.0, 0.04)]}
    ctx = NS(window=NS(trace=NS(first_round=0, last_round=1, reduced=red)),
             plan=NS(replay=NS(work=work)))
    listed = [{"name": "a"}, {"name": "b"}]
    assert all(c["value"] == 0
               for c in CK.trace_checks(listed, {"a": 1, "b": 2}, ctx))
    red["modules"] = [("jit__decode_chunk_v2", 0.0, 0.04)]
    got = {c["name"]: c["value"]
           for c in CK.trace_checks(listed, {"a": 1}, ctx)}
    assert got == {"per_layer_unread": 1, "round_programs_unfound": 1}


def test_decode_step_ms_holds_when_two_program_runs_show_as_one():
    from types import SimpleNamespace as NS

    from bench import measure as MX
    from bench import schedule as SC

    work = [SC.RoundWork("decode", [(4, 100)] * 8, [])] * 3
    runs = [("jit__decode_chunk_impl", 0.0, 0.32)] * 3
    ctx = NS(window=NS(trace=NS(first_round=0, last_round=3,
                                reduced={"modules": runs})),
             plan=NS(replay=NS(work=work)))
    assert MX.decode_step_ms(ctx) == pytest.approx(40.0)
    # the profiler merged the last two runs into one event
    ctx.window.trace.reduced["modules"] = [runs[0], (runs[0][0], 0.32, 0.64)]
    assert MX.decode_step_ms(ctx) == pytest.approx(40.0)
