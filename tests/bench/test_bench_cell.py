"""A cell made only of new data files, found and run by name on the CPU:
its window ends on a synced round boundary, its rate divides by the
window's measured seconds, nothing compiles inside it, the schedule is the
host replay's, and the served tokens pass the reference while the float8
control does not."""

import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness

from bench_tiny_cell import LIMIT, ROOT, WORKLOAD, write_cell

SECONDS = 0.3


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    root = write_cell(tmp_path_factory.mktemp("checkout"))
    seen = {}
    window = harness.run_window

    def spy(*args, **kwargs):
        seen["window"] = window(*args, **kwargs)
        seen["sched"] = args[1]
        return seen["window"]

    harness.run_window = spy
    try:
        out = harness.run(WORKLOAD, 2_718_281_828, SECONDS, False,
                          time.perf_counter(), root=root, require_chip=False,
                          control=True)
    finally:
        harness.run_window = window
    return out, seen["window"], seen["sched"]


def test_new_cell_runs_by_name_and_is_correct(run):
    out, win, _ = run
    assert out["correct"], out["check"]
    assert out["attempted"] == len(win.finished) > 0
    assert set(out["metrics"]) == {"out_tok_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"


def test_rate_divides_by_the_measured_window_to_a_round_boundary(run):
    out, win, sched = run
    assert win.seconds >= SECONDS
    assert win.last_round > win.first_round
    assert sched._stash is None                  # the last round was synced
    assert out["metrics"]["out_tok_s"]["value"] == pytest.approx(
        win.out_tokens / win.seconds)
    assert out["check"]["counters_off_replay"]["value"] == 0


def test_no_compiles_no_drain_no_early_stop(run):
    out, _, _ = run
    for name in ("window_compiles", "backlog_drained", "ended_before_budget"):
        assert out["check"][name]["value"] == 0, name


def test_control_fails_the_limit_the_program_passes(run):
    out, _, _ = run
    assert out["check"]["logit_gap"]["value"] <= LIMIT
    assert out["control_gap"] > LIMIT


def _bench_run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm_3b.decode_backlog", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _bench_run(ROOT, env)
    assert r.returncode == 3 and r.stdout == ""


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _bench_run(tmp_path, env)
    assert r.returncode != 0 and r.stdout == ""
