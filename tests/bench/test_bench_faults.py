"""The check catches a timed path broken underneath: a run of the tiny cell
with the fault planted in the engine must come out not correct."""

import time

import pytest

from bench import harness

from bench_tiny_cell import CONFIG, WORKLOAD, write_cell


def token_altered(eng):
    """Every sampled token is moved to the next id where it is produced."""
    sample = eng._sample

    def bad(logits, sstate=None, out_buf=None, n_gen=None):
        r = sample(logits, sstate, out_buf, n_gen)
        if isinstance(r, tuple):
            return (r[0] + 1) % CONFIG["vocab_size"], r[1]
        return (r + 1) % CONFIG["vocab_size"]

    eng._sample = bad


def state_unchanged(eng):
    """A decode step returns the cache it was given: no K/V written, no
    position advanced."""
    decode = eng._cached_decode

    def bad(params, batch, cache):
        logits, _ = decode(params, batch, cache)
        return logits, cache

    eng._cached_decode = bad


@pytest.mark.parametrize("fault", [token_altered, state_unchanged],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_not_correct(fault, tmp_path):
    root = write_cell(tmp_path)
    out = harness.run(WORKLOAD, 31_415_926_535, 0.3, False,
                      time.perf_counter(), root=root, require_chip=False,
                      fault=fault)
    assert not out["correct"]
    assert out["check"]["logit_gap"]["value"] > out["check"]["logit_gap"][
        "limit"]
