"""Every seed of a cell asks for the same work: the request list is fixed by
the traffic file, the seed draws only token ids."""

import json
import pathlib

import numpy as np
import pytest

from bench import traffic as T

TRAFFIC = pathlib.Path(__file__).resolve().parents[2] / "bench" / "traffic"


@pytest.mark.parametrize("name", ["decode_backlog", "code_backlog"])
def test_two_seeds_one_request_list(name):
    traffic = json.loads((TRAFFIC / f"{name}.json").read_text())
    a, b = T.request_list(traffic), T.request_list(traffic)
    assert a == b and len(a) == traffic["requests"]
    lens = [r.prompt_len for r in a]
    assert min(lens) >= traffic["prompt"]["min"]
    assert max(lens) <= traffic["prompt"]["max"]
    outs = [r.budget for r in a]
    assert min(outs) >= traffic["output"]["min"]
    assert max(outs) <= traffic["output"]["max"]
    seeds = (2_718_281_828, 4_123_456_789)     # past 32 signed bits
    p1, p2 = (T.prompt_tokens(a, 50304, s) for s in seeds)
    assert [len(x) for x in p1] == [len(x) for x in p2] == lens
    assert any((x != y).any() for x, y in zip(p1, p2))
    again = T.prompt_tokens(a, 50304, seeds[0])
    assert all((x == y).all() for x, y in zip(p1, again))


def test_quantiles_of_a_log_uniform_range():
    x = T.quantile_lengths({"dist": "loguniform", "min": 64, "max": 256}, 4)
    # quantiles 1/8, 3/8, 5/8, 7/8 of log2 in [6, 8]
    want = np.rint(2.0 ** (6 + 2 * np.array([1, 3, 5, 7]) / 8))
    assert x.tolist() == want.astype(int).tolist()


def test_unknown_kinds_are_refused():
    with pytest.raises(ValueError):
        T.quantile_lengths({"dist": "zipf", "min": 1, "max": 2}, 3)
    with pytest.raises(ValueError):
        T.request_list({"kind": "poisson"})
