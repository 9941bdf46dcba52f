"""The one traffic generator: a traffic file fixes the request list, the seed
fills in the token ids.

A traffic file (``bench/traffic/<name>.json``) gives the number of requests
and, for prompt and output lengths each, a distribution with a range.  The
lengths are the distribution's values at the quantiles ``(i + 0.5) / n`` of
the range, so every octave of the range gets its stated share, and a fixed
permutation (``order_seed`` in the file) sets each request's position in the
list.  ``--seed`` never touches any of that: it draws only the prompt token
ids, so every seed of a cell asks for exactly the same work.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    prompt_len: int
    budget: int                 # output tokens the request must produce


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of the distribution
    ``spec`` truncated to ``[min, max]``, in ascending order."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 1 <= lo <= hi:
        raise ValueError(f"length range [{lo}, {hi}] is empty")
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "uniform":
        x = lo + q * (hi - lo)
    elif dist == "loguniform":
        x = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x).astype(np.int64), lo, hi)


def request_list(traffic: dict) -> list[Request]:
    """The fixed request list of a traffic file: the same for every seed."""
    if traffic["kind"] != "backlog":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    n = int(traffic["requests"])
    order = np.random.RandomState(int(traffic["order_seed"]))
    prompts = order.permutation(quantile_lengths(traffic["prompt"], n))
    outputs = order.permutation(quantile_lengths(traffic["output"], n))
    return [Request(int(p), int(o)) for p, o in zip(prompts, outputs)]


def prompt_tokens(requests: list[Request], vocab_size: int,
                  seed: int) -> list[np.ndarray]:
    """Token ids of every prompt, drawn from ``seed`` alone: ids in
    ``[1, vocab_size)``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab_size, r.prompt_len, dtype=np.int32)
            for r in requests]
