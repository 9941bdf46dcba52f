"""The matmul / attention split of a round's work: it sums to the round's
count, and each part's roofline share reads its own scopes' time."""

import json
import pathlib

import pytest

from bench import parts as PT
from bench import work as W
from bench.schedule import RoundWork

CONFIGS = pathlib.Path(__file__).resolve().parents[2] / "bench" / "configs"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

ROUNDS = {
    "prefill": RoundWork("fused", [], [[(1983, 0), (1024, 0)], [(77, 16)]]),
    "decode": RoundWork("decode", [(4, 2000), (4, 2004), (3, 1500)], []),
    "mixed": RoundWork("fused", [(8, 9000), (8, 9008)], [[(3840, 0)]]),
}


def dims(name):
    return W.Dims.of(json.loads((CONFIGS / f"{name}.json").read_text()))


@pytest.mark.parametrize("name", ["stablelm_3b", "stablelm_12b_pp4"])
@pytest.mark.parametrize("kind", sorted(ROUNDS))
def test_round_parts_sum_to_round_counts(name, kind):
    d, rw = dims(name), ROUNDS[kind]
    parts = PT.round_parts(d, rw)
    assert set(parts) == set(PT.DEVICE_PARTS)
    flops, nbytes = W.round_counts(d, rw)
    assert sum(f for f, _ in parts.values()) == flops
    assert sum(b for _, b in parts.values()) == nbytes


def test_decode_step_parts_by_hand():
    d = dims("stablelm_3b")
    parts = PT.round_parts(d, RoundWork("decode", [(4, 2000)], []))
    # attention: 4 x 32 heads x 80 x 32 layers per key, 2000 keys; K/V of
    # 2000 read and 4 written
    assert parts["attention"] == (4 * 32 * 80 * 32 * 2000,
                                  2004 * d.kv_bytes_per_token)
    assert parts["matmul"][1] == d.pass_weight_bytes + 4 * 2560 * 2


def test_roofline_shares_read_their_own_scopes():
    d = dims("stablelm_3b")
    work = [ROUNDS["decode"]]
    least = {p: W.least_seconds(*PT.round_parts(d, work[0])[p], PEAK)
             for p in PT.DEVICE_PARTS}
    part_s = {"attn_proj": 0.01, "mlp": 0.02, "logits": 0.005,
              "embed": 0.005, "attn_core": 0.03, "kv_write": 0.01,
              "sample": 9.0, "unscoped": 9.0}
    shares = PT.roofline_shares(d, work, PEAK, part_s)
    assert shares["matmul"] == pytest.approx(100 * least["matmul"] / 0.04)
    assert shares["attention"] == pytest.approx(
        100 * least["attention"] / 0.04)
    assert PT.roofline_shares(d, work, PEAK, {})["matmul"] is None
