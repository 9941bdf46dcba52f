"""The work function against counts made by hand from the configurations."""

import json
import pathlib

import pytest

from bench import work as W

CONFIGS = pathlib.Path(__file__).resolve().parents[2] / "bench" / "configs"


def dims(name):
    return W.Dims.of(json.loads((CONFIGS / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,params,kv_bytes", [
    # 32 x (4 x 2560^2 + 3 x 2560 x 6912) + 2 x 50304 x 2560
    ("stablelm_3b", 2_795_110_400, 2 * 32 * 32 * 80 * 2),
    # 10 x (2 x 5120^2 + 2 x 5120 x 1280 + 3 x 5120 x 13824)
    #   + 2 x 100352 x 5120
    ("stablelm_12b_pp4", 3_806_330_880, 2 * 10 * 8 * 160 * 2),
])
def test_matrix_params_and_kv_bytes(name, params, kv_bytes):
    d = dims(name)
    assert d.layers * d.layer_matrix_params + 2 * d.vocab * d.hidden == params
    assert d.kv_bytes_per_token == kv_bytes


def test_decode_step_by_hand():
    d = dims("stablelm_3b")
    # 2 lanes at contexts 100 and 300
    flops, nbytes = W.decode_step(d, 2, 400)
    matrix = 32 * (4 * 2560 * 2560 + 3 * 2560 * 6912)
    assert flops == 2 * (2 * matrix + 2 * 2560 * 50304) + 4 * 32 * 80 * 32 * 400
    weights = 2 * (matrix + 32 * 4 * 2560 + 2 * 2560 + 2560 * 50304)
    assert nbytes == weights + 2 * 2560 * 2 + 402 * 327_680


def test_prefill_row_by_hand():
    d = dims("stablelm_12b_pp4")
    # one row: 3 tokens after 5 cached ones attend to 6, 7 and 8 keys
    flops, nbytes = W.prefill_pass(d, [(3, 5)])
    matrix = 10 * (2 * 5120 * 5120 + 2 * 5120 * 1280 + 3 * 5120 * 13824)
    assert flops == (2 * matrix * 3 + 4 * 32 * 160 * 10 * 21
                     + 2 * 5120 * 100352)
    weights = 2 * (matrix + 10 * 4 * 5120 + 2 * 5120 + 5120 * 100352)
    assert nbytes == weights + 8 * 51_200 + 3 * 5120 * 2
    assert W.prefill_pass(d, []) == (0.0, 0.0)


def test_least_time_takes_the_tighter_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert W.least_seconds(1000.0, 50.0, peak) == 10.0
    assert W.least_seconds(100.0, 50.0, peak) == 5.0
