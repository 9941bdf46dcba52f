"""A cell made only of data files, at a size the CPU runs in seconds: the
tests write it into a temporary checkout root and run it by name."""

import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
WORKLOAD = "tiny.backlog"
LIMIT = 0.03

CONFIG = dict(
    json.loads((ROOT / "bench" / "configs" / "stablelm_12b_pp4.json")
               .read_text()),
    name="tiny", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=2,
    vocab_size=256, reduced=[])
TRAFFIC = {"kind": "backlog", "requests": 60, "order_seed": 3,
           "prompt": {"dist": "loguniform", "min": 8, "max": 24},
           "output": {"dist": "uniform", "min": 6, "max": 16}}
SERVER = {"capacity": 2, "page_size": 8, "pool_pages": 24, "decode_chunk": 4,
          "prefill_chunk": None, "ramp_steps": 8, "trace_rounds": 2,
          "check_tokens": 24,
          "logit_gap_limit": LIMIT}


def write_cell(root: pathlib.Path) -> pathlib.Path:
    """BENCHMARK.json and the cell's three data files under ``root``."""
    for sub in ("configs", "traffic", "cells"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (root / "bench" / "traffic" / "backlog.json").write_text(
        json.dumps(TRAFFIC))
    (root / "bench" / "cells" / f"{WORKLOAD}.json").write_text(
        json.dumps(SERVER))
    bench = {
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": WORKLOAD, "config": "tiny",
                       "traffic": "backlog", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "out_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.03, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "lane_occupancy.decode", "unit": "%",
             "better": "higher", "source": "program_counter",
             "layer": "scheduler", "moves": "out_tok_s"}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
