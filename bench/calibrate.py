#!/usr/bin/env python3
"""Readings that a cell's ``logit_gap_limit`` is set from, on the chip.

    python3 bench/calibrate.py --workload stablelm_3b.decode_backlog \\
        --seeds 11,12,13 --seconds 20 [--control] [--out FILE]

Runs the cell once per seed in one process (the compiled programs are
loaded once), each run exactly as ``run.py`` makes it, and prints one JSON
line per seed: the program's ``logit_gap`` (the lower reading comes from
sound runs over a dozen seeds or more) and, with ``--control``, the float8
control's gap on the same sample (its least reading is the upper one).  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import harness

    t = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(args.workload, seed, args.seconds, False, t,
                          control=args.control)
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "correct": out["correct"],
                           "attempted": out["attempted"],
                           "check": out["check"],
                           "control_gap": out.get("control_gap"),
                           "metrics": out["metrics"],
                           "memory_peak_bytes":
                               out["device"]["memory_peak_bytes"]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        gc.collect()
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
