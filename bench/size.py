#!/usr/bin/env python3
"""Size a cell for one v5e chip without the chip: compile each program its
schedule calls for a described TPU and print what ``memory_analysis`` says.

    JAX_PLATFORMS=cpu python3 bench/size.py --workload stablelm_3b.decode_backlog

The page pool of a cell is the memory left after the weights and the largest
program's temporaries, less a stated margin.  A compile that passes here is
no chip run: it says nothing of times.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), p) for p in ("", "src")]

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402


def abstract_server(plan):
    """Engine and the shapes of the scheduler's state (for AOT sizing)."""
    import jax
    from repro.serve import ServeEngine

    from bench import program as P

    eng = ServeEngine(plan.cfg, None,
                      max_new_tokens=plan.server["max_new_tokens"],
                      stop_token=plan.server["stop_token"])
    state = jax.eval_shape(
        lambda: P.state_of(P.make_scheduler(eng, plan.server)))
    return eng, state


def call_weight(call) -> int:
    """Prefill rows x padded width of a call: the largest programs first."""
    n = 0
    if call.admit is not None:
        n += int(np.prod(call.admit["batch"]["tokens"].shape))
    for part in call.parts:
        n += int(np.prod(part["batch"]["tokens"].shape))
    return n


def describe_call(call) -> str:
    kw = call.kwargs
    admit = ("-" if call.admit is None
             else "x".join(map(str, call.admit["batch"]["tokens"].shape)))
    return (f"admit={admit} parts={len(call.parts)} width={kw.get('width')} "
            f"steps={kw['n_steps']}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--capacity", type=int, default=None)
    ap.add_argument("--pool-pages", type=int, default=None)
    ap.add_argument("--max-programs", type=int, default=4)
    ap.add_argument("--horizon", type=int, default=400,
                    help="programs first used within this many rounds of "
                         "the window's start")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, spec

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    if args.capacity:
        cell.server["capacity"] = args.capacity
    if args.pool_pages:
        cell.server["pool_pages"] = args.pool_pages
    plan = harness.plan_cell(cell, seed=0)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    eng, state = abstract_server(plan)
    params = sds(plan.weight_shapes)
    gib = 2.0 ** 30
    init = jax.jit(lambda k: plan.init_fn(k)).lower(
        jax.ShapeDtypeStruct((2,), "uint32", sharding=one)).compile()
    m = init.memory_analysis()
    print(f"[size] weight init: out={m.output_size_in_bytes / gib:.2f} GiB "
          f"temp={m.temp_size_in_bytes / gib:.2f} GiB", flush=True)
    state = sds(state)
    start = plan.window_start
    calls = sorted((c for c in plan.replay.calls
                    if c.first_round < start + args.horizon),
                   key=call_weight, reverse=True)
    calls = calls[:args.max_programs] + [
        c for c in calls[args.max_programs:]
        if c.fn == "_decode_chunk_serve" and c.kwargs["width"] is None]
    progs = P.sizing_programs(eng)
    for call in calls:
        t = time.time()
        admit, parts = P.call_inputs(call, plan, sds=sds)
        fn = progs[call.fn]
        if call.fn == "_fused_step":
            lowered = fn.lower(params, *state, admit, parts, **call.kwargs)
        else:
            lowered = fn.lower(params, *state, **call.kwargs)
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        remat = compiled.as_text().count("remat")
        print(f"[size] {call.fn} round={call.first_round} "
              f"{describe_call(call)}: "
              f"args={m.argument_size_in_bytes / gib:.2f} GiB "
              f"temp={m.temp_size_in_bytes / gib:.2f} GiB "
              f"out={m.output_size_in_bytes / gib:.2f} GiB "
              f"alias={m.alias_size_in_bytes / gib:.2f} GiB "
              f"remat_ops={remat} "
              f"compile_s={time.time() - t:.1f}", flush=True)


if __name__ == "__main__":
    main()
