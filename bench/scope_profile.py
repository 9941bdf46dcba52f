#!/usr/bin/env python3
"""Run one cell traced, as ``bench/run.py --trace 1`` does, and break its
traced rounds down by the model's parts and the program's spans.

    python3 bench/scope_profile.py --workload stablelm_3b.decode_backlog \\
        --seed 2718281828 --seconds 30

The run is the harness's own (``harness.run`` with ``trace=True``); this
script only watches it: it records the abstract arguments of every round
program called in the traced rounds, and, before the harness reduces and
deletes the profile, compiles those programs again (from the persistent
compile cache) for their scope maps and reduces the profile with
``scopes.reduce``.  The last line of standard output is one JSON object:
``run`` (the harness's result line) and ``scopes`` (``[phase, part,
seconds]``, the seconds per part, the unscoped share, the host round share,
the parts' roofline shares, and the idle gaps named by program spans).

``--keep DIR`` writes the profile and the scope maps of its program runs
there, as a fixture for the tests.  ``--root`` runs a cell of another
checkout root (the tests' tiny cell).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# the engine's round programs and the jit names a profile gives their runs
ROUND_FNS = {"_fused_step": "jit__fused_step_impl",
             "_decode_chunk_serve": "jit__decode_chunk_impl"}


def _abstract(tree):
    import jax

    def one(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding,
                                        weak_type=x.weak_type)
        return x
    return jax.tree.map(one, tree)


def _key(fn, args, kwargs):
    import jax
    leaves, tree = jax.tree.flatten((args, kwargs))
    return (fn, str(tree), tuple(
        (tuple(getattr(x, "shape", ())), str(getattr(x, "dtype", type(x))),
         bool(getattr(x, "weak_type", False))) for x in leaves))


class Watch:
    """What the harness's traced run hands over: its plan, its engine, the
    round programs called in the traced rounds, and the reduction."""

    def __init__(self, keep=None):
        self.keep = keep
        self.plan = self.eng = self.result = None
        self.recording = False
        self.calls = []                  # (fn, key) in call order
        self.signatures = {}             # key -> (jitted fn, args, kwargs)
        self.texts = {}                  # program run name -> compiled text

    @contextlib.contextmanager
    def installed(self, harness):
        P = harness.P
        saved = (harness.plan_cell, P.build_server, harness.traced_rounds,
                 harness.reduce_trace)

        def plan_cell(*a, **k):
            self.plan = saved[0](*a, **k)
            return self.plan

        def build_server(*a, **k):
            self.eng, sched = saved[1](*a, **k)
            self.watch(self.eng)
            return self.eng, sched

        def traced_rounds(*a, **k):
            self.recording = True
            try:
                return saved[2](*a, **k)
            finally:
                self.recording = False

        def reduce_trace(tw):
            self.result = self.reduce(tw)
            return saved[3](tw)

        harness.plan_cell, P.build_server = plan_cell, build_server
        harness.traced_rounds, harness.reduce_trace = (traced_rounds,
                                                       reduce_trace)
        try:
            yield self
        finally:
            (harness.plan_cell, P.build_server, harness.traced_rounds,
             harness.reduce_trace) = saved

    def watch(self, eng):
        """Record the calls of ``eng``'s round programs while
        ``recording``."""
        for fn in ROUND_FNS:
            setattr(eng, fn, self._recorded(fn, getattr(eng, fn)))

    def compiled_text(self, key) -> str:
        """The compiled text of the recorded program ``key``."""
        jitted, args, kwargs = self.signatures[key]
        return jitted.lower(*args, **kwargs).compile().as_text()

    def _recorded(self, fn, jitted):
        def call(*args, **kwargs):
            if self.recording:
                key = _key(fn, args, kwargs)
                if key not in self.signatures:
                    self.signatures[key] = (jitted, _abstract(args),
                                            dict(kwargs))
                self.calls.append((fn, key))
            return jitted(*args, **kwargs)
        return call

    def scope_maps(self, trace) -> tuple:
        """``{program run name: scope_map}`` for the traced round programs:
        each distinct program of the traced calls, in the order they were
        first called, is the program of the run names (with their ids) of
        its jit name, in the order those first ran."""
        from bench import scopes as S

        first_runs = {}                  # jit name -> run names, in order
        for dev in sorted(trace["modules"]):
            for name, _, _ in sorted(trace["modules"][dev],
                                     key=lambda m: m[1]):
                first_runs.setdefault(name.split("(")[0], {})[name] = None
        maps, matched = {}, True
        for fn, jit_name in ROUND_FNS.items():
            keys = list(dict.fromkeys(k for f, k in self.calls if f == fn))
            runs = list(first_runs.get(jit_name, ()))
            matched &= len(keys) == len(runs)
            for key, run in zip(keys, runs):
                self.texts[run] = self.compiled_text(key)
                maps[run] = S.scope_map(self.texts[run])
        return maps, matched

    def reduce(self, tw) -> dict:
        import jax

        from bench import harness, measure as MX, parts as PT, scopes as S
        from bench import work as W

        t = time.perf_counter()
        trace = S.load(tw.trace_dir)
        maps, matched = self.scope_maps(trace)
        red = S.reduce(trace, maps)
        work = [w for w in self.plan.replay.work[tw.first_round:tw.last_round]
                if w.kind != "idle"]
        shares = PT.roofline_shares(
            W.Dims.of(self.plan.cell.config), work,
            MX.peak(jax.devices()[0].device_kind), red["part_s"])
        lo, hi = S.window(trace)
        rounds = sorted(d / 1e9 for n, s, d in trace["spans"]
                        if n == "round" and lo <= s < hi)
        unmapped = sorted({name for dev, ops in trace["ops"].items()
                           for name, s, d in ops if lo <= s < hi
                           and not any(name in m for m in maps.values())})
        harness.log(f"[scopes] without_map={unmapped[:40]}")
        for run, name, secs in red["top_unscoped"]:
            line = next((ln.strip() for ln in self.texts.get(run, "")
                         .splitlines() if f"%{name} = " in ln), "")
            harness.log(f"[scopes] unscoped {secs:.6f} s {run} {name}: "
                        f"{line[:400]}")
        out = {"scopes": red["scopes"], "part_s": red["part_s"],
               "uncovered_share": red["uncovered_share"],
               "host_round_share": red["host_round_share"],
               "attn_roofline_share": shares["attention"],
               "matmul_roofline_share": shares["matmul"],
               "busy_s": red["busy_s"], "window_s": red["window_s"],
               "idle_gaps": red["idle_gaps"],
               "top_unscoped": red["top_unscoped"],
               "longest_gaps": red["longest_gaps"],
               "round_s_median": rounds[len(rounds) // 2] if rounds else None,
               "rounds": len(rounds), "programs_matched": matched,
               "programs": sorted(maps),
               "ops_without_map": len(unmapped)}
        harness.log(f"[scopes] reduce_s={time.perf_counter() - t:.3f} "
                    f"uncovered_share={red['uncovered_share']:.6f} "
                    f"host_round_share={red['host_round_share']:.6f} "
                    f"programs_matched={matched} "
                    f"ops_without_map={len(unmapped)} "
                    + S.phase_part_table(red))
        if self.keep is not None:
            self._keep(tw.trace_dir, trace, maps)
        return out

    def _keep(self, trace_dir, trace, maps):
        """The profile and, per program run, the map of the operations it
        ran, under ``self.keep``."""
        keep = pathlib.Path(self.keep)
        keep.mkdir(parents=True, exist_ok=True)
        pb = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
        shutil.copy(pb, keep / "scoped.xplane.pb")
        ran = {name for ops in trace["ops"].values() for name, _, _ in ops}
        small = {run: {k: v for k, v in m.items() if k in ran}
                 for run, m in maps.items()}
        (keep / "scoped.maps.json").write_text(
            json.dumps(small, indent=0, sort_keys=True) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--root", default=None)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    from bench import harness, spec

    root = pathlib.Path(args.root).resolve() if args.root else spec.ROOT
    watch = Watch(args.keep)
    try:
        with watch.installed(harness):
            out = harness.run(args.workload, args.seed, args.seconds, True,
                              T_PROCESS, root=root)
    except harness.NoChip as e:
        harness.log(f"[bench] {e}")
        return 3
    print(json.dumps({"run": out, "scopes": watch.result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
