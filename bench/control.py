#!/usr/bin/env python3
"""Readings that set the limits of the ``correct`` comparison.

    python bench/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,... --control-seeds 7,8,9 \
        [--controls oracle,acquisition,random_acquisition]

In one process, after one set-up: for each of ``--seeds`` one window of
the planner as the benchmark runs it; then, for each control or planted
fault of ``bench/lib/control.py`` in turn put in the planner's place and
warmed up, one window for each of ``--control-seeds``. Prints each
window's compared numbers as a JSON line, then for each number the
largest reading of the planner and the smallest of each control. The
benchmark's own runs never run this. Refuses a host without the cell's
TPU chips.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(bench, seeds, seconds, kind,
             log=lambda line: print(line, flush=True)):
    out = []
    for s in seeds:
        win = bench.window(s, seconds)
        ch = bench.check(win)
        row = dict(kind=kind, seed=s, attempted=len(win["due"]),
                   timed_out=win["timed_out"],
                   programs_in_window=win["programs_in_window"],
                   solves_in_window=win["solves_in_window"],
                   unanswered=ch["unanswered"],
                   regret_mean=ch["regret_mean"],
                   checks={k: float(v) for k, (v, _) in ch["checks"].items()})
        log(json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--controls", default="oracle")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cseeds = [int(s) for s in args.control_seeds.split(",")]
    kinds = args.controls.split(",")

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib import control
    from bench.lib.harness import Bench
    from bench.lib.spec import Spec

    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"refused: the cell needs {cell['chips']} TPU chip(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()
    bench = Bench(spec, cell)
    bench.warm_up(seeds[0])
    prog = readings(bench, seeds, args.seconds, "program")
    ctrl = {}
    for kind in kinds:
        undo = control.install(kind)
        try:
            bench.warm_up(cseeds[0])
            ctrl[kind] = readings(bench, cseeds, args.seconds, kind)
        finally:
            undo()
    for name in prog[0]["checks"]:
        row = dict(number=name,
                   lower=max(r["checks"][name] for r in prog),
                   limit=bench.cfg["limits"].get(name, 0))
        for kind, rows in ctrl.items():
            row[kind] = min(r["checks"][name] for r in rows)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
