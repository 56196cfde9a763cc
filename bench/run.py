#!/usr/bin/env python3
"""Benchmark of the streaming split planner on a TPU.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process: warms the server up
on the cell's traffic (that is ``setup_s``), serves the cell's traffic
for ``--seconds``, checks every solve of the window against the float64
reference in ``bench/lib/reference.py``, and prints one JSON line last.
With ``--trace 1`` the window is profiled and the line carries the
per-layer metrics instead of the end-to-end ones. The run refuses a host
without the TPU chips the cell asks for. JAX's compilation cache is kept
in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _print_checks(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    seed = args.seed % 2 ** 63

    # the compile cache stays inside the checkout even where the host
    # names another: runs of two checkouts must share nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib.harness import Bench, queue_summary, result_line
    from bench.lib.spec import Spec

    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    import jax
    devs = jax.devices()
    print(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
          f"device_count={len(devs)}", file=sys.stderr)
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"refused: the cell needs {cell['chips']} TPU chip(s)",
              file=sys.stderr)
        return 2
    # with the directory named above, this only has every program
    # cached, however fast it compiled
    from repro.launch.compile_cache import place_compile_cache
    print(f"compile_cache={place_compile_cache()}", file=sys.stderr)

    bench = Bench(spec, cell)
    cc = bench.warm_up(seed)
    setup_s = time.monotonic() - T_START
    print(f"setup_s={setup_s:.3f} programs={cc.programs} "
          f"compiled={cc.compiled} cache_hits={cc.cache_hits}",
          file=sys.stderr)
    win = bench.window(seed, args.seconds, trace=bool(args.trace))
    checked = bench.check(win)
    out = result_line(bench, win, checked, setup_s, cc.programs,
                      bool(args.trace))
    print(f"programs_in_window={win['programs_in_window']} "
          f"{win['compiled_in_window']} timed_out={win['timed_out']} "
          f"solves_in_window={win['solves_in_window']} "
          f"unanswered={checked['unanswered']} "
          f"regret_mean={checked['regret_mean']!r} "
          f"{queue_summary(win['queue_depth'], args.seconds)}",
          file=sys.stderr)
    _print_checks(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
