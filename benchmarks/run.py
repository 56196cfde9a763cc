"""Benchmark harness — one entry per paper table/figure + the roofline
report. ``python -m benchmarks.run [names...]``

Prints one CSV line per benchmark: name,seconds,derived-headline."""
from __future__ import annotations

import sys
import time
import traceback

from benchmarks import (fig6_convergence, fig7_space, fig8_regret,
                        fig9_ablation, fig10_seeds, profiling,
                        roofline_report, table1, trace_robustness)


def _derived_table1(rows):
    ours = next(r for r in rows if "Ours" in r["algorithm"])
    exh = next(r for r in rows if "Exhaustive" in r["algorithm"])
    return (f"ours: l={ours['split_layer']} P={ours['power_w']} "
            f"acc={ours['accuracy']} in {ours['evals']} evals "
            f"({exh['evals'] // max(ours['evals'], 1)}x fewer than exhaustive)")


def _derived_fig10(hits):
    ok = [h for h in hits if h]
    import numpy as np
    return (f"{len(ok)}/{len(hits)} seeds converged, "
            f"mean iter {np.mean(ok):.1f}" if ok else "no convergence")


BENCHES = [
    ("table1", table1.main, _derived_table1),
    ("fig2-4_profiling", profiling.main,
     lambda o: f"{len(o['layers'])} layers profiled"),
    ("fig6_convergence", fig6_convergence.main,
     lambda o: f"{len(o)} strategies traced"),
    ("fig7_space", fig7_space.main,
     lambda o: f"band={len(o['optimum_band'])} pts"),
    ("fig8_regret", fig8_regret.main,
     lambda o: "; ".join(
         f"{p}: ours {c['Bayes-Split-Edge']['decay_exponent']:.2f} vs "
         f"basic {c['Basic-BO']['decay_exponent']:.2f}"
         for p, c in o.items())),
    ("fig9_ablation", fig9_ablation.main,
     lambda o: f"{len(o)} variants"),
    ("fig10_seeds", fig10_seeds.main, _derived_fig10),
    ("trace_robustness", trace_robustness.main,
     lambda rows: f"{sum(1 for r in rows if r.get('feasible'))}/"
                  f"{len(rows)} frames solved"),
    ("roofline", roofline_report.main,
     lambda rows: f"{len(rows)} dry-run cells analysed"),
]


def main() -> int:
    """Run the selected benchmarks (all by default); the exit code is the
    number of benchmarks that raised, so a failure never reads as 0."""
    names = set(sys.argv[1:])
    print("benchmark,seconds,derived")
    failed = []
    for name, fn, derived in BENCHES:
        if names and name not in names:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            out = fn()
            d = derived(out)
        except Exception as e:  # noqa: BLE001 — report, run the rest
            traceback.print_exc()
            d = f"ERROR {type(e).__name__}: {e}"
            failed.append(name)
        print(f"CSV,{name},{time.time() - t0:.1f},{d}", flush=True)
    if failed:
        print(f"failed: {', '.join(failed)}", file=sys.stderr)
    return len(failed)


if __name__ == "__main__":
    sys.exit(main())
