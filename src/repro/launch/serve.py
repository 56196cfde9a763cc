"""Split-serving driver: Bayes-Split-Edge picks (split layer, tx power)
for an LM from the assigned pool, then serves batched requests with the
chosen partition — every BO evaluation runs the real partitioned forward.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --reduced
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, reduced
from repro.core.bo import BayesSplitEdge
from repro.core.cost_model import Budgets, CostModel
from repro.core.problem import SplitInferenceProblem
from repro.core.profiles import lm_profile
from repro.launch.compile_cache import place_compile_cache
from repro.models import transformer as tfm
from repro.runtime.splitpoint import SplitRunner


def build_problem(cfg, seq: int, budgets: Budgets = None, executor=None,
                  gain_db: float = -100.0, p_max: float = 0.5):
    """Auto-budgeted split-serving problem for an LM arch on a FIXED
    nominal link (-100 dB). The budget derivation lives in
    ``core.problem.derive_lm_budgets``; ``core.problem
    .default_lm_problem`` is the same construction with per-arch
    channel anchoring instead of the fixed gain — this CLI keeps the
    explicit-gain variant so ``--arch``/budget overrides stay scriptable."""
    from repro.core.problem import derive_lm_budgets
    prof = lm_profile(cfg, seq)
    if budgets is None:
        budgets = derive_lm_budgets(CostModel(prof), gain_db=gain_db,
                                    p_max=p_max)
    # build with the effective budgets — caller-supplied ones included,
    # which the pre-engine code silently dropped
    cm = CostModel(prof, budgets=budgets)
    return SplitInferenceProblem(cm, gain_db, executor=executor, p_max=p_max)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--budget", type=int, default=15)
    ap.add_argument("--e-max", type=float, default=0.0)
    ap.add_argument("--tau-max", type=float, default=0.0)
    args = ap.parse_args(argv)
    place_compile_cache()

    cfg = get_config(args.arch)
    exec_cfg = reduced(cfg) if args.reduced else cfg
    params = tfm.init_model(jax.random.PRNGKey(0), exec_cfg)
    runner = SplitRunner(exec_cfg, params, args.batch, args.seq)

    budgets = (Budgets(e_max_j=args.e_max, tau_max_s=args.tau_max)
               if args.e_max and args.tau_max else None)
    # the COST model uses the full arch's profile; the EXECUTION runs the
    # (reduced on CPU) real partitioned forward for every BO evaluation
    pb = build_problem(cfg, args.seq, budgets,
                       executor=lambda l, p: runner.run(
                           min(l, exec_cfg.n_layers), p))
    bo = BayesSplitEdge(pb, budget=args.budget)
    res = bo.run(seed=0)
    if res.best_a is None:
        print(f"[serve] {args.arch}: no feasible (split, power) found "
              f"within {res.n_evals} evals — budgets E<={pb.cm.budgets.e_max_j} J"
              f" tau<={pb.cm.budgets.tau_max_s} s are unsatisfiable on this "
              f"channel; not starting the serving loop")
        return
    l, p = pb.denormalize(res.best_a)
    e, t = pb.constraint_values(res.best_a)
    print(f"[serve] {args.arch}: split l={l}/{cfg.n_layers} "
          f"P={p:.3f} W  E={e:.3f} J  tau={t:.3f} s "
          f"({res.n_evals} evals, feasible={pb.feasible(res.best_a)})")

    # steady-state serving with the chosen partition
    logits, bb = runner.run(min(l, exec_cfg.n_layers), p)
    print(f"[serve] partitioned batch served: logits {logits.shape}, "
          f"boundary payload {bb} B")
    return res


if __name__ == "__main__":
    main()
