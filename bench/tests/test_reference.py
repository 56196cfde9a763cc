"""The float64 reference rebuilds each architecture's problem from its
public description alone; it has to agree with the planner's own
float64 host oracle wherever both are defined."""
from pathlib import Path

import numpy as np
import pytest

from bench.lib import reference as ref

ARCHS = ["vgg19", "resnet101"]
ARCHS_DIR = Path(__file__).resolve().parents[1] / "archs"


@pytest.mark.parametrize("name", ARCHS)
def test_reference_matches_the_planners_host_oracle(name):
    from repro.core.batch_bo import scenario_from_request
    arch = ref.load_archs(ARCHS_DIR, [name])[name]
    pb = scenario_from_request(name, gain_offset_db=-3.0).problem
    assert arch.L == pb.L
    assert arch.gain0_db - 3.0 == pytest.approx(pb.gain_db, abs=1e-9)
    rng = np.random.default_rng(0)
    for a in rng.random((300, 2)):
        l, p = pb.denormalize(a)
        u, acc = pb._accuracy(l, p)
        ur, ar, fr = arch.utility(l, p, pb.gain_db)
        assert (ur, ar) == pytest.approx((u, acc), abs=1e-9)
        assert fr == pb.feasible(a)
        assert arch.denormalize(a) == ([l], p) or l in arch.denormalize(a)[0]
    # the best feasible utility on a fine power grid: never above the
    # reference's optimum, which takes each layer's least feasible power
    grid = max(u for l in range(1, pb.L + 1)
               for pn in np.linspace(0, 1, 801)
               for u, _ in [pb._accuracy(*pb.denormalize([pn, (l - 1) / (pb.L - 1)]))]
               if pb.feasible([pn, (l - 1) / (pb.L - 1)]))
    assert 0 <= arch.optimum(pb.gain_db) - grid < 2e-3


class _Res:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _solve(arch, g):
    """A hand-made sound solve: nine evaluations just above the least
    feasible power of layers 5..13, answer the best of them."""
    us, accs, feas, pts = [], [], [], []
    for l in range(5, 14):
        p = arch.required_power(l, g) * 1.02
        u, acc, f = arch.utility(l, p, g)
        us.append(u)
        accs.append(acc)
        feas.append(f)
        pts.append((l, p))
    best = int(np.argmax(np.where(feas, us, -np.inf)))
    l, p = pts[best]
    a = np.array([(p - arch.p_min) / (arch.p_max - arch.p_min),
                  (l - 1) / (arch.L - 1)])
    trace = np.maximum.accumulate(np.where(feas, us, 0.0))
    return _Res(best_a=a, best_utility=us[best], best_accuracy=accs[best],
                n_evals=9, utilities=us, accuracies=accs, feasible=feas,
                incumbent_trace=list(trace)), list(range(5, 14))


def test_sound_solve_passes_and_altered_ones_fail():
    arch = ref.load_archs(ARCHS_DIR, ["vgg19"])["vgg19"]
    res, ev_l = _solve(arch, arch.gain0_db)
    c = ref.check_solve(arch, 0.0, 20, 9, res, ev_l)
    assert c["ledger_faults"] == 0
    assert c["eval_gap"] < 1e-9 and c["answer_gap"] < 1e-9
    res.best_a = res.best_a + np.array([0.0, 1.0 / (arch.L - 1)])  # l + 1
    assert ref.check_solve(arch, 0.0, 20, 9, res, ev_l)["answer_gap"] > 1e-4
    res, ev_l = _solve(arch, arch.gain0_db)
    res.utilities[3] += 0.05                             # value altered
    assert ref.check_solve(arch, 0.0, 20, 9, res, ev_l)["eval_gap"] > 1e-4
    res, ev_l = _solve(arch, arch.gain0_db)
    res.n_evals, res.utilities = 21, res.utilities * 3   # over budget
    assert ref.check_solve(arch, 0.0, 20, 9, res, ev_l)["ledger_faults"] > 0


def test_regret_and_unanswered():
    arch = ref.load_archs(ARCHS_DIR, ["vgg19"])["vgg19"]
    res, ev_l = _solve(arch, arch.gain0_db)
    c = ref.check_solve(arch, 0.0, 20, 9, res, ev_l)
    assert c["regret"] >= 0 and c["unanswered"] == 0
    assert c["regret"] == pytest.approx(
        (arch.optimum(arch.gain0_db) - res.best_utility) / arch.base)
    # no answer, and every evaluation infeasible, where a feasible
    # point exists: a ledger that is sound but left unanswered
    n = len(res.utilities)
    res.best_a, res.best_utility, res.best_accuracy = None, 0.0, 0.0
    res.utilities, res.accuracies = [0.0] * n, [0.0] * n
    res.feasible, res.incumbent_trace = [False] * n, [0.0] * n
    c = ref.check_solve(arch, 0.0, 20, 9, res, ev_l)
    assert c["ledger_faults"] == 0 and c["regret"] is None
    assert c["unanswered"] == 1
