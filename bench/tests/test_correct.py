"""The ``correct`` comparison on a small cell, on the CPU: the planner
passes it; the bfloat16 control, and each fault the timed path can have
on one chip (the loop body's acquisition picking any candidate among
them), fail it. The harness's look for a chip is skipped; the
rest of a run is driven as ``bench/run.py`` drives it."""
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.lib import control
from bench.lib.harness import Bench, result_line
from bench.lib.spec import Spec

REPO = Path(__file__).resolve().parents[2]
SECONDS, WAIT = 3.0, 30.0


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg_path = root / "bench" / "configs" / "paper_cnn_b20.json"
    cfg = json.loads(cfg_path.read_text())
    cfg.update(lanes_per_chip=4, requests=dict(cfg["requests"],
                                               budgets=[20]))
    cfg_path.write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "paper_cnn_b20.poisson.json").write_text(
        json.dumps({"loop": "open", "arrivals": "poisson", "rate_hz": 3.0}))
    spec = Spec(root)
    b = Bench(spec, spec.cell("paper_cnn_b20.poisson"), log=lambda *a: None)
    b.warm_up(2 ** 31 + 11)
    yield b
    jax.clear_caches()


def _run(bench, seed=2 ** 31 + 17):
    win = bench.window(seed, SECONDS, wait_s=WAIT)
    checked = bench.check(win)
    return win, checked


def test_planner_is_correct(bench):
    win, checked = _run(bench)
    assert len(win["due"]) >= 4 and not win["timed_out"]
    out = result_line(bench, win, checked, 1.0, 0, False)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"solve_p50_ms", "solve_p95_ms",
                                   "setup_s"}
    json.dumps(out)


def test_bf16_control_is_not_correct(bench):
    undo = control.install()
    try:
        bench.warm_up(2 ** 31 + 13)
        win, checked = _run(bench)
    finally:
        undo()
    c = checked["checks"]
    assert c["eval_gap"][0] > 30 * c["eval_gap"][1], c
    assert not all(v <= lim for v, lim in c.values())


def _stuck(run_data, state, it, *args):
    return state, it


def _half_left_out(orig):
    def phase(run_data, state, it, *args):
        new, it2 = orig(run_data, state, it, *args)
        # the first half: the server fills the lowest free lanes first
        h = state["active"].shape[0] // 2
        return jax.tree.map(lambda n, o: n.at[:h].set(o[:h]), new,
                            state), it2
    return phase


def _answer_altered(orig):
    def result_from_row(out, i, sc):
        r = orig(out, i, sc)
        if r.best_a is not None:
            r.best_a = r.best_a + np.array([0.0, 1.0 / (sc.problem.L - 1)])
        return r
    return result_from_row


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch_left_out",
                                   "answer_altered", "random_acquisition"])
def test_broken_timed_path_is_not_correct(bench, monkeypatch, fault):
    from repro.core import wholerun as wr
    if fault == "random_acquisition":
        undo = control.install(fault)
        try:
            bench.warm_up(2 ** 31 + 13)
            win, checked = _run(bench)
        finally:
            undo()
        c = checked["checks"]
        assert c["unanswered_share"][0] > c["unanswered_share"][1], c
        return
    if fault == "state_unchanged":
        monkeypatch.setattr(wr, "stream_phase", _stuck)
    elif fault == "half_batch_left_out":
        monkeypatch.setattr(wr, "stream_phase",
                            _half_left_out(wr.stream_phase))
    else:
        monkeypatch.setattr(wr, "result_from_row",
                            _answer_altered(wr.result_from_row))
    win, checked = _run(bench)
    c = checked["checks"]
    assert not all(v <= lim for v, lim in c.values()), c
    if fault != "answer_altered":
        assert c["missing"][0] > 0 and win["timed_out"]
    else:
        assert c["answer_gap"][0] > c["answer_gap"][1]
