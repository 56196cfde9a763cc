"""The on-chip smoke test's phase and guard, exercised on the CPU.

``chip_smoke.py`` itself runs only on a TPU. Here its one-device phase is
called in-process at a tiny size (8 requests, 4 lanes) so that the checks
it applies on the chip are known to pass where the answers can be read,
check b is shown to fail on a changed answer but not on changed last
bits, and its ``main`` is shown to refuse a host without a TPU.
"""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_checks_pass_at_tiny_size(chip_smoke):
    lines = []
    report = chip_smoke.serve_phase(n_requests=8, n_lanes=4, seed=0,
                                    log=lines.append)
    assert report["checks"] == dict(a_exactly_once=True,
                                    b_same_answer_wholerun=True,
                                    c_host_oracle_feasible=True,
                                    d_quickstart_optimum=True), lines
    assert report["solves"] == 8
    # on the CPU the cold stream replays the whole run bit for bit
    assert report["bitwise_wholerun"] == 8, lines
    assert 0 < report["answers"] <= 8
    # the second, timed serve finds every program already built
    assert report["compiles_in_window"] == 0


def _result(**kw):
    fields = dict(best_a=np.array([0.25, 0.5]), n_evals=20,
                  utilities=[1.0, 2.0], incumbent_trace=[1.0, 2.0],
                  feasible=[True, True], best_accuracy=87.5)
    fields.update(kw)
    return SimpleNamespace(**fields)


@pytest.mark.parametrize("change, same", [
    (dict(utilities=[1.0, 2.0000002]), True),   # last bits only: counted
    (dict(best_accuracy=85.0), False),
    (dict(best_a=None), False),
])
def test_check_b_gates_the_answer_and_counts_bits(chip_smoke, change, same):
    ref = [_result(), _result()]
    got = {0: _result(), 1: _result(**change)}
    lines = []
    ok, n_bitwise = chip_smoke._compare("b", got, ref, lines.append)
    assert ok is same
    assert n_bitwise == 1
    assert "bitwise 1/2 (differ at [1])" in lines[0]


def test_main_refuses_a_host_without_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    cache_dir = jax.config.jax_compilation_cache_dir
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err
    # refused before the compile cache was placed
    assert jax.config.jax_compilation_cache_dir == cache_dir
