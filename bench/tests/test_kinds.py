"""An architecture kind's float64 profile is the file
``bench/kinds/<kind>.py``: the CNNs' profiles read bitwise what they did
when the reference held them, a new kind is a new file, and no kind
imports the planner."""
import ast
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.lib import reference as ref
from bench.tests.test_reference import _solve

BENCH = Path(__file__).resolve().parents[1]
KINDS = sorted((BENCH / "kinds").glob("*.py"))

# the pins were read from the reference as it stood before the kinds
# had files of their own (every float as ``float.hex``)
PINS = json.loads((Path(__file__).parent / "reference_pins.json").read_text())

# a decoder-shaped toy: the device sends token ids when it runs nothing,
# and after layer l the residual stream and the caches of its l layers
TOY = '''
def profile(net):
    d, s, n = net["hidden"], net["seq"], net["layers"]
    macs = [s * 12 * d * d] * n
    sent = [4 * s] + [net["bytes_per_elem"] * s * (d + l * net["cache"])
                      for l in range(1, n + 1)]
    return macs, sent, s * d * net["vocab"]
'''
TOY_ARCH = {
    "arch": "toydec",
    "network": {"kind": "toydec", "layers": 16, "hidden": 512, "seq": 64,
                "cache": 576, "vocab": 32000, "bytes_per_elem": 2},
    "budgets": {"e_max_j": 1.0, "tau_max_s": 2.0},
    "utility": {"base_acc": 62.5, "bump": 6.25, "peak_layer": 9,
                "sigma": 2.0},
    "anchor": {"layer": 9, "p_w": 0.2},
    "power_w": [0.0, 0.5],
}


def _hex(a):
    return [float(x).hex() for x in np.asarray(a, np.float64).ravel()]


@pytest.fixture
def bench_copy(tmp_path):
    b = tmp_path / "bench"
    shutil.copytree(BENCH, b,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return b


def _write_arch(b, spec):
    (b / "archs" / f"{spec['arch']}.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("name", ["vgg19", "resnet101"])
def test_cnn_kinds_read_the_pinned_profile_bitwise(name):
    arch = ref.load_archs(BENCH / "archs", [name])[name]
    pin = PINS[name]
    assert arch.L == pin["L"]
    assert _hex(arch.cum) == pin["cum"]
    assert float(arch.total).hex() == pin["total"]
    assert _hex(arch.bits) == pin["bits"]
    assert float(arch.gain0_db).hex() == pin["gain0_db"]


def test_a_new_kind_is_a_new_file(bench_copy):
    (bench_copy / "kinds" / "toydec.py").write_text(TOY)
    _write_arch(bench_copy, TOY_ARCH)
    arch = ref.load_archs(bench_copy / "archs", ["toydec"])["toydec"]
    net = TOY_ARCH["network"]
    assert arch.L == 16
    assert arch.bits[0] == 8 * 4 * 64
    assert np.array_equal(arch.bits[1:], [16 * 64 * (512 + l * 576)
                                          for l in range(1, 17)])
    assert arch.total == 16 * 64 * 12 * 512 ** 2 + 64 * 512 * net["vocab"]
    assert arch.required_power(9, arch.gain0_db) == pytest.approx(0.2)

    g = arch.gain0_db
    res, ev_l = _solve(arch, g)
    c = ref.check_solve(arch, 0.0, 20, 9, res, ev_l)
    assert c["ledger_faults"] == 0 and c["unanswered"] == 0
    assert c["eval_gap"] < 1e-9 and c["answer_gap"] < 1e-9
    res.best_a = res.best_a + np.array([0.0, 1.0 / (arch.L - 1)])  # l + 1
    assert ref.check_solve(arch, 0.0, 20, 9, res, ev_l)["answer_gap"] > 1e-4
    res, ev_l = _solve(arch, g)
    res.utilities[3] += 0.05                             # value altered
    assert ref.check_solve(arch, 0.0, 20, 9, res, ev_l)["eval_gap"] > 1e-4


def test_a_profile_of_the_wrong_length_is_refused(bench_copy):
    (bench_copy / "kinds" / "toydec.py").write_text(
        TOY.replace("for l in range(1, n + 1)", "for l in range(1, n)"))
    _write_arch(bench_copy, TOY_ARCH)
    with pytest.raises(ValueError, match="16 boundary sizes for 16 split"):
        ref.load_archs(bench_copy / "archs", ["toydec"])


def test_an_unknown_kind_names_its_missing_file(bench_copy):
    _write_arch(bench_copy, dict(TOY_ARCH, arch="lm", network=dict(
        TOY_ARCH["network"], kind="decoder")))
    with pytest.raises(FileNotFoundError, match="bench/kinds/decoder.py"):
        ref.load_archs(bench_copy / "archs", ["lm"])


@pytest.mark.parametrize("kind", ["../archs/vgg19", "kinds/vgg", "/tmp/vgg",
                                  "..", ""])
def test_a_kind_with_a_path_is_refused(bench_copy, kind):
    _write_arch(bench_copy, dict(TOY_ARCH, arch="bad", network=dict(
        TOY_ARCH["network"], kind=kind)))
    with pytest.raises(ValueError, match="bad kind name"):
        ref.load_archs(bench_copy / "archs", ["bad"])


@pytest.mark.parametrize("path", KINDS, ids=[p.stem for p in KINDS])
def test_kinds_import_only_the_standard_library_and_numpy(path):
    allowed = set(sys.stdlib_module_names) | {"numpy"}
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            roots = [a.name.split(".")[0] for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "a kind is loaded from its file alone"
            roots = [node.module.split(".")[0]]
        else:
            continue
        assert set(roots) <= allowed, (path.name, roots)
