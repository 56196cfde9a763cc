"""A new cell, configuration, traffic mix or metric is a new file and a
new BENCHMARK.json entry: the harness finds each by name."""
import json
import shutil
from pathlib import Path

import pytest

from bench.lib.spec import Spec

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def root(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def test_every_cell_of_the_benchmark_resolves():
    spec = Spec(REPO)
    for w in spec.data["workloads"]:
        cell = spec.cell(w["name"])
        assert spec.config(cell["config"])["name"] == cell["config"]
        assert spec.traffic(cell["traffic"])["loop"] in ("open", "backlog")
        for trace in (False, True):
            for m in spec.metrics(cell, trace):
                assert callable(spec.reader(m["name"]))


def test_new_files_are_found_by_name(root):
    b = root / "bench"
    cfg = json.loads((b / "configs" / "paper_cnn_b20.json").read_text())
    cfg.update(name="paper_cnn_b10", requests=dict(cfg["requests"],
                                                    budgets=[10]))
    (b / "configs" / "paper_cnn_b10.json").write_text(json.dumps(cfg))
    (b / "traffic" / "steady_5hz.json").write_text(json.dumps(
        {"loop": "open", "arrivals": "poisson", "rate_hz": 5.0}))
    (b / "metrics" / "solves_in_window.py").write_text(
        "def read(record):\n    return record['solves_in_window']\n")
    data = json.loads((root / "BENCHMARK.json").read_text())
    data["workloads"].append(dict(name="paper_cnn_b10.steady",
                                  config="paper_cnn_b10",
                                  traffic="steady_5hz", chips=1,
                                  why="a test cell"))
    data["per_layer"].append(dict(name="solves_in_window.steady",
                                  unit="solves", better="higher",
                                  source="program_counter", layer="x",
                                  moves="setup_s",
                                  workloads=["paper_cnn_b10.steady"]))
    (root / "BENCHMARK.json").write_text(json.dumps(data))

    spec = Spec(root)
    cell = spec.cell("paper_cnn_b10.steady")
    assert spec.config(cell["config"])["requests"]["budgets"] == [10]
    assert spec.traffic(cell["traffic"])["rate_hz"] == 5.0
    names = [m["name"] for m in spec.metrics(cell, trace=True)]
    assert "solves_in_window.steady" in names
    assert "setup_programs" in names          # moves setup_s, no workloads
    assert "iter_ms.open" not in names        # lists other cells only
    assert spec.reader("solves_in_window.steady")(
        {"solves_in_window": 7}) == 7
    e2e = [m["name"] for m in spec.metrics(cell, trace=False)]
    assert e2e == ["setup_s"]


def test_config_pair_outside_the_cells_runs_ad_hoc():
    spec = Spec(REPO)
    cell = spec.cell("paper_cnn_b20.backlog")
    assert (cell["config"], cell["traffic"]) == ("paper_cnn_b20", "backlog")
    names = [m["name"] for m in spec.metrics(cell, trace=False)]
    # the open-loop cells' latencies are not a backlog's metrics
    assert "setup_s" in names and "solve_p95_ms" not in names
    with pytest.raises(KeyError):
        spec.cell("paper_cnn_b20.nosuchtraffic")


def test_names_with_a_path_are_refused():
    with pytest.raises(ValueError):
        Spec(REPO).config("../BENCHMARK")


def test_probe_refuses_a_server_without_lane_pools():
    from bench.lib.harness import _Probe

    class Server:
        _pools = [object()]
    with pytest.raises(RuntimeError):
        _Probe(Server())
    with pytest.raises(RuntimeError):
        _Probe(object())
