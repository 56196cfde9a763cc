"""The benchmark's data, found by name under a checkout's ``bench/``.

``BENCHMARK.json`` lists the cells and metrics. A cell names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); a metric is computed by the reader
``bench/metrics/<stem>.py``, where the stem is the metric's name up to
its first ``.``, and the request architectures' reference data is
``bench/archs/<arch>.json``. An architecture's ``network.kind`` names
the float64 profile of its kind, ``bench/kinds/<kind>.py``, whose
``profile(network)`` returns ``(macs, boundary_bytes, tail_macs)``: the
work of each of the L split layers, the bytes that cross the link after
split ``l`` for ``l`` = 0..L (0: the device runs nothing) and the
server-only work after the last split layer (see
``bench/lib/reference.py``). Adding any of these is adding files.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _name(kind: str, name: str) -> str:
    if not NAME.match(name or ""):
        raise ValueError(f"bad {kind} name {name!r}")
    return name


def load_module(directory, kind: str, stem: str):
    """The module ``<directory>/<stem>.py``, loaded from its file; the
    stem has to be a name, so it holds no path."""
    path = Path(directory) / f"{_name(kind, stem)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} {stem!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """``BENCHMARK.json`` of a checkout, and the files it names."""

    def __init__(self, root):
        self.root = Path(root)
        self.dir = self.root / "bench"
        with open(self.root / "BENCHMARK.json") as f:
            self.data = json.load(f)

    def _json(self, sub: str, name: str) -> dict:
        with open(self.dir / sub / f"{_name(sub, name)}.json") as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    @property
    def archs_dir(self) -> Path:
        return self.dir / "archs"

    def cell(self, name: str) -> dict:
        """The cell of that name. A ``<config>.<traffic>`` pair that is
        not a cell runs as one too, with only the metrics that list no
        cells: that is how the knee of a configuration is read, from a
        backlogged run (``<config>.backlog``) and the solves it completes
        in its window, which the run prints on standard error."""
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        for w in self.data["workloads"]:
            pre = w["config"] + "."
            if name.startswith(pre) and (
                    self.dir / "traffic" / f"{name[len(pre):]}.json").exists():
                return dict(name=name, config=w["config"],
                            traffic=name[len(pre):], chips=w["chips"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def metrics(self, cell: dict, trace: bool) -> list:
        """The metric entries a cell reports: its end-to-end metrics
        without the trace, its per-layer metrics with it. A metric that
        lists no workloads belongs to every cell that reports the
        end-to-end metric it moves."""
        e2e = [m for m in self.data["end_to_end"] if self._in(m, cell)]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if (self._in(m, cell) if "workloads" in m
                    else m["moves"] in moved)]

    @staticmethod
    def _in(metric: dict, cell: dict) -> bool:
        return "workloads" not in metric or cell["name"] in metric["workloads"]

    def reader(self, metric: str):
        """``read(record) -> float | None`` of ``bench/metrics/<stem>.py``."""
        stem = _name("metric", metric).split(".", 1)[0]
        return load_module(self.dir / "metrics", "metric", stem).read
