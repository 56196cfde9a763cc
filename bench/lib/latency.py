"""Latency statistics over every request of a window."""
from __future__ import annotations

import math
from typing import Iterable


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of all values. A
    missing answer enters as ``math.inf``, so once more than ``100 - q``
    percent are missing the percentile is infinite."""
    v = sorted(values)
    if not v:
        raise ValueError("no requests in the window")
    k = max(1, math.ceil(q / 100.0 * len(v)))
    return v[k - 1]


def latencies_ms(due_s: dict, emit_s: dict) -> list:
    """Emit time minus due time, in ms, of every request in ``due_s``
    (request -> due time); one with no emit time counts as infinite."""
    return [(emit_s[i] - t) * 1e3 if i in emit_s else math.inf
            for i, t in due_s.items()]
