"""Reduction of the server's own spans, with a profiler trace of the same
window, to per-layer times of the serving path.

The spans are the rows of ``repro.runtime.spans.Spans``:
``(name, start_ns, end_ns, parent, attrs)`` on the host's monotonic
clock, with the server's start as ``t0_ns``. The trace is what
``trace_reduce.load`` returns: event times relative to the profile's
start. One offset joins the two clocks: the start of the ``WINDOW``
annotation in the trace, minus the monotonic time at which the
benchmark entered it.

Only the profiled part of the window counts: a span counts if it starts
after the tracer's start pause and ends before its stop pause, so
neither pause enters a span.

Device time per loop phase needs each device operation's ``named_scope``
(``gp_fit``, ``acquisition``, ``oracle_step`` in ``wholerun._make_body``).
On a TPU v5e the trace's ``XLA Ops`` events carry no scope: an event is
named by its HLO instruction (``%fusion.96 = s32[16]{...} fusion(...)``)
and its stats hold only its device offset and duration. So the scope of
an operation is read from the ``op_name`` metadata of the compiled
``stream_phase`` programs' HLO text (``hlo_ops``), each program
execution of the trace matched to the compiled text whose instructions
it ran (``program_scopes``).
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.lib import trace_reduce as trd

PHASES = ("gp_fit", "acquisition", "oracle_step")
PROGRAM = "jit_stream_phase"

Row = Tuple[str, int, int, object, dict]


class Clock:
    """The server's start and the profiled part of a window on the
    monotonic clock, and the offset from it to the trace's clock."""

    def __init__(self, t0_ns: int, window_mono_ns: int,
                 pauses: Sequence[Tuple[int, int]], trace: trd.Trace):
        self.t0_ns = int(t0_ns)
        self.lo = int(pauses[0][1])       # end of the start pause
        self.hi = int(pauses[1][0])       # start of the stop pause
        self.offset = trd.window(trace)[0] - int(window_mono_ns)

    def inside(self, row: Row) -> bool:
        return row[1] >= self.lo and row[2] <= self.hi


def _named(rows, name: str, clock: Clock) -> List[Row]:
    return [r for r in rows if r[0] == name and clock.inside(r)]


def _mean_ms(rows: Sequence[Row]) -> Optional[float]:
    if not rows:
        return None
    return float(np.mean([e - s for _, s, e, _, _ in rows])) * 1e-6


def due_ns(clock: Clock, arrivals, time_scale: float, i: int) -> int:
    return clock.t0_ns + int(round(arrivals[i] * time_scale * 1e9))


# -- host spans --------------------------------------------------------------

def decode_ms(rows, clock: Clock) -> Optional[float]:
    """Mean wall of ``serve.decode``: one request pulled from the feed."""
    return _mean_ms(_named(rows, "serve.decode", clock))


def admit_ms(rows, clock: Clock) -> Optional[float]:
    """Mean wall of ``serve.admit``, per admission."""
    return _mean_ms(_named(rows, "serve.admit", clock))


def readback_ms(rows, clock: Clock) -> Optional[float]:
    """Mean wall of the ``serve.readback`` spans that flushed a lane."""
    return _mean_ms([r for r in _named(rows, "serve.readback", clock)
                     if r[4].get("rows", 0) > 0])


def first_admits(rows) -> Dict[int, Row]:
    """Each request's first ``serve.admit``."""
    out: Dict[int, Row] = {}
    for r in sorted((r for r in rows if r[0] == "serve.admit"),
                    key=lambda r: r[1]):
        for i in r[4].get("reqs", ()):
            out.setdefault(i, r)
    return out


def queue_wait_ms(rows, clock: Clock, arrivals,
                  time_scale: float) -> Optional[float]:
    """Median over requests of the start of the first ``serve.admit``
    holding the request, minus its due time."""
    waits = [(r[1] - due_ns(clock, arrivals, time_scale, i)) * 1e-6
             for i, r in first_admits(rows).items()
             if clock.inside(r) and i < len(arrivals)]
    return float(statistics.median(waits)) if waits else None


def latency_split(rows, clock: Clock, arrivals, time_scale: float,
                  emit_s: Dict[int, float]) -> List[dict]:
    """Per request of the profiled part, three pieces that add up to its
    emit time minus its due time: ``queue`` (due to the start of its
    first admission), ``device`` (that start to the end of the
    ``serve.wait`` before the ``serve.readback`` that flushed it) and
    ``readback`` (that wait's end to the readback's end); ``residual``
    is the sum minus ``emit - due`` (ns)."""
    admits = first_admits(rows)
    waits: Dict[object, List[Row]] = {}
    for r in rows:
        if r[0] == "serve.wait":
            waits.setdefault(r[4].get("pool"), []).append(r)
    for w in waits.values():
        w.sort(key=lambda r: r[2])
    out = []
    for rb in rows:
        if rb[0] != "serve.readback" or not clock.inside(rb):
            continue
        before = [w for w in waits.get(rb[4].get("pool"), ())
                  if w[2] <= rb[1]]
        if not before:
            continue
        w_end = before[-1][2]
        for i in rb[4].get("reqs", ()):
            a = admits.get(i)
            if a is None or not clock.inside(a) or i not in emit_s:
                continue
            due = due_ns(clock, arrivals, time_scale, i)
            q, d, b = a[1] - due, w_end - a[1], rb[2] - w_end
            emit = clock.t0_ns + emit_s[i] * time_scale * 1e9
            out.append(dict(req=i, queue=q, device=d, readback=b,
                            residual=q + d + b - (emit - due)))
    return out


# -- device trace joined with the spans -------------------------------------

def _device_lines(trace: trd.Trace, device: int = 0) -> dict:
    return trace.get(f"/device:TPU:{device}", {})


def iter_device_ms(rows, clock: Clock, trace: trd.Trace,
                   device: int = 0) -> Optional[float]:
    """Device time of the ``stream_phase`` programs (``XLA Modules``)
    over the loop iterations they ran: each program execution is joined
    to the ``serve.dispatch`` that launched it (the execution starts
    between the dispatch's start and the end of the next ``serve.wait``
    of its pool), and the iterations are the ``iters`` of the
    ``serve.readback`` after that wait."""
    mods = [(t - clock.offset, d) for n, t, d in
            _device_lines(trace, device).get("XLA Modules", [])
            if trd._short(n) == PROGRAM]
    by_pool: Dict[object, List[Row]] = {}
    for r in rows:
        if r[0] in ("serve.dispatch", "serve.wait", "serve.readback"):
            by_pool.setdefault(r[4].get("pool"), []).append(r)
    dev_ns = iters = 0
    for seq in by_pool.values():
        seq.sort(key=lambda r: r[1])
        for k, r in enumerate(seq):
            if r[0] != "serve.dispatch" or "bucket" not in r[4]:
                continue
            nxt = seq[k + 1:k + 3]
            if ([x[0] for x in nxt] != ["serve.wait", "serve.readback"]
                    or not (clock.inside(r) and clock.inside(nxt[1]))):
                continue
            t_lo, t_hi = r[1], nxt[0][2]
            dev_ns += sum(d for t, d in mods if t_lo <= t <= t_hi)
            iters += nxt[1][4].get("iters", 0)
    return dev_ns / iters * 1e-6 if iters else None


def self_times(evs: Sequence[Tuple[str, int, int]]) -> List[int]:
    """Each event's duration minus the part of it that events nested
    under it on the same line cover (``while``/``cond`` containers hold
    their bodies' operations)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    own = [int(d) for _, _, d in evs]
    stack: List[int] = []
    for i in order:
        t, d = evs[i][1], evs[i][2]
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= t:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(t + d, evs[p][1] + evs[p][2]) - t
        stack.append(i)
    return own


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _head(instruction: str) -> str:
    """``%exp.5 = f32[32]{0:T(128)} exponential(...)`` -> ``exp.5 =
    f32[32]{0:T``: the name and the start of the result shape, which
    the trace's event name and the HLO text print alike."""
    return instruction.strip().removeprefix("ROOT ").lstrip("%").split(
        "(", 1)[0]


def hlo_ops(text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (head, ``op_name``) of a compiled module's
    HLO text (``jax.stages.Compiled.as_text()``)."""
    out = {}
    for line in text.splitlines():
        if " = " not in line or not line.lstrip().startswith(("%", "ROOT %")):
            continue
        head = _head(line)
        m = _OP_NAME.search(line)
        out[head.split(" = ", 1)[0]] = (head, m.group(1) if m else "")
    return out


def program_scopes(trace: trd.Trace, hlo_texts: Sequence[str],
                   device: int = 0) -> Dict[str, Dict[str, str]]:
    """For each ``stream_phase`` program that ran in the trace (its
    ``XLA Modules`` event name), its operations' scope paths, taken
    from the compiled HLO text whose instruction heads match most of
    the operations it ran."""
    lines = _device_lines(trace, device)
    mods = sorted((t, t + d, n) for n, t, d in lines.get("XLA Modules", [])
                  if trd._short(n) == PROGRAM)
    starts = np.asarray([m[0] for m in mods], np.int64)
    heads: Dict[str, set] = {}
    for name, t, d in trd._device_events(lines):
        k = int(np.searchsorted(starts, t, side="right")) - 1
        if k >= 0 and t + d <= mods[k][1]:
            heads.setdefault(mods[k][2], set()).add(_head(name))
    texts = [hlo_ops(x) for x in hlo_texts]
    out = {}
    for prog, seen in heads.items():
        best = max(texts, key=lambda ops: sum(
            ops.get(h.split(" = ", 1)[0], ("",))[0] == h for h in seen))
        out[prog] = {k: v[1] for k, v in best.items()}
    return out


def phase_of(path: str) -> Optional[str]:
    """The loop phase a scope path names (its first ``PHASES`` part)."""
    for part in path.split("/"):
        if part in PHASES:
            return part
    return None


def phase_self_ns(trace: trd.Trace, scopes: Dict[str, Dict[str, str]],
                  device: int = 0) -> Dict[str, int]:
    """Device self time per loop phase, over the ``XLA Ops`` events
    inside ``stream_phase`` executions within the trace's window.
    ``scopes`` maps a program execution's name (``XLA Modules`` event,
    e.g. ``jit_stream_phase(12)``) to its operations' scope paths."""
    lines = _device_lines(trace, device)
    w0, w1 = trd.window(trace)
    evs = trd._device_events(lines)
    own = self_times(evs)
    mods = sorted((t, t + d, n) for n, t, d in lines.get("XLA Modules", [])
                  if trd._short(n) == PROGRAM)
    starts = np.asarray([m[0] for m in mods], np.int64)
    out = {p: 0 for p in PHASES}
    for (name, t, d), o in zip(evs, own):
        if t < w0 or t + d > w1 or not len(starts):
            continue
        k = int(np.searchsorted(starts, t, side="right")) - 1
        if k < 0 or t + d > mods[k][1]:
            continue
        phase = phase_of(scopes.get(mods[k][2], {}).get(trd._short(name),
                                                          ""))
        if phase is not None:
            out[phase] += o
    return out


def fit_share(trace: trd.Trace, scopes, device: int = 0) -> Optional[float]:
    """Device self time under ``gp_fit`` over self time under any of
    the three loop phases, inside ``stream_phase``."""
    t = phase_self_ns(trace, scopes, device)
    total = sum(t.values())
    return t["gp_fit"] / total if total else None


def _complement(iv: np.ndarray, lo: int, hi: int) -> np.ndarray:
    edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, np.int64).reshape(-1, 2)


def _length(iv: np.ndarray) -> int:
    return int((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0


def _span_union(rows, names, clock: Clock, w0: int, w1: int) -> np.ndarray:
    iv = np.asarray([(max(s + clock.offset, w0), min(e + clock.offset, w1))
                     for n, s, e, _, _ in rows
                     if n in names and clock.inside((n, s, e, None, None))],
                    np.int64).reshape(-1, 2)
    return trd._merge(iv[iv[:, 1] > iv[:, 0]])


def idle_cover(rows, clock: Clock, trace: trd.Trace,
               device: int = 0) -> dict:
    """The trace window's device-idle time, and how much of it the
    server spent asleep (``serve.idle``) and how much in its own work
    (inside ``serve.round`` and not in ``serve.idle``), in ns."""
    w0, w1 = trd.window(trace)
    evs = trd._device_events(_device_lines(trace, device))
    busy = trd._merge(np.asarray(
        [(max(t, w0), min(t + d, w1)) for _, t, d in evs
         if t < w1 and t + d > w0], np.int64).reshape(-1, 2))
    idle = _complement(busy, w0, w1)
    asleep = _span_union(rows, ("serve.idle",), clock, w0, w1)
    rounds = _span_union(rows, ("serve.round",), clock, w0, w1)
    working = _intersect(rounds, _complement(asleep, w0, w1))
    return dict(window=w1 - w0, idle=_length(idle),
                asleep=_length(_intersect(idle, asleep)),
                working=_length(_intersect(idle, working)))


def host_gap_share(rows, clock: Clock, trace: trd.Trace,
                   device: int = 0) -> Optional[float]:
    """Share of the trace window in which the device is idle and the
    host is inside a serving round but not asleep in ``serve.idle``."""
    c = idle_cover(rows, clock, trace, device)
    return c["working"] / c["window"] if c["window"] > 0 else None
