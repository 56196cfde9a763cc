"""Reduction of a profiler trace to device busy time, top device
operations and the longest device-idle gaps.

A trace is read into plain data: ``{plane name: {line name: [(event
name, start ns, duration ns), ...]}}``. The traced window is the host
span named ``WINDOW`` that the benchmark opens around its measured
window. Busy time is the union of the device's ``XLA Ops`` events inside
that window; each idle gap is named by the host event that overlaps it
most (the jitted call being dispatched, a transfer, a wait).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINES = ("XLA Ops", "XLA Modules")
# host bookkeeping of the runtime's thread pools, never a cause
HOST_NOISE = ("ThreadpoolListener", "SlinkyThreadPool", WINDOW)
TOP = 10

Trace = Dict[str, Dict[str, List[Tuple[str, int, int]]]]


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out: Trace = {}
    for plane in pd.planes:
        if plane.name != HOST_PLANE and not DEVICE_PLANE.match(plane.name):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines[line.name] = [(ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)) for ev in line.events]
    return out


def window(trace: Trace) -> Tuple[int, int]:
    for evs in trace.get(HOST_PLANE, {}).values():
        for name, t, d in evs:
            if name == WINDOW:
                return t, t + d
    raise ValueError(f"the trace has no {WINDOW!r} span")


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of (start, end) intervals, sorted."""
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def _device_events(lines: dict) -> list:
    for name in OPS_LINES:
        if lines.get(name):
            return lines[name]
    return []


def _short(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``;
    ``jit_stream_phase(1234)`` -> ``jit_stream_phase``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def _op_names(lines: dict, evs: list) -> list:
    """Each operation's short name, prefixed by the program (XLA module)
    it ran in."""
    mods = sorted(lines.get("XLA Modules", []), key=lambda e: e[1])
    starts = np.asarray([t for _, t, _ in mods], np.int64)
    out = []
    for name, t, _ in evs:
        k = int(np.searchsorted(starts, t, side="right")) - 1
        inside = k >= 0 and t < mods[k][1] + mods[k][2]
        out.append(f"{_short(mods[k][0])}/{_short(name)}" if inside
                   else _short(name))
    return out


def reduce(trace: Trace, devices=(0,)) -> dict:
    """``busy_s`` (mean over ``devices``), ``window_s``, the top device
    operations by total time, and the longest idle gaps of the first
    device, each named by the host event overlapping it most."""
    t0, t1 = window(trace)
    busy, ops, gaps = [], {}, None
    for dev in devices:
        lines = trace.get(f"/device:TPU:{dev}", {})
        evs = _device_events(lines)
        if not evs:
            raise ValueError(f"no device operations on TPU {dev}")
        iv = np.asarray([(max(t, t0), min(t + d, t1)) for _, t, d in evs
                         if t < t1 and t + d > t0], np.int64).reshape(-1, 2)
        for name, (_, t, d) in zip(_op_names(lines, evs), evs):
            if t < t1 and t + d > t0:
                ops[name] = ops.get(name, 0) + min(t + d, t1) - max(t, t0)
        merged = _merge(iv)
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
        if gaps is None:
            edges = np.concatenate([[t0], merged.ravel(), [t1]])
            g = edges.reshape(-1, 2)
            gaps = g[g[:, 1] > g[:, 0]]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    longest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])][:TOP]
    host = [(n, t, d) for evs in trace.get(HOST_PLANE, {}).values()
            for n, t, d in evs if not n.startswith(HOST_NOISE)]
    names = [n for n, _, _ in host]
    start = np.asarray([t for _, t, _ in host], np.int64)
    dur = np.asarray([d for _, _, d in host], np.int64)
    return dict(busy_s=float(np.mean(busy)), window_s=(t1 - t0) * 1e-9,
                device_ops=[[n, d * 1e-9] for n, d in top_ops],
                idle_gaps=[[_host_cause(names, start, dur, s, e),
                            (e - s) * 1e-9] for s, e in longest])


def _host_cause(names, start, dur, g0: int, g1: int) -> str:
    """The host event overlapping [g0, g1) most, if it covers at least
    half of the gap; of equal overlaps, the shortest (the innermost
    call)."""
    over = np.minimum(start + dur, g1) - np.maximum(start, g0)
    hit = np.flatnonzero(2 * over >= g1 - g0)
    if not hit.size:
        return "host Python (no JAX event)"
    best = hit[np.lexsort((dur[hit], -over[hit]))[0]]
    return names[best]
