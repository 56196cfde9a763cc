"""Spans of the serving loop (``runtime/spans.py``), the live
``stream_stats()``, and the loop body's named scopes.

One short served feed (two lanes, five requests on an arrival schedule,
under a CPU profile) backs the tests that need a server."""
import glob
import os
import resource
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Scenario, default_vgg19_problem
from repro.core import wholerun as wr
from repro.core.engine_config import EngineConfig
from repro.runtime import spans as spm
from repro.runtime.stream import StreamingBayesSplitEdge

MS = 1_000_000
OUTER = "spans_test_window"
# the first arrival is late, so the server always sleeps for it once
ARRIVALS = [0.2, 0.2, 0.25, 0.3, 0.5]
STATS_KEYS = {
    "n_results", "n_dispatches", "lane_slots", "loop_evals",
    "occupancy_mean", "queue_depth_mean", "queue_depth_max", "wall_s",
    "arrivals_per_s", "rounds", "deadline_hit_rate", "max_pending",
    "pool_widths", "n_faults", "n_requeued", "n_preempted", "n_shed",
    "n_degraded", "n_pool_drops", "n_checkpoints", "deadline_total",
    "deadline_hits", "n_rejected", "n_overflow_shed", "n_grows",
    "n_shrinks", "n_backoffs", "n_rebalanced", "lane_log", "queue_depth",
    "resize_log"}


def _clocks():
    """This thread's wall clock, CPU time and count of voluntary context
    switches (blocking waits), for the off-CPU time between two reads."""
    return (time.monotonic_ns(), time.thread_time_ns(),
            resource.getrusage(resource.RUSAGE_THREAD).ru_nvcsw)


def _off_cpu_ns(a, b) -> int:
    """Wall time between clock reads ``a`` and ``b`` in which the thread
    ran no code of its own, not having asked to wait: preempted by other
    processes, or its virtual CPU taken by the host. A blocking wait in
    between counts as the thread's own time (0 is returned)."""
    if b[2] != a[2]:
        return 0
    return max(0, (b[0] - a[0]) - (b[1] - a[1]))


class _ClockedSpan(spm._Span):
    """A span that reads the thread's clocks on either side of entering
    and of leaving (its profiler annotation included)."""

    __slots__ = ("entered",)

    def __enter__(self):
        before = _clocks()
        super().__enter__()
        self.entered = (before, _clocks())
        return self

    def __exit__(self, *exc):
        before = _clocks()
        super().__exit__(*exc)
        self.rec.clocks.append((self.entered, (before, _clocks())))
        return False


class _Spans(spm.Spans):
    """The recorder, with ``clocks[j]``, the clock reads around entering
    and leaving the span of ``rows[j]``."""

    def __init__(self):
        super().__init__()
        self.clocks = []

    def span(self, name, annotate=True, **attrs):
        return _ClockedSpan(self, name, attrs, annotate)


def _reqs():
    return [Scenario(default_vgg19_problem(), seed=s, budget=b)
            for s, b in ((0, 10), (1, 12), (2, 10), (3, 12), (4, 10))]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Serve the feed under a CPU profile, reading ``stream_stats()`` at
    every result. A first pass builds the programs, so that the profiled
    one runs on the arrival schedule."""
    def engine(**kw):
        return StreamingBayesSplitEdge(
            _reqs(), EngineConfig(warm_start=False), n_lanes=2,
            budget_max=12, **kw)

    engine().run()
    rec = _Spans()
    eng = engine(arrivals=ARRIVALS, spans=rec)
    tdir = str(tmp_path_factory.mktemp("trace"))
    live = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        before = _clocks()
        with jax.profiler.TraceAnnotation(OUTER):
            outer_ns = time.monotonic_ns()
            outer_clocks = (before, _clocks())
            results, received = [], {}
            for res in eng.serve():
                received[res.index] = _clocks()
                results.append(res)
                live.append(eng.stream_stats())
    finally:
        jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(pb[0])
    host = [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]
    return dict(eng=eng, rec=rec, results=results, live=live, host=host,
                outer_ns=outer_ns, outer_clocks=outer_clocks,
                received=received)


def _rows(rec, name):
    return [r for r in rec.rows if r[0] == name]


# -- the recorder ------------------------------------------------------------

def test_null_recorder_records_nothing_and_shares_one_context():
    null = spm.NULL
    assert null.span("a", x=1) is null.span("b")
    assert null.gap() is null.span("c")
    with null.span("a"):
        null.note(x=1)
    assert list(null.rows) == []
    eng = StreamingBayesSplitEdge(_reqs(), n_lanes=2, budget_max=12)
    assert eng._spans is null


def test_nested_spans_carry_their_parent():
    rec = spm.Spans()
    with rec.span("outer", k=1):
        with rec.span("inner", reqs=[3, 4]):
            rec.note(rows=2)
    inner, outer = rec.rows
    assert outer[0] == "outer" and outer[3] is None and outer[4] == {"k": 1}
    assert inner[0] == "inner" and inner[3] == ("outer", outer[1])
    assert inner[4] == {"reqs": [3, 4], "rows": 2}
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]


def test_gap_closes_and_reopens_the_open_spans():
    rec = spm.Spans()
    with rec.span("round", round=1):
        with rec.gap():
            assert not rec._open
        with rec.span("child"):
            pass
    first, child, second = rec.rows
    assert first[0] == second[0] == "round"
    assert first[2] <= second[1] and second[4] == {"round": 1}
    assert child[3] == ("round", second[1])


def test_the_deque_is_bounded():
    rec = spm.Spans(cap=4)
    for i in range(10):
        with rec.span("s", i=i):
            pass
    assert [r[4]["i"] for r in rec.rows] == [6, 7, 8, 9]
    assert spm.Spans().rows.maxlen == spm.Spans.CAP == 2 ** 17


# -- the served feed ---------------------------------------------------------

def test_every_request_is_decoded_and_admitted_once(served):
    rec, n = served["rec"], len(ARRIVALS)
    decoded = [r[4]["req"] for r in _rows(rec, "serve.decode")]
    assert sorted(i for i in decoded if i < n) == list(range(n))
    admitted = [i for r in _rows(rec, "serve.admit") for i in r[4]["reqs"]]
    assert sorted(admitted) == list(range(n))
    flushed = [i for r in _rows(rec, "serve.readback") for i in r[4]["reqs"]]
    assert sorted(flushed) == list(range(n))
    assert all(r[3] is not None and r[3][0] == "serve.round"
               for r in rec.rows if r[0] != "serve.round")
    assert {r[4]["why"] for r in _rows(rec, "serve.idle")} == {"arrival"}
    for r in _rows(rec, "serve.dispatch"):
        assert r[4]["pool"] == 0 and r[4]["live"] >= 1 and r[4]["bucket"]


def test_the_three_pieces_add_up_to_each_latency(served):
    """Due to admission, admission to the end of the wait before the
    readback that flushed it, and that readback: their sum is the
    request's emit time minus its due time, within 1 ms of the program's
    own time. Between the readback's end and the emit stamp a loaded
    machine (other test workers, other guests of the host) can keep the
    serving thread off its CPU for milliseconds; that time is read from
    the thread's clocks, up to the first result of the flush, and is not
    the program's."""
    rec = served["rec"]
    admit = {}
    for r in sorted(_rows(rec, "serve.admit"), key=lambda r: r[1]):
        for i in r[4]["reqs"]:
            admit.setdefault(i, r[1])
    waits = sorted(_rows(rec, "serve.wait"), key=lambda r: r[2])
    emit = {res.index: res.emit_s for res in served["results"]}
    assert len(rec.clocks) == len(rec.rows)
    checked = 0
    for rb, (_, (leaving, _)) in zip(rec.rows, rec.clocks):
        reqs = rb[4].get("reqs") if rb[0] == "serve.readback" else None
        if not reqs:
            continue
        w_end = [w[2] for w in waits if w[2] <= rb[1]][-1]
        off_cpu = _off_cpu_ns(leaving, served["received"][reqs[0]])
        for i in reqs:
            due = rec.t0_ns + ARRIVALS[i] * 1e9
            pieces = (admit[i] - due) + (w_end - admit[i]) + (rb[2] - w_end)
            latency = emit[i] * 1e9 - (due - rec.t0_ns)
            assert admit[i] >= due - MS
            assert abs(pieces - latency) < MS + off_cpu
            checked += 1
    assert checked == len(ARRIVALS)


def test_stream_stats_is_live_during_serve(served):
    live, eng = served["live"], served["eng"]
    dispatches = [s["n_dispatches"] for s in live]
    assert dispatches == sorted(dispatches) and dispatches[-1] > dispatches[0]
    assert [s["n_results"] for s in live] == list(range(1, len(live) + 1))
    end = eng.stream_stats()
    assert set(end) == STATS_KEYS
    assert end["n_results"] == len(served["results"]) == len(ARRIVALS)
    assert end["n_dispatches"] == len(end["lane_log"]) >= dispatches[-1]
    assert end["rounds"] == eng._round
    assert end == eng.stream_stats()        # frozen once serve() ended
    assert StreamingBayesSplitEdge(_reqs(), n_lanes=2,
                                   budget_max=12).stream_stats() == {}


def test_each_span_is_a_host_event_on_the_profilers_clock(served):
    """Each span's start and duration match its profiler event's within
    1 ms of the program's own time; off-CPU time between the span's
    clock stamps and the annotation's, read as in the test above, is
    not the program's."""
    host, rec = served["host"], served["rec"]
    outer = [t for n, t, _ in host if n == OUTER]
    assert len(outer) == 1
    offset = outer[0] - served["outer_ns"]
    off_outer = _off_cpu_ns(*served["outer_clocks"])
    events = {}
    for n, t, d in host:
        if n.startswith("serve."):
            events.setdefault(n, []).append((t, d))
    # serve.round holds the others and stays out of the profile
    rows = [(r, c) for r, c in zip(rec.rows, rec.clocks)
            if r[0] != "serve.round"]
    assert set(events) == {r[0] for r, _ in rows} == {
        "serve.decode", "serve.admit", "serve.dispatch", "serve.prestage",
        "serve.wait", "serve.readback", "serve.idle"}
    for (name, s, e, _, _), (entering, leaving) in rows:
        off_in, off_out = _off_cpu_ns(*entering), _off_cpu_ns(*leaving)
        t = np.asarray([t for t, _ in events[name]])
        k = int(np.argmin(np.abs(t - (s + offset))))
        t_ev, d_ev = events[name][k]
        assert abs(t_ev - (s + offset)) < MS + off_in + off_outer
        assert abs(d_ev - (e - s)) < MS + off_in + off_out
    assert len(rows) == sum(len(v) for v in events.values())


def test_stream_phase_carries_the_loop_phase_scopes(served):
    eng = served["eng"]
    p = eng._pools[0]
    text = wr.stream_phase.lower(
        p.run_data, p.state, p.it, jnp.int32(1), eng.grid, eng.wvec,
        eng.cfg, 16, True).as_text(debug_info=True)
    for scope in ("gp_fit", "acquisition", "oracle_step"):
        assert f"/{scope}/" in text
