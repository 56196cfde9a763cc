"""Span reduction on a small recorded trace and the server's span rows:
each per-layer reading, self time under nested control flow, the clock
join, and the profiled part's bounds."""
import pytest

from bench.lib import span_reduce as sr
from bench.lib import trace_reduce as trd

MS = 1_000_000
# the trace's clock is the monotonic clock minus one second
MONO = 1000 * MS
T0 = 900 * MS                          # the server's start
ARRIVALS = [0.105, 0.109]              # due at 1005 and 1009 ms
PAUSES = [(990 * MS, 1000 * MS), (1100 * MS, 1150 * MS)]
SCOPE = "jit(stream_phase)/while/body/cond/branch_1_fun/"


def _row(name, s, e, **attrs):
    return (name, MONO + int(s * MS), MONO + int(e * MS), None, attrs)


def _rows():
    return [
        _row("serve.round", 1, 40, round=7),
        _row("serve.decode", 6, 7, req=0),
        _row("serve.admit", 8, 12, pool=0, k=1, reqs=[0], prestaged=0),
        _row("serve.decode", 12, 12.5, req=1),
        _row("serve.admit", 13, 15, pool=0, k=1, reqs=[1], prestaged=1),
        _row("serve.dispatch", 16, 17, pool=0, live=2, bucket=16),
        _row("serve.prestage", 17, 18, n=0),
        _row("serve.wait", 18, 30, pool=0),
        _row("serve.readback", 30, 36, pool=0, rows=2, iters=4,
             reqs=[0, 1]),
        _row("serve.round", 41, 95, round=8),
        _row("serve.idle", 45, 90, why="arrival"),
        _row("serve.readback", 92, 93, pool=0, rows=0, iters=0, reqs=[]),
        # straddle the tracer's stop pause: left out
        _row("serve.round", 96, 105, round=9),
        _row("serve.decode", 98, 102, req=2),
    ]


def _ops(rows):
    return [(name, int(t * MS), int(d * MS)) for name, t, d in rows]


def _trace():
    return {
        "/host:CPU": {"python": [(trd.WINDOW, 0, 100 * MS)]},
        "/device:TPU:0": {
            "XLA Modules": _ops([("jit_admit_init(3)", 9, 2),
                                 ("jit_stream_phase(7)", 17, 11)]),
            "XLA Ops": _ops([
                ("%fusion.9 = f32[2]{0} fusion(%a)", 9, 2),
                ("%while.1 = (s32[], f32[16]{0}) while(%t)", 17, 10),
                ("%fusion.2 = f32[16]{0} fusion(%p)", 18, 3),
                ("%cond.3 = (f32[16]{0}) conditional(%q)", 22, 4),
                ("%fusion.4 = f32[16,2]{1,0} fusion(%r)", 23, 2),
                ("%fusion.5 = pred[16]{0} fusion(%s)", 27, 1)]),
        },
    }


def _hlo(rows_dim, phases):
    """A compiled module's text: the same instruction names at another
    dataset bucket map to other scopes."""
    ops = [("while.1", "(s32[], f32[{n}]{{0}}) while(%t)", "gp_fit/while"),
           ("fusion.2", "f32[{n}]{{0}} fusion(%p)", "gp_fit/mul"),
           ("cond.3", "(f32[{n}]{{0}}) conditional(%q)", "acquisition/cond"),
           ("fusion.4", "f32[{n},2]{{1,0}} fusion(%r)", "acquisition/add"),
           ("fusion.5", "pred[{n}]{{0}} fusion(%s)", "oracle_step/and")]
    lines = ["HloModule jit_stream_phase, is_scheduled=true", "",
             "ENTRY %main.1 (p: f32[16]) -> f32[16] {"]
    for (name, rhs, scope), phase in zip(ops, phases):
        scope = f"{phase}/{scope.split('/', 1)[1]}"
        lines.append(f'  %{name} = {rhs.format(n=rows_dim)}, '
                     f'metadata={{op_name="{SCOPE}{scope}" '
                     f'stack_frame_id=6}}')
    lines.append("  ROOT %tuple.9 = (f32[16]{0}) tuple(%fusion.2)")
    lines.append("}")
    return "\n".join(lines)


HLO_16 = _hlo(16, ("gp_fit", "gp_fit", "acquisition", "acquisition",
                   "oracle_step"))
HLO_32 = _hlo(32, ("oracle_step",) * 5)


def _clock():
    return sr.Clock(T0, MONO, PAUSES, _trace())


def test_clock_joins_on_the_window_annotation():
    c = _clock()
    assert c.offset == -MONO
    assert c.inside(_rows()[0]) and not c.inside(_rows()[-1])


def test_host_span_means_leave_out_spans_across_a_pause():
    rows, c = _rows(), _clock()
    assert sr.decode_ms(rows, c) == pytest.approx(0.75)
    assert sr.admit_ms(rows, c) == pytest.approx(3.0)
    # only the readback that flushed lanes
    assert sr.readback_ms(rows, c) == pytest.approx(6.0)
    assert sr.decode_ms(rows[:1], c) is None


def test_queue_wait_is_first_admission_minus_due_time():
    rows = _rows()
    # a second admission of request 0 (a requeue) does not count
    rows.append(_row("serve.admit", 50, 51, pool=0, k=1, reqs=[0],
                     prestaged=1))
    assert sr.queue_wait_ms(rows, _clock(), ARRIVALS, 1.0) == \
        pytest.approx(3.5)


def test_latency_split_adds_up_to_emit_minus_due():
    emit_s = {0: 0.136, 1: 0.136}        # the readback's end, 1036 ms
    split = sr.latency_split(_rows(), _clock(), ARRIVALS, 1.0, emit_s)
    assert [s["req"] for s in split] == [0, 1]
    a, b = split
    assert (a["queue"], a["device"], a["readback"]) == (3 * MS, 22 * MS,
                                                        6 * MS)
    assert (b["queue"], b["device"], b["readback"]) == (4 * MS, 17 * MS,
                                                        6 * MS)
    assert all(abs(s["residual"]) < 1e3 for s in split)


def test_iter_device_ms_joins_programs_to_their_dispatch():
    # 11 ms of stream_phase over the 4 iterations its readback counted;
    # the admission program is not a loop iteration
    assert sr.iter_device_ms(_rows(), _clock(), _trace()) == \
        pytest.approx(11 / 4)


def test_self_time_of_nested_while_and_cond():
    evs = _trace()["/device:TPU:0"]["XLA Ops"][1:]
    assert sr.self_times(evs) == [3 * MS, 3 * MS, 2 * MS, 2 * MS, 1 * MS]
    # siblings inside one container, and a child running past its end
    assert sr.self_times([("w", 0, 10), ("a", 1, 2), ("b", 4, 2),
                          ("c", 9, 3)]) == [5, 2, 2, 3]


def test_program_scopes_pick_the_matching_compiled_text():
    scopes = sr.program_scopes(_trace(), [HLO_32, HLO_16])
    assert set(scopes) == {"jit_stream_phase(7)"}
    got = scopes["jit_stream_phase(7)"]
    assert sr.phase_of(got["while.1"]) == "gp_fit"
    assert sr.phase_of(got["fusion.5"]) == "oracle_step"
    assert sr.hlo_ops(HLO_16)["fusion.4"][0] == \
        "fusion.4 = f32[16,2]{1,0} fusion"


def test_fit_share_is_gp_fit_self_time_over_the_three_phases():
    scopes = sr.program_scopes(_trace(), [HLO_16, HLO_32])
    own = sr.phase_self_ns(_trace(), scopes)
    assert own == {"gp_fit": 6 * MS, "acquisition": 4 * MS,
                   "oracle_step": 1 * MS}
    assert sr.fit_share(_trace(), scopes) == pytest.approx(6 / 11)
    assert sr.fit_share(_trace(), {}) is None


def test_host_gap_share_counts_idle_device_time_in_server_work():
    rows, c, t = _rows(), _clock(), _trace()
    cover = sr.idle_cover(rows, c, t)
    # busy [9, 11] and [17, 28] ms of the 100 ms window
    assert cover["idle"] == 87 * MS
    assert cover["asleep"] == 45 * MS
    # rounds minus the sleep, where the device is idle: [1, 9], [11, 17],
    # [28, 40], [41, 45], [90, 95]; the round across the pause is out
    assert cover["working"] == 35 * MS
    assert sr.host_gap_share(rows, c, t) == pytest.approx(0.35)
