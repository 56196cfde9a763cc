"""Trace reduction on a small recorded trace: busy and idle time, top
device operations, and idle gaps named by the host event in them."""
import pytest

from bench.lib import trace_reduce as trd

MS = 1_000_000


def _trace():
    return {
        "/host:CPU": {
            "python": [(trd.WINDOW, 0, 100 * MS),
                       ("PjitFunction(stream_phase)", 0, 2 * MS),
                       ("ThreadpoolListener::StartRegion", 40 * MS, 30 * MS),
                       ("PjitFunction(admit_init)", 45 * MS, 20 * MS),
                       ("TransferToDevice", 50 * MS, 5 * MS)],
        },
        "/device:TPU:0": {
            "XLA Modules": [("jit_stream_phase(123)", 2 * MS, 38 * MS),
                            ("jit_admit_init(45)", 70 * MS, 10 * MS)],
            "XLA Ops": [("%fusion.1 = f32[32]{0} fusion(%p)", 2 * MS, 20 * MS),
                        ("%while.3 = (s32[]) while(%t)", 20 * MS, 20 * MS),
                        ("%fusion.1 = f32[32]{0} fusion(%q)", 70 * MS, 10 * MS),
                        ("%copy.2 = f32[8]{0} copy(%r)", 95 * MS, 10 * MS)],
        },
        "/device:TPU:1": {"XLA Ops": [("fusion.9", 0, 50 * MS)]},
    }


def test_busy_idle_and_top_ops():
    r = trd.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    # union of [2,40], [70,80], [95,100] ms
    assert r["busy_s"] == pytest.approx(0.053)
    # named by program and operation; while.3 overlaps fusion.1, and
    # copy.2 runs past the window's end and outside any program
    assert r["device_ops"] == [
        ["jit_stream_phase/fusion.1", pytest.approx(0.020)],
        ["jit_stream_phase/while.3", pytest.approx(0.020)],
        ["jit_admit_init/fusion.1", pytest.approx(0.010)],
        ["copy.2", pytest.approx(0.005)]]


def test_idle_gaps_named_by_host_event():
    gaps = trd.reduce(_trace())["idle_gaps"]
    # [40, 70] ms: the admission dispatch overlaps it most, not the
    # thread-pool bookkeeping or the window span itself
    assert gaps[0] == ["PjitFunction(admit_init)", pytest.approx(0.030)]
    assert gaps[1] == ["host Python (no JAX event)", pytest.approx(0.015)]
    assert gaps[2] == ["PjitFunction(stream_phase)", pytest.approx(0.002)]


def test_busy_is_averaged_over_the_cells_chips():
    r = trd.reduce(_trace(), devices=(0, 1))
    assert r["busy_s"] == pytest.approx((0.053 + 0.050) / 2)


def test_trace_without_window_or_device_ops_is_refused():
    t = _trace()
    with pytest.raises(ValueError):
        trd.reduce({"/device:TPU:0": t["/device:TPU:0"]})
    t.pop("/device:TPU:0")
    with pytest.raises(ValueError):
        trd.reduce(t)
