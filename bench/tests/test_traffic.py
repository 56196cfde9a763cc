"""The benchmark's traffic generators: deterministic per seed, the same
work in another order across seeds, and the stated rates."""
import collections
import json
import numpy as np
import pytest

from bench.lib import traffic as tr

REQ = {"archs": ["vgg19", "resnet101"], "budgets": [6, 20]}
CHANNEL = {"seed": 0, "n_frames": 450}


@pytest.mark.parametrize("kind", ["poisson", "bursty"])
def test_arrivals_deterministic_per_seed(kind):
    t = {"arrivals": kind, "rate_hz": 25.0, "burst_len": 8,
         "burst_rate_hz": 200.0}
    a = tr.arrivals(t, 500, seed=2 ** 31 + 5)
    assert np.array_equal(a, tr.arrivals(t, 500, seed=2 ** 31 + 5))
    assert not np.array_equal(a, tr.arrivals(t, 500, seed=2 ** 31 + 6))
    assert np.all(np.diff(a) > 0)


def test_request_stream_same_work_other_order():
    a = tr.take(tr.request_stream(REQ, CHANNEL, 1), 64)
    b = tr.take(tr.request_stream(REQ, CHANNEL, 1), 64)
    c = tr.take(tr.request_stream(REQ, CHANNEL, 2), 64)
    assert a == b and a != c
    pairs = collections.Counter((r[0], r[1]) for r in a)
    assert pairs == collections.Counter((r[0], r[1]) for r in c)
    assert set(pairs.values()) == {16}


def test_poisson_blocks_span_equal_time():
    t = tr.poisson_arrivals(128, rate_hz=40.0, seed=9, block=64)
    assert t[63] == pytest.approx(t[127] - t[63], rel=1e-12)
    assert 64 / t[63] == pytest.approx(40.0, rel=0.02)


def test_bursty_mean_rate_is_the_traffic_files(tmp_path):
    path = tmp_path / "bursty.json"
    path.write_text(json.dumps({"loop": "open", "arrivals": "bursty",
                                "burst_len": 8, "burst_rate_hz": 200.0,
                                "rate_hz": 21.28}))
    spec = json.loads(path.read_text())
    burst = spec["burst_len"]
    n = burst * 16 * 8
    t = tr.arrivals(spec, n, seed=3)
    # n arrivals span n / rate seconds, less the idle gap after the last
    # burst, which the arrivals do not include
    idle = burst / spec["rate_hz"] - burst / spec["burst_rate_hz"]
    assert (t[-1] + idle) == pytest.approx(n / spec["rate_hz"], rel=0.01)
    gaps = np.diff(t.reshape(-1, burst), axis=1)
    assert np.mean(gaps) < 2.5 / spec["burst_rate_hz"]


def test_bursty_refuses_a_rate_with_no_idle_time():
    with pytest.raises(ValueError):
        tr.bursty_arrivals(16, rate_hz=300.0, burst_len=8,
                           burst_rate_hz=200.0, seed=0)


def test_mmobile_copy_is_seeded():
    a = tr.synth_mmobile_trace(seed=4)
    assert np.array_equal(a, tr.synth_mmobile_trace(seed=4))
    assert a.shape == (450,) and abs(a.mean() + 102.64) < 5
