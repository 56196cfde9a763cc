"""Latency statistics: a missing answer is a miss in the tail."""
import math

from bench.lib.latency import latencies_ms, percentile


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert percentile(v, 50) == 50
    assert percentile(v, 95) == 95


def test_missing_request_is_infinite_in_the_tail():
    due = {i: 0.0 for i in range(20)}
    emitted = {i: 0.1 for i in range(19)}           # request 19 never emits
    lat = latencies_ms(due, emitted)
    assert lat.count(math.inf) == 1
    assert percentile(lat, 50) == 100.0
    assert percentile(lat, 95) == 100.0              # 19 of 20 answered
    emitted.pop(18)
    assert percentile(latencies_ms(due, emitted), 95) == math.inf


def test_latency_counts_from_due_time():
    assert latencies_ms({3: 1.5}, {3: 2.0}) == [500.0]


def test_queue_summary_reads_each_third_of_the_window():
    from bench.lib.harness import queue_summary
    depth = [(0.5, 3), (1.0, 1), (4.0, 2), (8.5, 5), (9.9, 0)]
    assert queue_summary(depth, 10.0) == (
        "queue_depth_max=5 queue_depth_max_by_third=[3, 2, 5]")
    assert queue_summary([], 10.0).startswith("queue_depth_max=0 ")
