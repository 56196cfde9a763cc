"""Median emit-minus-due time of every request due in the window (ms)."""
from bench.lib.latency import percentile


def read(record):
    lat = record["latencies_ms"]
    return None if lat is None else percentile(lat, 50)
