"""Median latency over every request due in the window."""
from bench.metrics._common import latency_percentile


def read(rec):
    return latency_percentile(rec, 50)
