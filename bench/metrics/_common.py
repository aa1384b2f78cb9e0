"""Helpers the metric readers share.  A reader is ``<metric>.py`` beside
this file with ``read(rec) -> float | None``; ``rec`` is the run record
that ``bench.harness.record`` builds (times in seconds from the window's
start)."""
import math

import numpy as np


def latencies(rec: dict) -> list[float]:
    """Latency of every request due in the window, from when it was due
    (scheduled in an open loop, sent in a closed one) to its reply; a
    request that failed, was shed or answered wrongly counts as beyond
    any limit."""
    return [r["done"] - r["due"] if r["correct"] else math.inf
            for r in rec["requests"]]


def latency_percentile(rec: dict, q: float):
    lat = latencies(rec)
    if not lat:
        return None
    with np.errstate(invalid="ignore"):     # inf - inf between two misses
        v = float(np.percentile(np.asarray(lat, float), q))
    return 1e3 * v if math.isfinite(v) else None
