"""The reduction from profiler events to per-layer numbers."""
import math
from pathlib import Path

import pytest

from bench.trace import TRACED_SPAN, reduce_trace

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1_000_000          # nanoseconds


def synthetic():
    """A 100 ms traced window: one launch whose loop module runs 20-70 ms,
    made of two ops (20-40, 45-70) and one more op at 80-90, while the
    host pumps from 10 to 75 and waits from 75 to 100."""
    return [
        (HOST, "python", TRACED_SPAN, 0, 100 * MS),
        (HOST, "python", "bench.pump", 10 * MS, 65 * MS),
        (HOST, "python", "bench.wait", 75 * MS, 25 * MS),
        (HOST, "python", "other", 0, 100 * MS),
        (DEV, "XLA Modules", "jit_run(7)", 20 * MS, 50 * MS),
        (DEV, "XLA Modules", "jit_other", 80 * MS, 10 * MS),
        (DEV, "XLA Ops", "fusion.1", 20 * MS, 20 * MS),
        (DEV, "XLA Ops", "while.2", 45 * MS, 25 * MS),
        (DEV, "XLA Ops", "fusion.1", 30 * MS, 5 * MS),   # overlaps
        (DEV, "XLA Ops", "copy.3", 80 * MS, 10 * MS),
        (DEV, "XLA Ops", "late", 99 * MS, 5 * MS),       # clipped at 100
    ]


def test_reduce_synthetic_trace():
    r = reduce_trace(synthetic())
    assert math.isclose(r["window_s"], 0.1)
    # union: 20-40, 45-70, 80-90, 99-100 = 56 ms
    assert math.isclose(r["busy_s"], 0.056)
    assert r["loop_s"] == [pytest.approx(0.05)]
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]
    assert [n for n, _ in r["device_ops"]] == \
        ["fusion.1", "while.2", "copy.3", "late"]
    gaps = r["idle_gaps"]
    # longest first: 0-20 (its middle, 10, is in the pump), 70-80 (at 75
    # the pump ends and the wait begins: the shorter span wins), 90-99
    # (wait), 40-45 (pump)
    assert [round(s * 1e3, 6) for _, s in gaps] == [20, 10, 9, 5]
    assert [n for n, _ in gaps] == \
        ["bench.pump", "bench.wait", "bench.wait", "bench.pump"]


def test_no_traced_span_reads_nothing():
    ev = [e for e in synthetic() if e[2] != TRACED_SPAN]
    assert reduce_trace(ev) == {}


def test_no_device_events_reads_nothing():
    ev = [e for e in synthetic() if e[0] != DEV]
    assert reduce_trace(ev) == {}


RECORDED = Path(__file__).parent / "data" / "q12_probe_device.trace.json.gz"


def test_reduce_recorded_chip_trace():
    """Device events recorded on a TPU v5e (``bench/run.py --trace 1
    --dump-trace``, cell ``q12_probe.closed``): every module of one traced
    pump and the first 8,000 operations of its resident loop, names cut
    to their ``%name``.  The host spans were not kept in that recording,
    so the traced span is put round the loop module here."""
    from bench.trace import load
    events = load(str(RECORDED))
    loops = [e for e in events
             if e[1] == "XLA Modules" and e[2].startswith("jit_run")]
    assert len(loops) == 1
    w0, w1 = loops[0][3], loops[0][3] + loops[0][4]
    r = reduce_trace(events + [("/host:CPU", "python", TRACED_SPAN,
                                w0, w1 - w0)])
    assert math.isclose(r["window_s"], (w1 - w0) * 1e-9)
    assert r["loop_s"] == [pytest.approx(loops[0][4] * 1e-9)]
    # busy time by an independent sweep over the clipped op intervals
    iv = sorted((max(s, w0), min(s + d, w1)) for p, l, _n, s, d in events
                if l == "XLA Ops" and p.startswith("/device:TPU"))
    busy, end = 0.0, -math.inf
    for s, e in iv:
        if e <= s:
            continue
        if s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    assert math.isclose(r["busy_s"], busy * 1e-9, rel_tol=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert len(r["device_ops"]) == 10
    assert {n for n, _s in r["idle_gaps"]} == {"none"}
