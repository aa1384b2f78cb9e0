"""Profiler capture of a bounded part of the window, and its reduction.

The traced part is whole launches: the profiler starts before launch
``first`` and stops after launch ``last`` (both counted within the window),
and the harness's own host spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``) mark what the host was doing meanwhile.
:func:`reduce_trace` works on plain event tuples, so that a recorded trace
can be reduced without a chip (see ``tests/``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import tempfile
import time
from collections import defaultdict
from typing import Iterable

import numpy as np

# (plane, line, name, start_ns, duration_ns)
Event = tuple

TRACED_SPAN = "bench.traced"


class Tracer:
    """Profiles launches ``first`` to ``first + count - 1`` of the window.
    The Python tracer is off (it would record every call of the host's
    image work); host spans come from ``TraceAnnotation``."""

    def __init__(self, first: int, count: int):
        self.first = int(first)
        self.last = int(first) + int(count) - 1
        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None
        self.timings: dict = {}

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        t = time.perf_counter()
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self.timings["trace_start_s"] = time.perf_counter() - t
        self._span = jax.profiler.TraceAnnotation(TRACED_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        self._span = None
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.timings["trace_stop_s"] = time.perf_counter() - t

    def close(self) -> None:
        """Stop the profiler if the window ended while it ran."""
        if self._span is not None:
            self.stop()

    def read(self) -> list:
        """The trace's events, read from the profiler's xplane file; the
        directory is removed afterwards."""
        from jax.profiler import ProfileData
        try:
            paths = glob.glob(os.path.join(
                self._dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths:
                return []
            return list(xplane_events(ProfileData.from_file(paths[0])))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def xplane_events(profile) -> Iterable[Event]:
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                yield (plane.name, line.name, e.name, float(e.start_ns),
                       float(e.duration_ns))


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and "TPU" in plane


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge ``(start, end)`` intervals; returns them sorted, disjoint."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    # an interval opens a new piece where it starts after all before it end
    first = np.flatnonzero(np.r_[True, iv[1:, 0] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(iv) - 1]
    return np.stack([iv[first, 0], reach[last]], axis=1)


def reduce_trace(events: list, *, ops_line: str = "XLA Ops",
                 modules_line: str = "XLA Modules",
                 loop_module: str = "jit_run", top: int = 10) -> dict:
    """Reduce a trace to the per-layer numbers the harness reports.

    - ``window_s``: the length of the traced span (``bench.traced``).
    - ``busy_s``: the union of the device's operation intervals inside
      it, averaged over the device planes that ran anything.
    - ``loop_s``: device durations of the resident loop's module
      (``loop_module``, matched as a name prefix), one per launch.
    - ``device_ops``: the ``top`` operations by total device time.
    - ``idle_gaps``: the ``top`` longest gaps between device operations
      inside the window, each named by the innermost host span of the
      harness (``bench.*``) open at the gap's middle.
    """
    spans = [(s, s + d, n) for p, _l, n, s, d in events
             if not is_device_plane(p) and n.startswith("bench.")]
    win = [(s, e) for s, e, n in spans if n == TRACED_SPAN]
    if not win:
        return {}
    w0, w1 = win[0]
    per_plane: dict[str, list] = defaultdict(list)
    op_time: dict[str, float] = defaultdict(float)
    loop_s = []
    for plane, line, name, s, d in events:
        if not is_device_plane(plane):
            continue
        if line == ops_line:
            per_plane[plane].append((max(s, w0), min(s + d, w1)))
            # an op's event name is its HLO text: keep "%name"
            op_time[name.split(" = ", 1)[0]] += d * 1e-9
        elif line == modules_line and name.startswith(loop_module):
            loop_s.append(d * 1e-9)
    busy = []
    gaps = []
    for plane, iv in sorted(per_plane.items()):
        iv = np.asarray([x for x in iv if x[1] > x[0]], float)
        u = _union(iv)
        if not len(u):
            continue
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        for s, e in edges:
            if e > s:
                gaps.append((s, e))
    if not busy:
        return {}
    spans.sort(key=lambda x: x[0])

    def host_at(t: float) -> str:
        inner = "none"
        best = None
        for s, e, n in spans:
            if s <= t <= e and n != TRACED_SPAN and \
                    (best is None or e - s < best):
                inner, best = n, e - s
        return inner

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": float(np.mean(busy)),
        "loop_s": loop_s,
        "device_ops": [[n, t] for n, t in sorted(
            op_time.items(), key=lambda x: x[1], reverse=True)[:top]],
        "idle_gaps": [[host_at((s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }


def dump(events: list, path: str, ops_limit: int = 100_000) -> None:
    """Write the events as gzipped JSON, with at most ``ops_limit`` device
    operations (for looking at a trace by hand, or recording one for the
    tests)."""
    ops = [e for e in events if e[1] == "XLA Ops"]
    rest = [e for e in events if e[1] != "XLA Ops"]
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in rest + ops[:ops_limit]], f)


def load(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]
