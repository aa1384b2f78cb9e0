"""The program's own spans and scopes in a profiler trace.

The resident launch path names its host stages with
``jax.profiler.TraceAnnotation`` (``revet.pump``, ``revet.batch.*``,
``revet.launch.*``, ``revet.pump.resolve``) and its device loop with
``jax.named_scope`` (``revet.loop``, ``revet.ready``, one
``revet.ctx.<context>.<id>`` per context), which reaches the HLO's
``op_name`` metadata.  This module reduces a trace to:

- :func:`span_self_times`: each ``revet.*`` span's wall and self time
  (its duration minus the part its child spans cover) per traced launch;
- :func:`idle_gaps`: the longest gaps between device operations, each named
  by the innermost ``bench.*`` or ``revet.*`` span open at its middle;
- :func:`loop_contexts`: the device self time of the loop's operations by
  ``revet.ctx.*`` / ``revet.ready`` scope;
- :func:`tick_us`: device time of the loop module per tick.

It works on the event tuples of :mod:`bench.trace`, so that it runs on
synthetic events without a chip (see ``tests/``).  A TPU v5e trace's
operation events carry no ``op_name``: the scopes come from the compiled
loop module's HLO text (:func:`hlo_scopes`).  Run as a script on a profile
captured around a serving process (``jax.profiler.trace(dir)``; DESIGN.md
§10):

    python3 bench/spans.py <dir or .xplane.pb> [--hlo FILE] [--ticks N ...]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Callable, Optional

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.trace import (TRACED_SPAN, _union, is_device_plane,  # noqa: E402
                         xplane_events)

PROGRAM = "revet."
PUMP = "revet.pump"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LOOP_MODULE = "jit_run"
OTHER = "other"


def _host_spans(events, prefixes=(PROGRAM,)):
    return [(p, l, n, s, s + d) for p, l, n, s, d in events
            if not is_device_plane(p) and n.startswith(prefixes)]


def _self_times(items) -> list:
    """``items``: ``(start, end)`` intervals of one timeline, each either
    nested in another or apart from it.  Returns each one's duration minus
    the durations of the intervals directly inside it, in input order."""
    order = sorted(range(len(items)),
                   key=lambda i: (items[i][0], -items[i][1]))
    child = [0.0] * len(items)
    stack: list = []
    for i in order:
        s, e = items[i]
        while stack and items[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= items[stack[-1]][1]:
            child[stack[-1]] += e - s
        stack.append(i)
    return [e - s - c for (s, e), c in zip(items, child)]


def span_self_times(events: list) -> dict:
    """Each ``revet.*`` span's mean wall and self time per traced launch
    (seconds), where the traced launches are the ``revet.pump`` spans.
    A span's children are the program's and the harness's spans nested in
    it on the same thread.  Empty when no pump was traced."""
    spans = _host_spans(events, (PROGRAM, "bench."))
    spans = [x for x in spans if x[2] != TRACED_SPAN]
    launches = sum(1 for x in spans if x[2] == PUMP)
    if not launches:
        return {}
    by_line = defaultdict(list)
    for x in spans:
        by_line[x[0], x[1]].append(x)
    wall: dict = defaultdict(float)
    own: dict = defaultdict(float)
    for line in by_line.values():
        selfs = _self_times([(s, e) for _p, _l, _n, s, e in line])
        for (_p, _l, n, s, e), t in zip(line, selfs):
            if n.startswith(PROGRAM):
                wall[n] += (e - s) * 1e-9
                own[n] += t * 1e-9
    return {n: {"wall_s": wall[n] / launches, "self_s": own[n] / launches}
            for n in sorted(wall)}


def idle_gaps(events: list, top: int = 10) -> list:
    """The ``top`` longest gaps between device operations inside the traced
    window (``bench.traced``), longest first, as ``[name, seconds]``: the
    name is the innermost ``bench.*`` or ``revet.*`` span open at the
    gap's middle ("none" where none is)."""
    spans = [(s, e, n) for _p, _l, n, s, e in
             _host_spans(events, (PROGRAM, "bench."))]
    win = [(s, e) for s, e, n in spans if n == TRACED_SPAN]
    if not win:
        return []
    w0, w1 = win[0]
    per_plane = defaultdict(list)
    for plane, line, _n, s, d in events:
        if is_device_plane(plane) and line == OPS_LINE:
            per_plane[plane].append((max(s, w0), min(s + d, w1)))
    gaps = []
    for iv in per_plane.values():
        u = _union(np.asarray([x for x in iv if x[1] > x[0]], float))
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        gaps.extend((s, e) for s, e in edges if e > s)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    inner = [x for x in spans if x[2] != TRACED_SPAN]

    def host_at(t: float) -> str:
        open_ = [(e - s, n) for s, e, n in inner if s <= t <= e]
        return min(open_)[1] if open_ else "none"

    return [[host_at((s + e) / 2), (e - s) * 1e-9] for s, e in gaps[:top]]


def op_scope(op_name: str) -> Optional[str]:
    """The innermost ``revet.ctx.*`` or ``revet.ready`` scope of an HLO
    ``op_name`` path (``jit(run)/while/body/revet.loop/revet.ctx.x.3/cond``)."""
    for part in reversed(op_name.split("/")):
        if part.startswith("revet.ctx.") or part == "revet.ready":
            return part
    return None


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> scope, from the ``op_name`` metadata of a
    compiled module's HLO text (``compiled.as_text()``)."""
    out = {}
    for m in re.finditer(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                         r'op_name="([^"]*)"', hlo_text, re.M):
        scope = op_scope(m.group(2))
        if scope:
            out[m.group(1)] = scope
    return out


def op_key(name: str) -> str:
    """An operation event's HLO instruction name: ``%cond.12 = ...`` ->
    ``cond.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def loop_contexts(events: list, scope_of: Callable[[str], Optional[str]]
                  ) -> dict:
    """Device self time (seconds) of the operations inside the loop
    module's runs, by scope: ``scope_of(event name)`` gives an operation's
    ``revet.ctx.*`` / ``revet.ready`` scope, or None (counted as
    ``other``).  An operation's self time leaves out the operations nested
    in it (a ``cond`` holds its branch's fusions), so the sum is the loop's
    busy time once.  Largest first; empty without a loop module."""
    loops = defaultdict(list)
    ops = defaultdict(list)
    for plane, line, name, s, d in events:
        if not is_device_plane(plane):
            continue
        if line == MODULES_LINE and name.startswith(LOOP_MODULE):
            loops[plane].append((s, s + d))
        elif line == OPS_LINE:
            ops[plane].append((name, s, s + d))
    out: dict = defaultdict(float)
    for plane, runs in loops.items():
        runs = np.asarray(sorted(runs), float)
        mine = [x for x in ops[plane] if _inside(runs, x[1], x[2])]
        selfs = _self_times([(s, e) for _n, s, e in mine])
        for (name, _s, _e), t in zip(mine, selfs):
            out[scope_of(name) or OTHER] += t * 1e-9
    return dict(sorted(out.items(), key=lambda x: x[1], reverse=True))


def _inside(runs: np.ndarray, s: float, e: float) -> bool:
    i = int(np.searchsorted(runs[:, 0], s, side="right")) - 1
    return i >= 0 and e <= runs[i, 1]


def program_share(contexts: dict) -> Optional[float]:
    """Share of the loop's device time that a program scope names."""
    total = sum(contexts.values())
    return (total - contexts.get(OTHER, 0.0)) / total if total else None


def tick_us(loop_s: list, ticks: list) -> Optional[float]:
    """Mean device time per tick (µs) over launches with both numbers."""
    per = [1e6 * s / t for s, t in zip(loop_s, ticks) if t]
    return float(np.mean(per)) if per else None


def read_profile(path: str) -> list:
    """A profile's events (the tuples of :mod:`bench.trace`), from a
    profile directory or its ``.xplane.pb``."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[0]
    return list(xplane_events(ProfileData.from_file(path)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("profile", help="a profile directory or .xplane.pb")
    ap.add_argument("--hlo", help="the loop module's compiled HLO text "
                    "(``compiled.as_text()``), for loop_contexts: a TPU "
                    "trace's operations carry no op_name")
    ap.add_argument("--ticks", type=int, nargs="*", default=[],
                    help="ticks of each traced launch, in order")
    args = ap.parse_args(argv)
    events = read_profile(args.profile)
    loops = [d * 1e-9 for p, l, n, _s, d in events if is_device_plane(p)
             and l == MODULES_LINE and n.startswith(LOOP_MODULE)]
    contexts = None
    if args.hlo:
        with open(args.hlo) as f:
            scopes = hlo_scopes(f.read())
        contexts = loop_contexts(events, lambda n: scopes.get(op_key(n)))
    print(json.dumps({
        "spans": span_self_times(events),
        "idle_gaps": idle_gaps(events),
        "loop_s": loops,
        "tick_us": tick_us(loops, args.ticks),
        "loop_contexts": contexts,
        "program_share": program_share(contexts or {}),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
