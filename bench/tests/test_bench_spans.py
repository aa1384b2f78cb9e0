"""The reduction of the program's own spans and scopes (bench/spans.py)."""
import json
import math
import time

import jax
import pytest
from jax.profiler import TraceAnnotation

from bench import spans
from bench.spans import (OTHER, hlo_scopes, idle_gaps, loop_contexts,
                         op_key, op_scope, program_share, span_self_times,
                         tick_us)
from bench.trace import TRACED_SPAN

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1_000_000          # nanoseconds


def pump():
    """One traced pump of 100 ms inside the harness's ``bench.pump``:
    check 0-2, fuse 2-10, upload 10-30, loop 30-60, readback 60-85,
    split 85-95, resolve 95-98; nothing names 98-100.  The device runs
    the loop module 32-58 and a copy at 12-14."""
    return [
        (HOST, "python", TRACED_SPAN, 0, 110 * MS),
        (HOST, "python", "bench.pump", 0, 101 * MS),
        (HOST, "python", "revet.pump", 0, 100 * MS),
        (HOST, "python", "revet.batch.check", 0, 2 * MS),
        (HOST, "python", "revet.batch.fuse", 2 * MS, 8 * MS),
        (HOST, "python", "revet.launch.upload", 10 * MS, 20 * MS),
        (HOST, "python", "revet.launch.loop", 30 * MS, 30 * MS),
        (HOST, "python", "revet.launch.readback", 60 * MS, 25 * MS),
        (HOST, "python", "revet.batch.split", 85 * MS, 10 * MS),
        (HOST, "python", "revet.pump.resolve", 95 * MS, 3 * MS),
        (HOST, "python", "PjitFunction(run)", 30 * MS, 1 * MS),
        (HOST, "python", "bench.collect", 101 * MS, 9 * MS),
        (DEV, "XLA Modules", "jit_run(7)", 32 * MS, 26 * MS),
        (DEV, "XLA Ops", "copy.1", 12 * MS, 2 * MS),
        (DEV, "XLA Ops", "%while.2 = s32[] while(...)", 32 * MS, 26 * MS),
        (DEV, "XLA Ops", "%cond.3 = (...) conditional(...)", 33 * MS,
         10 * MS),
        (DEV, "XLA Ops", "%fusion.4 = s32[8] fusion(...)", 34 * MS, 6 * MS),
        (DEV, "XLA Ops", "%fusion.5 = pred[] fusion(...)", 44 * MS, 4 * MS),
        (DEV, "XLA Ops", "%cond.6 = (...) conditional(...)", 49 * MS,
         8 * MS),
    ]


SCOPES = {"cond.3": "revet.ctx.probe.3", "fusion.4": "revet.ctx.probe.3",
          "fusion.5": "revet.ready", "cond.6": "revet.ctx.emit.5"}


def scope_of(name):
    return SCOPES.get(op_key(name))


def test_span_self_times():
    sp = span_self_times(pump())
    assert set(sp) == {"revet.pump", "revet.batch.check", "revet.batch.fuse",
                       "revet.launch.upload", "revet.launch.loop",
                       "revet.launch.readback", "revet.batch.split",
                       "revet.pump.resolve"}
    # the pump's own time is what no stage covers: 98-100
    assert sp["revet.pump"]["wall_s"] == pytest.approx(0.1)
    assert sp["revet.pump"]["self_s"] == pytest.approx(0.002)
    assert sp["revet.launch.upload"]["self_s"] == pytest.approx(0.02)
    # a span that is no program's or harness's (PjitFunction) is no child
    assert sp["revet.launch.loop"]["self_s"] == pytest.approx(0.03)
    assert sp["revet.batch.fuse"]["wall_s"] == pytest.approx(0.008)


def test_span_self_times_per_traced_launch():
    """Two traced pumps: every number is the mean of the two."""
    second = [(p, l, n, s + 200 * MS, d) for p, l, n, s, d in pump()
              if n.startswith("revet.")]
    sp = span_self_times(pump() + second)
    assert sp["revet.launch.readback"]["self_s"] == pytest.approx(0.025)
    assert sp["revet.pump"]["self_s"] == pytest.approx(0.002)


def test_idle_gaps_named_by_innermost_program_span():
    gaps = idle_gaps(pump())
    # device busy 12-14 and 32-58: gaps 58-110 (middle 84: readback),
    # 14-32 (middle 23: upload), 0-12 (middle 6: fuse)
    assert [n for n, _ in gaps] == ["revet.launch.readback",
                                    "revet.launch.upload",
                                    "revet.batch.fuse"]
    assert [round(s * 1e3, 6) for _, s in gaps] == [52, 18, 12]


def test_idle_gap_outside_every_span_is_none():
    ev = pump() + [(HOST, "python", TRACED_SPAN, 0, 130 * MS)]
    ev = [e for e in ev if not (e[2] == TRACED_SPAN and e[4] == 110 * MS)]
    ev.append((DEV, "XLA Ops", "copy.9", 111 * MS, 1 * MS))
    names = [n for n, _ in idle_gaps(ev)]
    assert "none" in names          # 112-130: after bench.collect ends


def test_loop_contexts_by_scope():
    lc = loop_contexts(pump(), scope_of)
    # self times: cond.3 10-6=4, fusion.4 6, fusion.5 4, cond.6 8, and the
    # while's own 26-10-4-8=4; the copy at 12-14 is outside the loop
    assert lc == {"revet.ctx.probe.3": pytest.approx(0.010),
                  "revet.ctx.emit.5": pytest.approx(0.008),
                  "revet.ready": pytest.approx(0.004),
                  OTHER: pytest.approx(0.004)}
    assert math.isclose(sum(lc.values()), 0.026)
    assert program_share(lc) == pytest.approx(22 / 26)


def test_tick_us():
    assert tick_us([0.026], [52]) == pytest.approx(500.0)
    assert tick_us([0.4, 0.6], [800, 1000]) == pytest.approx(550.0)


def test_op_scope_and_hlo_scopes():
    assert op_scope("jit(run)/while/body/revet.loop/revet.ctx.if.then.7/"
                    "cond/branch_1_fun/add") == "revet.ctx.if.then.7"
    assert op_scope("jit(run)/while/body/revet.loop/revet.ready/gt") == \
        "revet.ready"
    assert op_scope("jit(run)/while/body/revet.loop/add") is None
    hlo = """HloModule jit_run
  %cond.12 = (s32[]) conditional(pred[] %p), metadata={op_name="jit(run)/while/body/revet.loop/revet.ctx.foreach.4/cond" source_file="x.py"}
  ROOT fusion.3 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop, metadata={op_name="jit(run)/while/body/revet.loop/revet.ready/and"}
  %add.1 = s32[] add(s32[] %x, s32[] %y), metadata={op_name="jit(run)/while/body/revet.loop/add"}
"""
    assert hlo_scopes(hlo) == {"cond.12": "revet.ctx.foreach.4",
                               "fusion.3": "revet.ready"}
    assert op_key("%cond.12 = (s32[]) conditional(...)") == "cond.12"


def test_trace_without_program_spans_reads_nothing():
    """A trace of a program without spans or scopes (the parent commit's):
    no span times, no tick time, and the loop's time all ``other``."""
    ev = [e for e in pump() if not e[2].startswith("revet.")]
    assert span_self_times(ev) == {}
    lc = loop_contexts(ev, lambda name: None)
    assert list(lc) == [OTHER] and program_share(lc) == 0.0
    assert tick_us([0.026], []) is None
    assert [n for n, _ in idle_gaps(ev)][:1] == ["bench.pump"]
    no_loop = [e for e in ev if not e[2].startswith("jit_run")]
    assert loop_contexts(no_loop, scope_of) == {}
    assert program_share({}) is None


def test_script_reads_a_captured_profile(tmp_path, capsys):
    """The script reads a profile the JAX profiler wrote: two program spans
    nested in a pump, on the host (XLA:CPU has no device planes)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("revet.pump", launch=0, size=1, served=1):
            with TraceAnnotation("revet.launch.upload"):
                time.sleep(0.02)
            with TraceAnnotation("revet.launch.loop"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    assert spans.main([str(tmp_path), "--ticks", "4"]) == 0
    out = json.loads(capsys.readouterr().out)
    sp = out["spans"]
    assert set(sp) == {"revet.pump", "revet.launch.upload",
                       "revet.launch.loop"}
    assert sp["revet.launch.upload"]["self_s"] >= 0.02
    assert sp["revet.pump"]["self_s"] < sp["revet.launch.loop"]["self_s"]
    assert out["loop_s"] == [] and out["tick_us"] is None
    assert out["loop_contexts"] is None and out["program_share"] is None
