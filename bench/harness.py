"""Runs one cell of ``BENCHMARK.json`` once: data, compile, warm-up, the
measured window, the check against the plain reference, the metrics.

Everything that belongs to one configuration, traffic mix or metric is a
file found by name (see ``bench/__init__.py``); this module holds no
knowledge of any of them.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
ENGINE_MAX_WAVE = 8


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file)."""


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"no file {path}")
    return json.loads(path.read_text())


class Cell:
    """A cell's entries and files, found from its name."""

    def __init__(self, name: str):
        spec = read_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
        self.name = name
        self.entry = cells[name]
        self.config = next(c for c in spec["configs"]
                           if c["name"] == self.entry["config"])
        self.cfg = read_json(ROOT / self.config["file"])
        self.config_module = ROOT / self.config["file"].replace(
            ".json", ".py")
        traffic = self.entry["traffic"]
        self.mix = read_json(BENCH / "traffic" / f"{traffic}.json")
        own = BENCH / "cells" / f"{name}.json"
        if own.is_file():
            self.mix.update(read_json(own))
        self.end_to_end = spec["end_to_end"]
        self.per_layer = spec["per_layer"]

    def metrics(self, trace: bool) -> list[dict]:
        return self.per_layer if trace else self.end_to_end

    def load(self, seed: int, overrides: Optional[dict] = None):
        cfg = dict(self.cfg, **(overrides or {}))
        mod = load_module(self.config_module, f"bench_config_{self.cfg['name']}")
        return mod.load(cfg, seed)


class CompileEvents:
    """JAX's own compile events, summed between snapshots: seconds of
    tracing, of lowering to MLIR and of XLA compilation or persistent-cache
    read, and the persistent cache's hits and misses.  They split set-up,
    and show that a run's second process finds its programs in the cache
    and that nothing compiles inside the window."""

    DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "xla_compile_s",
    }
    COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax.monitoring
        self._sums: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_kw) -> None:
        key = self.DURATIONS.get(event)
        if key:
            self._sums[key] = self._sums.get(key, 0.0) + duration_secs

    def _event(self, event: str, **_kw) -> None:
        key = self.COUNTS.get(event)
        if key:
            self._sums[key] = self._sums.get(key, 0) + 1

    def take(self) -> dict:
        """The sums since the last call, every key present."""
        out = {k: self._sums.get(k, 0.0) for k in self.DURATIONS.values()}
        out.update({k: self._sums.get(k, 0) for k in self.COUNTS.values()})
        self._sums = {}
        return out

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def read_metric(name: str, rec: dict) -> Optional[float]:
    """The metric's own reader, ``metrics/<name>.py``: ``read(rec)``
    returns a number, or ``None`` where the run holds nothing to read."""
    mod = load_module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}")
    v = mod.read(rec)
    return None if v is None else float(v)


def check(window, wl) -> tuple[dict, int]:
    """Compare every request of the window with the plain reference.
    Returns the numbers compared, each with its limit, and the number of
    requests that failed."""
    mismatched_rows = 0
    off_path = 0
    unanswered = 0
    failed = 0
    for r in window.requests:
        bad = False
        if r.status != "ok" or r.answer is None:
            unanswered += 1
            bad = True
        else:
            if r.execution != "resident":
                off_path += 1
                bad = True
            miss = int(np.count_nonzero(
                np.asarray(r.answer) != wl.reference(r.index)))
            mismatched_rows += miss
            bad |= miss > 0
        r.correct = not bad
        failed += bad
    checks = {
        "mismatched_rows": {"value": mismatched_rows, "limit": 0},
        "off_path_requests": {"value": off_path, "limit": 0},
        "unanswered_requests": {"value": unanswered, "limit": 0},
    }
    return checks, failed


def engine_checks(stats: dict) -> dict:
    """Launches the engine took off the resident path."""
    n = int(stats["resident_fallbacks"]) + \
        int(stats["windowed_instead_of_resident"]) + int(stats["degraded"])
    return {"fallback_launches": {"value": n, "limit": 0}}


def record(window, setup: dict, image_bytes_per_slot: int,
           memory_peak_bytes: Optional[int], trace: Optional[dict]) -> dict:
    """The run as plain data: what every metric reader reads."""
    t0 = window.t0
    return {
        "setup": setup,
        "seconds": window.seconds,
        "rows_per_request": setup["rows_per_request"],
        "requests": [{
            "due": r.due - t0,
            "submit": None if r.submit_t is None else r.submit_t - t0,
            "admit": None if r.admit_t is None else r.admit_t - t0,
            "done": None if r.done_t is None else r.done_t - t0,
            "correct": bool(r.correct),
        } for r in window.requests],
        "launches": [{"size": l.size, "served": l.served,
                      "wall_s": l.wall_s, "start": l.start,
                      "pump_s": l.pump_s} for l in window.launches],
        "image_bytes_per_slot": image_bytes_per_slot,
        "memory_peak_bytes": memory_peak_bytes,
        "trace": trace,
    }


def window_summary(rec: dict) -> dict:
    """Each launch's start, pump and run_batch walls, and the latencies
    (ms, in the order sent): what a spread of the latency metrics is made
    of."""
    return {
        "launches": [[round(l["start"], 4), round(l["pump_s"], 4),
                      round(l["wall_s"], 4), l["served"]]
                     for l in rec["launches"]],
        "latency_ms": [None if r["done"] is None else
                       round(1e3 * (r["done"] - r["due"]), 1)
                       for r in rec["requests"]],
    }


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             overrides: Optional[dict] = None,
             mix_overrides: Optional[dict] = None, fault=None,
             dump_trace: Optional[str] = None) -> dict:
    """One run of cell ``name``; returns the result line's object.
    ``overrides`` and ``mix_overrides`` (sizes for a small rehearsal) and
    ``fault(engine)`` (a broken timed path) serve the tests.
    ``dump_trace`` names a file to keep the traced events in."""
    cell = Cell(name)
    cell.mix.update(mix_overrides or {})
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no devices: {e}") from e
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) < int(cell.entry["chips"]):
        raise BenchError(f"cell {name} needs {cell.entry['chips']} chips, "
                         f"JAX found {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    import revet
    from repro.compile_cache import use_compile_cache
    from repro.serve.async_engine import AsyncRequest, AsyncServeEngine

    from bench.drive import Traffic
    from bench.trace import Tracer, dump, reduce_trace
    from bench.workload import WARM_BASE

    cache_dir = use_compile_cache()
    # every program the run compiles goes to the cache, however quick
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileEvents()
    setup = {"jax_init_s": time.perf_counter() - t_start,
             "compile_cache_dir": cache_dir}

    t = time.perf_counter()
    wl = cell.load(seed, overrides)
    rows = wl.rows_per_request
    setup["data_s"] = time.perf_counter() - t
    setup["rows_per_request"] = rows

    t = time.perf_counter()
    arrays, scalars = wl.request(WARM_BASE, rows)
    compiled = revet.compile(
        wl.program, **arrays, **scalars, **wl.statics,
        options=revet.CompileOptions(backend="jax", place=True,
                                     execution="resident"))
    setup["compile_s"] = time.perf_counter() - t
    setup["compiles_in_set_up"] = compiles.take()

    def engine():
        return AsyncServeEngine(
            compiled, max_wave=ENGINE_MAX_WAVE,
            queue_cap=int(cell.mix["queue_cap"]), execution="resident",
            clock=time.perf_counter)

    # warm exactly the buckets this traffic launches, on the same compiled
    # program, then serve the window from a fresh engine
    # (a configuration may send its warm requests with fewer rows, or with
    # only some of their arrays: the rest stay zero, the shapes are those
    # of the window)
    t = time.perf_counter()
    warm_rows = int(cell.cfg.get("warm_rows", rows))
    warm_arrays = cell.cfg.get("warm_arrays")
    buckets = [int(b) for b in cell.mix["warm_buckets"]]
    setup["warm_launches"] = []
    for k, b in enumerate(buckets):
        eng = engine()
        for j in range(b):
            a, s = wl.request(WARM_BASE + 16 * k + j, warm_rows)
            if warm_arrays is not None:
                a = {n: a[n] for n in warm_arrays}
            eng.submit(AsyncRequest(params=s, dram_init=a))
        tb = time.perf_counter()
        compiles.take()
        done = eng.run_until_idle()
        setup["warm_launches"].append(
            dict(bucket=b, seconds=time.perf_counter() - tb,
                 launch_s=eng.launch_walls[-1][2], **compiles.take()))
        if eng.launch_counts != {b: 1} or \
                not all(r.ok and r.report.execution == "resident"
                        for r in done):
            raise BenchError(f"warm-up of bucket {b} did not serve one "
                             f"resident launch: {eng.stats()}")
        del done, eng           # each response holds its request's image
    setup["warm_s"] = time.perf_counter() - t
    setup["buckets_warmed"] = buckets

    tracer = None
    if trace:
        tracer = Tracer(int(cell.mix["trace_from_launch"]),
                        int(cell.mix["trace_launches"]))
    eng = engine()
    if fault is not None:
        fault(eng)
    traffic = Traffic(eng, lambda i: wl.request(i, rows), rows, wl.output,
                    cell.mix, tracer=tracer)
    t_window = time.perf_counter()
    setup["setup_s"] = t_window - t_start
    setup["compiles_before_window"] = compiles.take()
    window = traffic.run(seconds, np.random.default_rng([seed, 2]))
    if tracer is not None:
        tracer.close()
    setup["compiles_in_window"] = compiles.take()
    compiles.close()
    stats = eng.stats()
    setup["window_and_drain_s"] = time.perf_counter() - t_window
    late = [r.submit_t - r.due for r in window.requests
            if r.submit_t is not None]
    setup["generator_late_mean_ms"] = 1e3 * float(np.mean(late)) \
        if late else 0.0
    setup["generator_late_max_ms"] = 1e3 * float(np.max(late)) \
        if late else 0.0
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    image_bytes_per_slot = 4 * sum(d.size for d in
                                   compiled.result.dfg.dram.values())
    del traffic, eng, compiled

    reduced = None
    if tracer is not None:
        t = time.perf_counter()
        events = tracer.read() if window.traced else []
        reduced = reduce_trace(events) if events else {}
        if dump_trace:
            dump(events, dump_trace)
        setup["trace_events"] = len(events)
        setup["trace_read_s"] = time.perf_counter() - t
        setup.update(tracer.timings)
        setup["traced_launches"] = window.traced

    t = time.perf_counter()
    checks, failed = check(window, wl)
    checks.update(engine_checks(stats))
    setup["check_s"] = time.perf_counter() - t
    rec = record(window, setup, image_bytes_per_slot, peak, reduced)
    log("setup " + json.dumps(setup, sort_keys=True))
    log("window " + json.dumps(window_summary(rec)))
    log("engine " + json.dumps(stats, sort_keys=True))

    metrics = {}
    for m in cell.metrics(trace):
        v = read_metric(m["name"], rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in checks.values()),
           "attempted": len(window.requests), "failed": failed,
           "metrics": metrics, "device": device}
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out
