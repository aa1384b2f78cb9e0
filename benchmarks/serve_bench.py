"""Request-batched serving benchmark.

``serve_batching`` drives ``serve.dataflow.DataflowEngine`` over every
Table III app on both executor backends, comparing sequential serving
(``step()`` per request) against fused batched serving
(``step_batch(max_batch=B)``) at batch sizes 1/4/8/16, verifying the batched
responses' DRAM bit-identical to the sequential ones, and writes
``BENCH_serve.json``. Acceptance: batch=8 must be >= 2x sequential
throughput on at least two apps on the numpy backend, and **no** cell may
fall below 0.9x sequential on either backend.

Cells are timed best-of-``REPEATS`` after a warm pass: a serving deployment
warms each launch-size bucket once at startup (``DataflowEngine.warmup``;
the engine's bucket padding keeps the set of jit launch shapes finite on
jax), so steady-state throughput — not first-call jit compilation — is the
thing to measure.  The historical single-cold-pass protocol is what made
hash_table/jax look like 0.16x at batch=4: the cell was timing XLA
recompiles for window widths first seen mid-run, not serving.
"""
from __future__ import annotations

import json
import time

import numpy as np

import repro.api as revet
from repro.apps import ALL_APPS
from repro.serve.dataflow import DataflowEngine, DataflowRequest

BENCH_JSON = "BENCH_serve.json"
BATCH_SIZES = (1, 4, 8, 16)
REPEATS = 2
ACCEPT_BATCH = 8     # the acceptance cell:
ACCEPT_SPEEDUP = 2.0  # batch=8 >= 2x sequential ...
ACCEPT_MIN_APPS = 2   # ... on >= this many apps (numpy backend)
MIN_SPEEDUP = 0.9    # no batch point below this, either backend


def _submit(engine: DataflowEngine, app, n: int) -> None:
    for rid in range(n):
        engine.submit(DataflowRequest(rid, dict(app.params), app.dram_init))


def _bench_cell(compiled, app, batch: int) -> dict:
    def seq_pass():
        eng = DataflowEngine(compiled, bucket_sizes=None)
        _submit(eng, app, batch)
        t0 = time.perf_counter()
        while eng.queue:
            eng.step()
        return time.perf_counter() - t0, eng.done

    def bat_pass():
        eng = DataflowEngine(compiled)
        _submit(eng, app, batch)
        t0 = time.perf_counter()
        responses = eng.step_batch(max_batch=batch)
        return time.perf_counter() - t0, responses

    seq_pass(), bat_pass()                    # warm both paths
    t_seq, seq_done = min((seq_pass() for _ in range(REPEATS)),
                          key=lambda x: x[0])
    t_bat, responses = min((bat_pass() for _ in range(REPEATS)),
                           key=lambda x: x[0])

    match = len(responses) == batch and all(
        np.array_equal(s.dram[k], b.dram[k])
        for s, b in zip(seq_done, responses) for k in s.dram)
    return {
        "seq_s": round(t_seq, 4),
        "batch_s": round(t_bat, 4),
        "speedup": round(t_seq / max(t_bat, 1e-9), 2),
        "req_per_s_seq": round(batch / max(t_seq, 1e-9), 1),
        "req_per_s_batch": round(batch / max(t_bat, 1e-9), 1),
        "match": bool(match),
    }


def serve_batching(rows: list[dict], out_path: str = BENCH_JSON) -> None:
    """Batched-vs-sequential serving throughput -> rows + BENCH_serve.json."""
    from repro.core.backend import JaxBackend
    jax_be = JaxBackend()            # auto route: XLA (jnp) everywhere
    apps_payload: dict[str, dict] = {}
    mismatched: list[str] = []
    for name in sorted(ALL_APPS):
        app = ALL_APPS[name]()
        per_backend: dict[str, dict] = {}
        for label, be in (("numpy", "numpy"), ("jax", jax_be)):
            compiled = revet.compile(app.fn, **app.dram_init, **app.params,
                                     **app.statics, backend=be)
            # deployment-style warmup: one launch per configured bucket size
            # (bounded by the engine's bucket padding), so the timed cells
            # measure steady-state serving, not first-call jit compiles
            warm = DataflowEngine(compiled)
            _submit(warm, app, 1)
            warm.warmup(buckets=tuple(
                b for b in (warm.bucket_sizes or BATCH_SIZES)
                if b <= max(BATCH_SIZES)))
            warm.step()
            cells = {str(b): _bench_cell(compiled, app, b)
                     for b in BATCH_SIZES}
            per_backend[label] = cells
            if not all(c["match"] for c in cells.values()):
                mismatched.append(f"{name}/{label}")
        apps_payload[name] = per_backend
        cell8 = per_backend["numpy"][str(ACCEPT_BATCH)]
        rows.append({"bench": "serve", "name": name,
                     "numpy_batch8_speedup": cell8["speedup"],
                     "numpy_req_per_s_batch8": cell8["req_per_s_batch"],
                     "jax_batch8_speedup":
                         per_backend["jax"][str(ACCEPT_BATCH)]["speedup"]})
    over = sorted(n for n, pb in apps_payload.items()
                  if pb["numpy"][str(ACCEPT_BATCH)]["speedup"]
                  >= ACCEPT_SPEEDUP)
    slow = sorted(f"{n}/{label}/batch={b}"
                  for n, pb in apps_payload.items()
                  for label, cells in pb.items()
                  for b, c in cells.items()
                  if c["speedup"] < MIN_SPEEDUP)
    payload = {
        "meta": {
            "jax_backend": jax_be.name,
            "route": jax_be.route,
            "interpret": jax_be.interpret,
            "batch_sizes": list(BATCH_SIZES),
            "acceptance": f"batch={ACCEPT_BATCH} >= {ACCEPT_SPEEDUP}x "
                          f"sequential on >= {ACCEPT_MIN_APPS} apps "
                          f"(numpy); no cell < {MIN_SPEEDUP}x",
            "apps_over_2x_numpy_batch8": over,
            "cells_below_floor": slow,
            "note": "validation-size app instances; best-of-"
                    f"{REPEATS} warm passes per cell after bucket warmup "
                    "(steady-state serving throughput)",
        },
        "apps": apps_payload,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    assert not mismatched, \
        f"batched DRAM diverged from sequential on: {mismatched}"
    assert len(over) >= ACCEPT_MIN_APPS, \
        (f"acceptance: only {over} reached {ACCEPT_SPEEDUP}x at "
         f"batch={ACCEPT_BATCH} on numpy (need {ACCEPT_MIN_APPS})")
    assert not slow, \
        f"serve regression: cells below {MIN_SPEEDUP}x sequential: {slow}"
