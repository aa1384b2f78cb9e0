"""Windowed-vs-resident executor benchmark (``BENCH_vectorvm.json``).

For every Table III app this times three execution routes at benchmark
scale (``benchmarks.common.BENCH_SIZES``):

* ``numpy``    — the windowed oracle: host superstep loop, numpy kernels;
* ``jax``      — the windowed jax route: one ``vm_*`` dispatch per window
  (~``ticks`` host round-trips per run);
* ``resident`` — the whole program as **one** fused ``lax.while_loop``
  launch (``core/device_vm.py``, DESIGN.md §9).

Every resident cell asserts DRAM bit-identity plus aggregate
``LANE_STATS`` against the numpy oracle before it is timed; ``launches``
must be 1 for every non-fallback app.  Timings are best-of-``REPEATS``
warm passes (jit caches steady — this tracks serving cost, not
cold-start; the one-off resident compile is reported separately as
``resident_compile_s``).

Acceptance (hard unless ``REVET_VECTORVM_SOFT_ACCEPT=1``): resident
beats windowed jax on every app, and ``resident_over_numpy`` <= 1.0 on
at least 6/9 apps with none above 1.5 — the PR 6 tentpole criterion that
one launch ends the jax backend's dispatch-bound losses to numpy.

CI regression gate (``REVET_VECTORVM_GATE=1``): before overwriting the
JSON, compare each app's fresh ``resident_over_numpy`` against the
checked-in value and fail if it regressed by more than
``REVET_VECTORVM_TOL`` (default 1.5x — shared-runner timing headroom;
bit-identity and the launch count are asserted exactly regardless).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core.backend import JaxBackend
from repro.core.vector_vm import LANE_STATS

from .common import BENCH_SIZES, build_bench_app

BENCH_JSON = "BENCH_vectorvm.json"
REPEATS = int(os.environ.get("REVET_VECTORVM_REPEATS", "3"))
ACCEPT_GOOD_RATIO = 1.0      # resident_over_numpy target ...
ACCEPT_MIN_APPS = 6          # ... on at least this many apps ...
ACCEPT_MAX_RATIO = 1.5       # ... and a hard per-app ceiling


def _best(fn, n: int = REPEATS) -> float:
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _lane_stats(stats) -> dict:
    return {k: int(stats.get(k, 0)) for k in LANE_STATS}


def vectorvm_backends(rows: list[dict], out_path: str = BENCH_JSON) -> None:
    """numpy / windowed-jax / resident timings -> rows + BENCH_vectorvm.json."""
    jax_be = JaxBackend()            # auto route: XLA (jnp) everywhere
    baseline = {}
    if os.environ.get("REVET_VECTORVM_GATE") == "1" and \
            os.path.exists(out_path):
        with open(out_path) as f:
            baseline = json.load(f).get("apps", {})

    apps: dict[str, dict] = {}
    mismatched: list[str] = []
    for name in sorted(BENCH_SIZES):
        app = build_bench_app(name)
        compiled = app.fn.lower(**app.dram_init, **app.params,
                                **app.statics).compile(jax_be)
        run = lambda **kw: compiled.execute(dict(app.dram_init), app.params,
                                            **kw)
        ref = run(backend="numpy")              # warm + the oracle image
        t_np = _best(lambda: run(backend="numpy"))
        run()                                   # warm the per-window jits
        t_jx = _best(lambda: run())
        t0 = time.perf_counter()
        res = run(execution="resident")         # warm + compile the loop
        compile_s = time.perf_counter() - t0
        fallback = res.report.execution != "resident"
        t_res = _best(lambda: run(execution="resident"))
        ok = all(np.array_equal(res.dram[k], ref.dram[k])
                 for k in ref.dram) and \
            _lane_stats(res.report.stats) == _lane_stats(ref.vm.stats)
        if not ok:
            mismatched.append(name)
        cell = {
            "numpy_s": round(t_np, 4),
            "jax_s": round(t_jx, 4),
            "jax_over_numpy": round(t_jx / max(t_np, 1e-9), 2),
            "ticks": int(ref.vm.stats["ticks"]),
            "match": bool(ok),
            "resident": {
                "resident_s": round(t_res, 4),
                "resident_compile_s": round(compile_s, 1),
                "launches": int(getattr(res.vm, "launches", 0)),
                "resident_over_numpy":
                    round(t_res / max(t_np, 1e-9), 2),
                "resident_over_windowed_jax":
                    round(t_res / max(t_jx, 1e-9), 2),
                "fallback": getattr(res.vm, "resident_fallback", None)
                    if fallback else None,
            },
        }
        apps[name] = cell
        rows.append({"bench": "vectorvm", "name": name,
                     **{k: v for k, v in cell.items() if k != "resident"},
                     **{k: v for k, v in cell["resident"].items()}})

    good = sorted(n for n, c in apps.items()
                  if c["resident"]["resident_over_numpy"]
                  <= ACCEPT_GOOD_RATIO)
    payload = {
        "meta": {
            "jax_backend": jax_be.name,
            "route": jax_be.route,
            "interpret": jax_be.interpret,
            "sizes": {n: dict(s) for n, s in sorted(BENCH_SIZES.items())},
            "repeats": REPEATS,
            "acceptance": f"resident beats windowed jax on every app; "
                          f"resident_over_numpy <= {ACCEPT_GOOD_RATIO} on "
                          f">= {ACCEPT_MIN_APPS}/9 apps, none above "
                          f"{ACCEPT_MAX_RATIO}",
            "apps_at_or_below_numpy": good,
            "note": "benchmark-scale instances (meta.sizes; PR 6 moved the "
                    "suite off the validation sizes so the resident loop "
                    "is measured at serving depth); best-of-repeats warm "
                    "passes, resident compile reported separately; every "
                    "resident cell asserted bit-identical (DRAM + lane "
                    "stats) to the numpy oracle",
        },
        "apps": apps,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    assert not mismatched, \
        f"resident outputs/stats diverged from the oracle on: {mismatched}"
    fellback = sorted(n for n, c in apps.items()
                      if c["resident"]["fallback"] or
                      c["resident"]["launches"] != 1)
    assert not fellback, \
        f"apps fell back to the windowed path (or launches != 1): {fellback}"

    soft = os.environ.get("REVET_VECTORVM_SOFT_ACCEPT") == "1"
    if not soft:
        slower = sorted(
            n for n, c in apps.items()
            if c["resident"]["resident_over_windowed_jax"] >= 1.0)
        assert not slower, \
            f"resident lost to the windowed jax route on: {slower}"
        over = sorted(n for n, c in apps.items()
                      if c["resident"]["resident_over_numpy"]
                      > ACCEPT_MAX_RATIO)
        assert len(good) >= ACCEPT_MIN_APPS and not over, \
            (f"acceptance: resident_over_numpy <= {ACCEPT_GOOD_RATIO} on "
             f"{good} (need {ACCEPT_MIN_APPS}); above "
             f"{ACCEPT_MAX_RATIO}: {over}")

    if baseline:
        tol = float(os.environ.get("REVET_VECTORVM_TOL", "1.5"))
        regressed = []
        for name, cell in apps.items():
            old = baseline.get(name, {}).get("resident", {}) \
                .get("resident_over_numpy")
            if old is None:
                continue
            new = cell["resident"]["resident_over_numpy"]
            if new > old * tol:
                regressed.append(f"{name}: {new} > {old} * {tol}")
        assert not regressed, \
            "resident perf regressed vs checked-in baseline: " \
            + "; ".join(regressed)
