#!/usr/bin/env python3
"""Chip smoke test: the resident serving path, end to end, on one TPU.

    python chip_smoke.py

Builds the ``hash_table`` lookup service at 2**20 slots (262,144 keys; a
16 MiB table per request on the device), compiles it with
``revet.compile`` (pass pipeline + ``place``, resident execution), warms an
``AsyncServeEngine``, serves 16 requests from 2 tenants (each with its own
seeded query set against the same table) and checks every answer against
the plain Python ``dict`` reference.

Exits non-zero, without the result line, when JAX finds no TPU, when any
request fails or is shed, when the engine degraded or served any request
windowed, or when any answer differs from the reference.  The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_SLOTS = 1 << 20
N_LOOKUPS = 4096
N_REQUESTS = 16
TENANTS = ("tenant-a", "tenant-b")
MAX_WAVE = 8


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def serve(n_slots: int = N_SLOTS, n_lookups: int = N_LOOKUPS,
          n_requests: int = N_REQUESTS, max_wave: int = MAX_WAVE) -> None:
    """Build, compile, warm and serve; raises :class:`SmokeFailure` on any
    check that does not hold."""
    import numpy as np

    import revet
    from repro.apps import hash_table
    from repro.serve.async_engine import AsyncRequest, AsyncServeEngine

    t0 = time.perf_counter()
    app = hash_table.build(n_lookups=n_lookups, n_slots=n_slots, seed=0)
    tk = app.dram_init["table_k"][:n_slots]
    tv = app.dram_init["table_v"][:n_slots]
    kv = {int(k): int(v) for k, v in zip(tk[tk != 0], tv[tk != 0])}
    keys = np.fromiter(kv, np.int64, len(kv))
    requests = []
    for i in range(n_requests):
        rng = np.random.default_rng(1 + i)
        q = hash_table.make_lookups(rng, keys, n_lookups)
        requests.append((q, hash_table.reference_lookup(kv, q)))
    log(f"build_s={time.perf_counter() - t0:.3f} slots={n_slots} "
        f"keys={len(kv)} lookups_per_request={n_lookups} "
        f"requests={n_requests}")

    t0 = time.perf_counter()
    compiled = revet.compile(
        app.fn, **app.dram_init, **app.params, **app.statics,
        options=revet.CompileOptions(backend="jax", place=True,
                                     execution="resident"))
    log(f"compile_s={time.perf_counter() - t0:.3f} "
        f"backend={compiled.backend.name} "
        f"replicas={compiled.default_replicas()}")

    engine = AsyncServeEngine(compiled, max_wave=max_wave)
    t0 = time.perf_counter()
    warmed = engine.warmup(app.dram_init, app.params)
    log(f"warmup_s={time.perf_counter() - t0:.3f} warmed={warmed}")

    expected = {}
    for i, (q, want) in enumerate(requests):
        req = engine.submit(AsyncRequest(
            params=dict(app.params), tenant=TENANTS[i % len(TENANTS)],
            dram_init={"table_k": app.dram_init["table_k"],
                       "table_v": app.dram_init["table_v"], "queries": q}))
        expected[req.id] = want
    t0 = time.perf_counter()
    done = engine.run_until_idle()
    log(f"serve_s={time.perf_counter() - t0:.3f}")
    for mode, size, wall in engine.launch_walls:
        log(f"launch mode={mode} size={size} wall_s={wall:.6f}")

    stats = engine.stats()
    log("stats=" + json.dumps(stats, sort_keys=True))
    if len(done) != n_requests:
        raise SmokeFailure(f"{len(done)}/{n_requests} responses")
    bad = [r.request.id for r in done if r.status != "ok"]
    if bad:
        raise SmokeFailure(f"requests {bad} not ok: "
                           f"{[r.error for r in done if not r.ok]}")
    if stats["degraded"] or stats["resident_fallbacks"] or \
            stats["windowed_instead_of_resident"]:
        raise SmokeFailure("engine left the resident path: "
                           f"degraded={stats['degraded']} "
                           f"resident_fallbacks={stats['resident_fallbacks']} "
                           "windowed_instead_of_resident="
                           f"{stats['windowed_instead_of_resident']}")
    not_resident = [r.request.id for r in done
                    if r.report.execution != "resident"]
    if not_resident:
        raise SmokeFailure(f"requests {not_resident} not served resident")
    wrong = [r.request.id for r in done
             if not np.array_equal(r.dram["results"][:n_lookups],
                                   expected[r.request.id])]
    log(f"matched={n_requests - len(wrong)}/{n_requests}")
    if wrong:
        raise SmokeFailure(f"requests {wrong} differ from the reference")


def main() -> int:
    import jax

    try:
        dev = jax.devices()[0]
        count = len(jax.devices())
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={count} jax={jax.__version__}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {ROOT / 'src'}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    log(f"compile_cache_dir={use_compile_cache()}")

    try:
        serve()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    mem = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={mem.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
