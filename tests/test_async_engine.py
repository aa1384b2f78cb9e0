"""Async continuous-batching serving (serve/async_engine.py): admission
fairness, priority shedding, retry/degrade robustness, SLO accounting, and
the open WaveSession mid-launch admission path (api.py) — every completed
response validated bit-identical against a solo run, since the serving
layer's core contract is that scheduling never changes results."""
import numpy as np
import pytest

from repro.apps import ALL_APPS
from repro.core.device_vm import RESIDENT_BUCKETS, bucket_launch_size
from repro.distributed.fault_tolerance import SimulatedFault
from repro.serve.async_engine import AsyncRequest, AsyncServeEngine
from repro.serve.dataflow import DataflowEngine, DataflowRequest


def _compiled(app, backend="numpy"):
    return app.fn.lower(**app.dram_init, **app.params,
                        **app.statics).compile(backend)


def _req(app, **kw):
    return AsyncRequest(params=dict(app.params),
                        dram_init=dict(app.dram_init), **kw)


def _assert_matches_solo(resp, compiled, app):
    solo = compiled.execute(dict(app.dram_init), resp.request.params,
                            require_inputs=False)
    for arr in solo.dram:
        np.testing.assert_array_equal(
            resp.dram[arr], solo.dram[arr],
            err_msg=f"req {resp.request.id}: '{arr}'")


class FakeClock:
    """Injectable monotonic time — tests control latency deterministically."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# bucketed launch shapes (core/device_vm.py)
# ---------------------------------------------------------------------------

def test_bucket_launch_size():
    assert bucket_launch_size(1) == 1
    assert bucket_launch_size(3) == 4
    assert bucket_launch_size(8) == 8
    assert bucket_launch_size(9, "auto") == 16
    assert bucket_launch_size(max(RESIDENT_BUCKETS) + 1) == \
        max(RESIDENT_BUCKETS) + 1            # beyond the ladder: exact size
    assert bucket_launch_size(3, (5,)) == 5
    assert bucket_launch_size(7, (5,)) == 7


# ---------------------------------------------------------------------------
# admission queue: bounded shedding + tenant fairness
# ---------------------------------------------------------------------------

def test_shed_lowest_priority_first():
    """With the queue full, the strictly lowest-priority request in the
    system sheds — the incoming one only when it *is* the minimum."""
    app = ALL_APPS["ip2int"]()
    eng = AsyncServeEngine(_compiled(app), max_wave=2, queue_cap=3)
    reqs = [eng.submit(_req(app, priority=p)) for p in (5, 1, 3, 0, 9)]
    # prio 0 arrives on a full queue and is itself the minimum -> shed;
    # prio 9 arrives on a full queue and evicts the queued prio-1 request
    assert [r.status for r in reqs] == \
        ["queued", "shed", "queued", "shed", "queued"]
    shed = [r for r in eng.done if r.status == "shed"]
    assert sorted(r.request.priority for r in shed) == [0, 1]
    assert all(r.met_slo is False and r.dram is None for r in shed)
    served = eng.run_until_idle()
    assert sorted(r.request.priority for r in served) == [3, 5, 9]
    for r in served:
        _assert_matches_solo(r, eng.compiled, app)
    st = eng.stats()
    assert st["submitted"] == 5 and st["served"] == 3 and st["shed"] == 2
    assert st["submitted"] == st["served"] + st["shed"] + st["failed"]


def test_tenant_fairness_10_to_1_skew():
    """Round-robin across tenants: a tenant submitting 10x the traffic must
    not starve the small tenant — both of the small tenant's requests land
    in the first wave despite 20 'big' requests ahead of them."""
    app = ALL_APPS["ip2int"]()
    eng = AsyncServeEngine(_compiled(app), max_wave=4, queue_cap=64)
    for _ in range(20):
        eng.submit(_req(app, tenant="big"))
    small = [eng.submit(_req(app, tenant="small")) for _ in range(2)]
    done = eng.run_until_idle()
    assert len(done) == 22
    first_wave = {r.request.id for r in done[:4]}
    assert {s.id for s in small} <= first_wave
    st = eng.stats()
    assert st["tenant_served"] == {"big": 20, "small": 2}
    for r in done:
        _assert_matches_solo(r, eng.compiled, app)


def test_priority_order_within_tenant():
    app = ALL_APPS["ip2int"]()
    eng = AsyncServeEngine(_compiled(app), max_wave=8, queue_cap=16)
    order = [eng.submit(_req(app, priority=p)).id for p in (0, 7, 3, 7)]
    done = eng.run_until_idle()
    # highest priority first, FIFO within a priority, all one tenant
    assert [r.request.id for r in done] == \
        [order[1], order[3], order[2], order[0]]


# ---------------------------------------------------------------------------
# robustness: retry, timeout, degraded mode
# ---------------------------------------------------------------------------

def test_retried_launch_bit_identical():
    """Chaos hook fails every first launch attempt; the verbatim replay must
    produce bit-identical results (launches are pure functions of their
    request batch)."""
    app = ALL_APPS["hash_table"]()
    compiled = _compiled(app)

    def chaos(attempt, mode, reqs):
        if attempt == 0:
            raise SimulatedFault(f"{mode} launch of {len(reqs)} lost")

    eng = AsyncServeEngine(compiled, max_wave=4, queue_cap=16,
                           max_retries=2, fault_hook=chaos)
    counts = [64, 17, 1, 40, 64, 9]
    for n in counts:
        eng.submit(AsyncRequest(params={"count": n},
                                dram_init=dict(app.dram_init)))
    done = eng.run_until_idle()
    assert [r.status for r in done] == ["ok"] * len(counts)
    for r in done:
        solo = compiled.execute(dict(app.dram_init), r.request.params)
        for arr in solo.dram:
            np.testing.assert_array_equal(r.dram[arr], solo.dram[arr])
        assert r.report.stats == solo.vm.request_stats(0)
    assert eng.supervisor.retries == 2          # one per wave (6 reqs / 4)
    assert eng.stats()["supervisor_failures"] == 2


def test_retries_exhausted_fail_the_wave():
    app = ALL_APPS["ip2int"]()

    def chaos(attempt, mode, reqs):
        raise SimulatedFault("always down")

    eng = AsyncServeEngine(_compiled(app), max_wave=4, queue_cap=8,
                           max_retries=1, fault_hook=chaos)
    for _ in range(3):
        eng.submit(_req(app))
    done = eng.run_until_idle()
    assert [r.status for r in done] == ["failed"] * 3
    assert all("SimulatedFault" in r.error for r in done)
    st = eng.stats()
    assert st["failed"] == 3 and st["served"] == 0
    assert st["submitted"] == st["served"] + st["shed"] + st["failed"]


def test_wave_timeout_requeues_then_serves():
    """A wave that overruns launch_timeout_s (virtual clock) is aborted and
    its requests replayed on a fresh wave — served, with retries stamped."""
    app = ALL_APPS["hash_table"]()
    clock = FakeClock()
    eng = AsyncServeEngine(_compiled(app), max_wave=2, queue_cap=8,
                           launch_timeout_s=5.0, max_retries=2,
                           advance_ticks=1, clock=clock)
    for _ in range(2):
        eng.submit(_req(app))
    eng.pump()                      # opens the wave at t=0, one superstep
    clock.t = 100.0                 # overrun: next pump aborts the wave
    done = eng.pump()
    assert done == [] and eng.queue_depth == 2   # requeued, not failed
    assert eng.counters["wave_timeouts"] == 1
    done = eng.run_until_idle()     # clock frozen now -> no more timeouts
    assert [r.status for r in done] == ["ok", "ok"]
    assert all(r.request.retries == 1 for r in done)
    for r in done:
        _assert_matches_solo(r, eng.compiled, app)


def test_wave_timeout_exhausts_to_failure():
    app = ALL_APPS["hash_table"]()
    clock = FakeClock()
    eng = AsyncServeEngine(_compiled(app), max_wave=2, queue_cap=8,
                           launch_timeout_s=5.0, max_retries=0,
                           advance_ticks=1, clock=clock)
    eng.submit(_req(app))
    eng.pump()
    clock.t = 100.0
    done = eng.pump()               # retries (0) exhausted -> failed
    assert [r.status for r in done] == ["failed"]
    assert "TimeoutError" in done[0].error or "timeout" in done[0].error


def test_slo_accounting_virtual_clock():
    app = ALL_APPS["ip2int"]()
    clock = FakeClock()
    eng = AsyncServeEngine(_compiled(app), max_wave=4, queue_cap=8,
                           slo_s=5.0, clock=clock)
    fast = eng.submit(_req(app))
    done = eng.run_until_idle()     # clock never moves -> latency 0
    clock.t = 50.0
    slow = eng.submit(_req(app))
    clock.t = 100.0                 # 50s in system before the wave closes
    done += eng.run_until_idle()
    by_id = {r.request.id: r for r in done}
    assert by_id[fast.id].met_slo is True
    assert by_id[slow.id].met_slo is False
    st = eng.stats()
    assert st["slo_met"] == 1 and st["slo_missed"] == 1
    # per-request SLO overrides the engine default
    clock.t = 200.0
    req = eng.submit(_req(app, slo_s=1000.0))
    clock.t = 300.0
    (r,) = eng.run_until_idle()
    assert r.request.id == req.id and r.met_slo is True


# ---------------------------------------------------------------------------
# in-flight batching: open waves admit mid-launch
# ---------------------------------------------------------------------------

def test_mid_wave_admission_counter_and_identity():
    """Requests submitted while the wave is already executing join it
    mid-launch (§III-B(d): the merge admits threads whenever a lane
    frees) — and results stay bit-identical."""
    app = ALL_APPS["hash_table"]()
    eng = AsyncServeEngine(_compiled(app), max_wave=4, queue_cap=8,
                           advance_ticks=1)
    eng.submit(AsyncRequest(params={"count": 64},
                            dram_init=dict(app.dram_init)))
    eng.pump()                      # wave open + advanced one superstep
    assert eng.in_flight == 1
    eng.submit(AsyncRequest(params={"count": 17},
                            dram_init=dict(app.dram_init)))
    eng.submit(AsyncRequest(params={"count": 40},
                            dram_init=dict(app.dram_init)))
    done = eng.run_until_idle()
    assert eng.counters["mid_wave_admissions"] == 2
    assert eng.stats()["waves"] == 1            # all three shared one wave
    assert [r.status for r in done] == ["ok"] * 3
    for r in done:
        _assert_matches_solo(r, eng.compiled, app)


def test_wave_session_mid_flight_bit_identity():
    """Direct WaveSession use: admit, run to idle, admit more mid-stream,
    finish — per-rid slices match solo runs exactly."""
    app = ALL_APPS["hash_table"]()
    compiled = _compiled(app)
    counts = [64, 17, 1, 40, 9]
    wave = compiled.open_session(capacity=len(counts))
    for n in counts[:2]:
        wave.admit(dict(app.dram_init), {"count": n})
    while not wave.advance(max_ticks=16):
        pass                        # first two requests fully drained
    for n in counts[2:]:
        wave.admit(dict(app.dram_init), {"count": n})
    bx = wave.finish()
    assert len(bx) == len(counts) and wave.closed
    for ex, n in zip(bx, counts):
        solo = compiled.execute(dict(app.dram_init), {"count": n})
        for arr in solo.dram:
            np.testing.assert_array_equal(ex.dram[arr], solo.dram[arr],
                                          err_msg=f"count={n}: '{arr}'")
        assert ex.report.stats == solo.vm.request_stats(0)


def test_wave_session_guards():
    app = ALL_APPS["ip2int"]()
    compiled = _compiled(app)
    wave = compiled.open_session(capacity=1)
    wave.admit(dict(app.dram_init), dict(app.params))
    with pytest.raises(RuntimeError, match="wave full"):
        wave.admit(dict(app.dram_init), dict(app.params))
    wave.close()
    with pytest.raises(RuntimeError, match="closed"):
        wave.admit(dict(app.dram_init), dict(app.params))
    assert len(wave.finish()) == 1
    # an empty wave finishes without running anything
    empty = compiled.open_session(capacity=2)
    assert len(empty.finish()) == 0


# ---------------------------------------------------------------------------
# DataflowEngine satellites: drain default + queue/launch stats
# ---------------------------------------------------------------------------

def test_engine_drain_default_batches():
    """drain() now defaults to fused batches of 8 (one launch for a small
    queue) instead of one launch per request."""
    app = ALL_APPS["ip2int"]()
    eng = DataflowEngine(_compiled(app))
    for rid in range(3):
        eng.submit(DataflowRequest(rid, dict(app.params),
                                   dict(app.dram_init)))
    eng.drain()
    st = eng.stats()
    assert st["launches"] == 1                  # not 3
    assert st["launches_by_bucket"] == {3: 1}
    assert st["queue_depth"] == 0 and st["queue_depth_peak"] == 3
    assert st["time_in_queue_s"] >= 0.0
    assert st["time_in_queue_mean_s"] >= 0.0
    for resp in eng.done:
        assert resp.report.queue_s is not None
        assert resp.report.queue_depth is not None


def test_engine_warmup_counter():
    app = ALL_APPS["ip2int"]()
    eng = DataflowEngine(_compiled(app))
    before = eng.stats()["warmup_launches"]
    warmed = eng.warmup(DataflowRequest(0, dict(app.params),
                                        dict(app.dram_init)),
                        buckets=(1, 2))
    assert warmed == [1, 2]
    assert eng.stats()["warmup_launches"] == before + 2
    assert not eng.done                      # warmup results are discarded


def test_async_stats_keys_complete():
    app = ALL_APPS["ip2int"]()
    eng = AsyncServeEngine(_compiled(app), max_wave=2, queue_cap=4)
    eng.submit(_req(app))
    eng.run_until_idle()
    st = eng.stats()
    for key in ("backend", "execution", "mode", "degraded", "submitted",
                "served", "shed", "failed", "waves", "wave_timeouts",
                "mid_wave_admissions", "resident_fallbacks",
                "windowed_instead_of_resident", "slo_met",
                "slo_missed", "queue_depth", "queue_depth_peak",
                "time_in_queue_s", "time_in_queue_mean_s", "launches",
                "launches_by_bucket", "warmup_launches", "warmup_s",
                "tenant_served",
                "supervisor_retries", "supervisor_failures", "stragglers"):
        assert key in st, key
    assert st["mode"] == "windowed" and st["launches_by_bucket"] == {1: 1}


# ---------------------------------------------------------------------------
# resident mode: bucketed launches + degraded fallback (jax only)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ip2int_jax():
    """One jax compile of ip2int, shared by the resident tests below so that
    each launch shape's DeviceProgram compiles once."""
    pytest.importorskip("jax")
    app = ALL_APPS["ip2int"]()
    return app, _compiled(app, "jax")


def test_resident_async_bucketed_launches(ip2int_jax):
    app, compiled = ip2int_jax
    eng = AsyncServeEngine(compiled, backend="jax", execution="resident",
                           max_wave=2, queue_cap=8)
    assert eng.mode() == "resident"
    warmed = eng.warmup(dict(app.dram_init), dict(app.params))
    assert warmed["resident"] == [1, 2]
    assert all(s > 0 for s in eng.stats()["warmup_s"].values())
    for _ in range(3):
        eng.submit(_req(app))
    done = eng.run_until_idle()
    assert [r.status for r in done] == ["ok"] * 3
    for r in done:
        assert r.report.execution == "resident"
        _assert_matches_solo(r, compiled, app)
    st = eng.stats()
    assert st["launches_by_bucket"] == {1: 1, 2: 1}   # 3 reqs -> 2 + pad(1)
    assert st["windowed_instead_of_resident"] == 0
    assert [(m, n) for m, n, _s in eng.launch_walls] == \
        [("resident", 2), ("resident", 1)]


def test_resident_degrades_to_windowed():
    """Resident launches that keep failing flip the supervisor's degraded
    latch; the batch replays on the windowed path and still completes."""
    pytest.importorskip("jax")
    app = ALL_APPS["ip2int"]()
    compiled = _compiled(app, "jax")

    def chaos(attempt, mode, reqs):
        if mode == "resident":
            raise SimulatedFault("resident pipeline down")

    eng = AsyncServeEngine(compiled, backend="jax", execution="resident",
                           max_wave=4, queue_cap=8, max_retries=1,
                           degrade_after=2, fault_hook=chaos)
    for _ in range(4):
        eng.submit(_req(app))
    done = eng.run_until_idle()
    assert eng.supervisor.degraded and eng.mode() == "windowed"
    st = eng.stats()
    assert st["resident_fallbacks"] >= 1 and st["degraded"]
    assert st["windowed_instead_of_resident"] == 4
    assert [r.status for r in done] == ["ok"] * 4
    for r in done:
        _assert_matches_solo(r, compiled, app)


def test_resident_on_backend_without_resident_path_raises():
    """No silent downgrade: asking for resident launches on the numpy
    oracle fails at construction, as ``run_fused`` does per call."""
    app = ALL_APPS["ip2int"]()
    with pytest.raises(ValueError, match="resident"):
        AsyncServeEngine(_compiled(app), execution="resident")


def test_unsupported_graph_counts_windowed_instead_of_resident():
    """A graph the resident loop cannot express is served windowed; every
    such request is counted, so a run that asked for resident can refuse
    itself without the supervisor ever degrading."""
    pytest.importorskip("jax")
    app = ALL_APPS["strlen"]()
    compiled = _compiled(app, "jax")
    red_outs = [o for c in compiled.result.dfg.contexts.values()
                for o in c.outs if o.kind == "reduce"]
    assert red_outs, "strlen should carry a reduce output"
    orig = red_outs[0].reduce_op
    red_outs[0].reduce_op = "xor"      # no resident scatter combiner
    try:
        eng = AsyncServeEngine(compiled, execution="resident", max_wave=2)
        for _ in range(3):
            eng.submit(_req(app))
        done = eng.run_until_idle()
    finally:
        red_outs[0].reduce_op = orig
    assert [r.status for r in done] == ["ok"] * 3
    assert all(r.report.execution == "windowed" for r in done)
    st = eng.stats()
    assert st["windowed_instead_of_resident"] == 3
    assert not st["degraded"] and st["resident_fallbacks"] == 0


# ---------------------------------------------------------------------------
# resident launch path: tick counter and profiler spans
# ---------------------------------------------------------------------------

def test_resident_launch_ticks_counted(ip2int_jax):
    """Each resident launch keeps the ticks of its launch report (the
    device loop's iterations), and stats() sums them."""
    app, compiled = ip2int_jax
    eng = AsyncServeEngine(compiled, backend="jax", execution="resident",
                           max_wave=2, queue_cap=8)
    for _ in range(3):
        eng.submit(_req(app))
    done = eng.run_until_idle()
    assert [r.status for r in done] == ["ok"] * 3
    want = [int(compiled.execute_batch(
        [(dict(app.dram_init), dict(app.params))] * n,
        require_inputs=False, execution="resident",
        bucket_sizes="auto").report.stats["ticks"]) for n in (2, 1)]
    assert all(t > 0 for t in want)
    assert eng.launch_ticks == want
    assert eng.stats()["ticks"] == sum(want)
    # a resident report claims no cost-model cycles or lane occupancy
    for r in done:
        assert r.report.cycles is None and r.report.lane_occupancy is None


def test_windowed_wave_ticks_counted():
    app = ALL_APPS["ip2int"]()
    eng = AsyncServeEngine(_compiled(app), max_wave=2)
    for _ in range(2):
        eng.submit(_req(app))
    done = eng.run_until_idle()
    assert [r.status for r in done] == ["ok"] * 2
    assert len(eng.launch_ticks) == len(eng.launch_walls) == 1
    assert eng.stats()["ticks"] == eng.launch_ticks[0] > 0
    # windowed reports keep their cost-model numbers
    assert all(r.report.cycles > 0 for r in done)


LAUNCH_SPANS = ("revet.batch.check", "revet.batch.fuse",
                "revet.launch.upload", "revet.launch.loop",
                "revet.launch.readback", "revet.batch.split",
                "revet.pump.resolve")


def test_resident_pump_spans_in_profile(ip2int_jax, tmp_path):
    """A profiler capture of one resident pump holds the launch path's
    nine spans: ``revet.pump`` with its arguments, the seven stages inside
    it, once each and in order, and the shared arrays' residency check
    inside the upload."""
    import jax
    from jax.profiler import ProfileData
    app, compiled = ip2int_jax
    eng = AsyncServeEngine(compiled, backend="jax", execution="resident",
                           max_wave=2, queue_cap=8, bucket_sizes=(2,))
    for _ in range(3):
        eng.submit(_req(app))
    eng.pump()                      # launch 0, untraced
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        done = eng.pump()           # launch 1: 1 request in a bucket of 2
    finally:
        jax.profiler.stop_trace()
    assert [r.status for r in done] == ["ok"]
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("revet."):
                    assert e.name not in spans, e.name
                    spans[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
    assert set(spans) == {"revet.pump", "revet.launch.upload.check",
                          *LAUNCH_SPANS}
    p0, p1, args = spans["revet.pump"]
    assert args == {"launch": 1, "size": 2, "served": 1}
    assert spans["revet.batch.check"][2] == {"requests": 1}
    assert spans["revet.batch.fuse"][2] == {"slots": 2}
    # the upload counts the shared read-only arrays; launch 0 left them
    # resident, so this launch copies only the per-request words
    up = spans["revet.launch.upload"][2]
    assert set(up) == {"shared", "reused", "upload_mib", "compared_mib"}
    assert up["shared"] >= 1 and up["reused"] == 1
    assert 0 < up["upload_mib"] < 1
    assert up["compared_mib"] > 0
    c0, c1, _ = spans["revet.launch.upload.check"]
    assert spans["revet.launch.upload"][0] <= c0 <= c1 \
        <= spans["revet.launch.upload"][1]
    starts = [spans[n][0] for n in LAUNCH_SPANS]
    assert starts == sorted(starts)
    for n in LAUNCH_SPANS:
        s, e, _ = spans[n]
        assert p0 <= s <= e <= p1, n
    # the stages do not overlap
    for a, b in zip(LAUNCH_SPANS, LAUNCH_SPANS[1:]):
        assert spans[a][1] <= spans[b][0], (a, b)
