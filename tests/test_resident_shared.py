"""Shared read-only arrays on the resident path (DESIGN.md §9).

A resident launch lays an array out once, instead of once per request,
when the program never writes it and every request carries the same value;
its device buffer stays on the compiled program across launches while the
value stays the same.  Every case here is bit-identical to running each
request alone on the windowed numpy oracle.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.apps import ALL_APPS
from repro.core.device_vm import DeviceProgram, shared_dram, written_dram
from repro.serve.async_engine import AsyncRequest, AsyncServeEngine

N = 8


@pytest.fixture(scope="module")
def table():
    """hash_table compiled for jax once: every test's launches of one
    shape share its DeviceProgram (and its shared-array store)."""
    app = ALL_APPS["hash_table"]()
    compiled = app.fn.lower(**app.dram_init, **app.params,
                            **app.statics).compile("jax")
    return app, compiled


def _queries(app, seed: int) -> np.ndarray:
    """A query set of the app's size: hits drawn from the table, and
    misses."""
    rng = np.random.default_rng(seed)
    tk = app.dram_init["table_k"]
    keys = tk[tk != 0]
    n = app.dram_init["queries"].size
    return np.where(rng.random(n) < 0.75, rng.choice(keys, n),
                    rng.integers(1 << 20, 1 << 21, n))


def _fresh_table(app, seed: int):
    """The app's table with its values renumbered, so no earlier launch
    left these contents resident."""
    tv = app.dram_init["table_v"].copy()
    tv[tv != 0] = np.random.default_rng(seed).integers(
        1, 1 << 20, int((tv != 0).sum()))
    return app.dram_init["table_k"].copy(), tv


def _requests(app, tk, tv, seed: int, n: int = N):
    return [({"table_k": tk, "table_v": tv,
              "queries": _queries(app, seed * 100 + r)},
             dict(app.params)) for r in range(n)]


def _assert_matches_sequential(bx, compiled, reqs):
    assert bx.report.execution == "resident"
    for rid, (ex, (arrays, scalars)) in enumerate(zip(bx, reqs)):
        ref = compiled.execute(dict(arrays), scalars, backend="numpy",
                               require_inputs=False)
        for arr in ref.dram:
            np.testing.assert_array_equal(
                ex.dram[arr], ref.dram[arr],
                err_msg=f"request {rid} '{arr}' vs sequential execute")
        for got, want in zip(ex.outputs, ref.outputs):
            np.testing.assert_array_equal(got, want)


def _sizes(compiled):
    return {n: d.size for n, d in compiled.result.dfg.dram.items()}


def test_same_table_object_is_laid_out_once(table):
    app, compiled = table
    tk, tv = _fresh_table(app, 1)
    reqs = _requests(app, tk, tv, 1)
    bx = compiled.execute_batch(reqs, execution="resident")
    _assert_matches_sequential(bx, compiled, reqs)
    sz = _sizes(compiled)
    assert bx.vm.shared == {"table_k", "table_v"}
    assert bx.vm.dram["table_k"].size == sz["table_k"]
    assert bx.vm.dram["table_v"].size == sz["table_v"]
    # the queries differ, the results are written: both stay per request
    assert bx.vm.dram["queries"].size == sz["queries"] * N
    assert bx.vm.dram["results"].size == sz["results"] * N
    assert bx.report.stats["shared_uploads"] == 1


def test_equal_tables_in_distinct_objects_are_shared(table):
    app, compiled = table
    tk, tv = _fresh_table(app, 2)
    reqs = [({**a, "table_k": tk.copy(), "table_v": tv.copy()}, s)
            for a, s in _requests(app, tk, tv, 2)]
    bx = compiled.execute_batch(reqs, execution="resident")
    _assert_matches_sequential(bx, compiled, reqs)
    assert bx.vm.shared == {"table_k", "table_v"}
    assert bx.vm.dram["table_k"].size == _sizes(compiled)["table_k"]


def test_tables_one_word_apart_are_not_shared(table):
    app, compiled = table
    tk, tv = _fresh_table(app, 3)
    reqs = _requests(app, tk, tv, 3)
    other = tk.copy()
    slot = int(np.flatnonzero(other)[0])
    other[slot] = 0                    # request 5 loses one key
    reqs[5] = ({**reqs[5][0], "table_k": other}, reqs[5][1])
    bx = compiled.execute_batch(reqs, execution="resident")
    _assert_matches_sequential(bx, compiled, reqs)
    assert bx.vm.shared == {"table_v"}
    assert bx.vm.dram["table_k"].size == _sizes(compiled)["table_k"] * N


def test_table_mutated_in_place_is_uploaded_again(table):
    app, compiled = table
    tk, tv = _fresh_table(app, 4)
    reqs = _requests(app, tk, tv, 4)
    first = compiled.execute_batch(reqs, execution="resident")
    _assert_matches_sequential(first, compiled, reqs)
    again = compiled.execute_batch(reqs, execution="resident")
    assert again.report.stats["shared_reuses"] == 1
    assert "shared_uploads" not in again.report.stats
    # the caller changes the table between launches, in place
    hit = np.isin(tk, reqs[0][0]["queries"]) & (tk != 0)
    assert hit.any()
    tv[hit] += 7
    after = compiled.execute_batch(reqs, execution="resident")
    _assert_matches_sequential(after, compiled, reqs)
    uploads = sum(b.report.stats.get("shared_uploads", 0)
                  for b in (first, again, after))
    assert uploads == 2
    assert not np.array_equal(after[0].outputs[0], first[0].outputs[0])


def test_absent_and_present_tables_share_one_trace(table):
    """A warm-up without the table (all zeros) and a window with it use
    the same DeviceProgram: both count as shared."""
    app, compiled = table
    tk, tv = _fresh_table(app, 5)
    reqs = _requests(app, tk, tv, 5)
    bare = [({"queries": a["queries"]}, s) for a, s in reqs]
    cold = compiled.execute_batch(bare, execution="resident",
                                  require_inputs=False)
    _assert_matches_sequential(cold, compiled, bare)
    keys = set(compiled.result._resident_cache)
    bx = compiled.execute_batch(reqs, execution="resident")
    _assert_matches_sequential(bx, compiled, reqs)
    assert set(compiled.result._resident_cache) == keys
    assert cold.vm.shared == bx.vm.shared == {"table_k", "table_v"}
    assert bx.report.stats["shared_uploads"] == 1


def test_written_array_is_never_shared(table):
    app, compiled = table
    g = compiled.result.dfg
    assert written_dram(g) == {"results"}
    tk, tv = _fresh_table(app, 6)
    ones = np.ones(_sizes(compiled)["results"], np.int64)
    reqs = [({**a, "results": ones}, s) for a, s in _requests(app, tk, tv, 6)]
    assert "results" not in shared_dram(g, [a for a, _ in reqs])
    bx = compiled.execute_batch(reqs, execution="resident")
    _assert_matches_sequential(bx, compiled, reqs)
    assert bx.vm.dram["results"].size == _sizes(compiled)["results"] * N
    with pytest.raises(ValueError, match="results"):
        DeviceProgram(g, n_requests=N, shared={"results"})


def test_atomic_add_target_is_never_shared():
    app = ALL_APPS["kdtree"]()
    compiled = app.fn.lower(**app.dram_init, **app.params,
                            **app.statics).compile("jax")
    g = compiled.result.dfg
    assert "results" in written_dram(g)
    zeros = np.zeros(g.dram["results"].size, np.int64)
    reqs = [({**app.dram_init, "results": zeros}, dict(app.params))] * 2
    shared = shared_dram(g, [a for a, _ in reqs])
    assert "results" not in shared and "px" in shared
    bx = compiled.execute_batch(reqs, execution="resident")
    _assert_matches_sequential(bx, compiled, reqs)
    assert bx.vm.dram["results"].size == g.dram["results"].size * 2


def test_shared_request_dram_is_read_only(table):
    app, compiled = table
    tk, tv = _fresh_table(app, 7)
    reqs = _requests(app, tk, tv, 7)
    bx = compiled.execute_batch(reqs, execution="resident")
    for ex in bx:
        assert not ex.dram["table_k"].flags.writeable
        assert ex.dram["queries"].flags.writeable
        with pytest.raises(ValueError):
            ex.dram["table_k"][0] = 1
    assert bx[0].dram["table_k"] is bx.vm.request_dram(3)["table_k"]


def test_engine_counts_shared_uploads_and_reuses(table):
    app, compiled = table
    tk, tv = _fresh_table(app, 8)
    eng = AsyncServeEngine(compiled, backend="jax", execution="resident",
                           max_wave=N, queue_cap=4 * N)
    reqs = _requests(app, tk, tv, 8, n=3 * N)
    for arrays, scalars in reqs:
        eng.submit(AsyncRequest(params=scalars, dram_init=arrays))
    done = eng.run_until_idle()
    assert [r.status for r in done] == ["ok"] * len(reqs)
    st = eng.stats()
    assert st["launches"] == 3
    assert (st["shared_uploads"], st["shared_reuses"]) == (1, 2)


def _upload_spans(tmp_path, launch):
    """The ``revet.launch.upload*`` spans of one profiled ``launch()``:
    name -> (start, end, arguments)."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        launch()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("revet.launch.upload"):
                    assert e.name not in spans, e.name
                    spans[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
    return spans


def test_residency_check_is_a_span_with_the_bytes_compared(table, tmp_path):
    """A launch that reuses the resident table compares the launch's value
    with the host image inside ``revet.launch.upload.check``, and
    ``revet.launch.upload`` counts the bytes compared."""
    app, compiled = table
    tk, tv = _fresh_table(app, 9)
    reqs = _requests(app, tk, tv, 9)
    first = compiled.execute_batch(reqs, execution="resident")
    assert first.report.stats["shared_uploads"] == 1
    out = []
    spans = _upload_spans(tmp_path, lambda: out.append(
        compiled.execute_batch(reqs, execution="resident")))
    assert out[0].report.stats["shared_reuses"] == 1
    _assert_matches_sequential(out[0], compiled, reqs)
    assert set(spans) == {"revet.launch.upload",
                          "revet.launch.upload.check"}
    u0, u1, args = spans["revet.launch.upload"]
    c0, c1, _ = spans["revet.launch.upload.check"]
    assert u0 <= c0 <= c1 <= u1
    assert args["reused"] == 1 and args["shared"] == 2
    assert args["compared_mib"] == pytest.approx(
        (tk.nbytes + tv.nbytes) / 2 ** 20)


def test_residency_check_counts_the_bytes_it_compares():
    """Nothing is resident under a name the store has not seen, and a value
    of another size is not compared: both read as not held, 0 bytes.  A
    value of the held size is compared whole, equal or not."""
    from repro.core.device_vm import SharedDram
    store = SharedDram()
    value = np.arange(10, dtype=np.int32)
    assert store.held("a", value) == (None, 0)
    e = store.put("a", "i32", 16, value)
    assert store.held("a", value) == (e, value.nbytes)
    assert store.held("a", value[:5]) == (None, 0)
    assert store.held("a", value + 1) == (None, value.nbytes)
    assert store.held("a", None) == (None, 0)
    zeros = store.put("a", "i32", 16, None)
    assert store.held("a", None) == (zeros, 0)
