"""Unit tests for the resident device executor (core/device_vm.py).

The differential matrix (tests/test_differential.py) proves whole-program
bit-identity; this file pins the pieces: the fixed-capacity ring primitives
(head/tail/rid invariants in kernels/device_loop.py), the host-side
capacity pre-check and :class:`QueueOverflow` diagnostics, the
placement-derived ring sizing, and the windowed fallback for graphs the
fused loop cannot express.
"""
import re

import numpy as np
import pytest

pytest.importorskip("jax")

import jax.numpy as jnp

from repro.apps import ALL_APPS
from repro.core.compiler import CompileOptions, compile_program
from repro.core.device_vm import (MAX_WIDTH, DeviceProgram, QueueOverflow,
                                  launch_width, queue_capacities,
                                  resident_unsupported, ring_floor,
                                  shared_dram)
from repro.core.vector_vm import VLEN, VectorVM
from repro.kernels.device_loop import ring_peek, ring_push, window_compact


# ---------------------------------------------------------------------------
# ring invariants (kinds/vals rings indexed by absolute head/tail & (cap-1);
# a payload is one (cap + pad,) vector per column, and the trailing pad
# slots are scratch so peek/push are contiguous slices)
# ---------------------------------------------------------------------------

PAD = 8


def _ring(cap: int, nv: int = 2, pad: int = PAD):
    return (jnp.zeros(cap + pad, jnp.int32),
            tuple(jnp.zeros(cap + pad, jnp.int32) for _ in range(nv)))


def _push(kinds, vals, tail, used, cap, ks, vs):
    """Push a concrete batch through ring_push (fixed-width buffers);
    ``vs`` holds one payload row per token."""
    w = len(ks)
    kb = jnp.asarray(np.asarray(ks, np.int32))
    vb = tuple(jnp.asarray(c) for c in np.asarray(vs, np.int32).T)
    kinds, vals, over = ring_push(kinds, vals, jnp.int32(tail),
                                  jnp.int32(used), cap, kb, vb,
                                  jnp.int32(w))
    return kinds, vals, bool(over)


def _peek(kinds, vals, head, cap, width):
    """ring_peek with its payload window as one row per token."""
    k, v = ring_peek(kinds, vals, jnp.int32(head), cap, width)
    assert len(v) == len(vals) and all(c.shape == (width,) for c in v)
    return np.asarray(k), np.stack([np.asarray(c) for c in v], axis=1)


def test_ring_fifo_roundtrip():
    cap = 8
    kinds, vals = _ring(cap)
    ks = [0, 0, 1, 2]
    vs = [[10, 0], [11, 1], [0, 2], [0, 0]]
    kinds, vals, over = _push(kinds, vals, 0, 0, cap, ks, vs)
    assert not over
    k, v = _peek(kinds, vals, 0, cap, 4)
    np.testing.assert_array_equal(k, ks)
    np.testing.assert_array_equal(v, vs)


def test_ring_wraparound_keeps_fifo_order():
    """Head/tail are absolute counters; & (cap-1) indexing must stay FIFO
    across the wrap seam, payload (rid column) included."""
    cap = 8
    kinds, vals = _ring(cap)
    # advance the ring to tail=6 (head=6: all consumed), then push 4 tokens
    kinds, vals, _ = _push(kinds, vals, 0, 0, cap,
                           [0] * 6, [[i, i] for i in range(6)])
    ks = [0, 1, 0, 2]
    vs = [[7, 0], [0, 1], [9, 2], [0, 3]]
    kinds, vals, over = _push(kinds, vals, 6, 0, cap, ks, vs)
    assert not over
    k, v = _peek(kinds, vals, 6, cap, 4)
    np.testing.assert_array_equal(k, ks)
    np.testing.assert_array_equal(v[:, 1], [0, 1, 2, 3],
                                  err_msg="rid column lost across the wrap")


def test_ring_overflow_writes_nothing():
    cap = 8
    kinds, vals = _ring(cap)
    kinds, vals, over = _push(kinds, vals, 0, 0, cap,
                              [0] * 7, [[i, 0] for i in range(1, 8)])
    assert not over
    before_k, before_v = np.asarray(kinds).copy(), np.stack(vals)
    kinds, vals, over = _push(kinds, vals, 7, 7, cap,
                              [0, 0], [[8, 0], [9, 0]])
    assert over, "7 used + 2 pushed > cap 8 must overflow"
    np.testing.assert_array_equal(np.asarray(kinds), before_k,
                                  err_msg="overflow corrupted the ring")
    np.testing.assert_array_equal(np.stack(vals), before_v)


def test_window_compact_preserves_order_and_rid():
    keep = jnp.asarray(np.array([1, 0, 1, 1, 0], bool))
    k_in = jnp.asarray(np.array([0, 9, 1, 0, 9], np.int32))
    v_in = tuple(jnp.asarray(c) for c in np.array(
        [[5, 0], [0, 0], [0, 1], [7, 2], [0, 0]], np.int32).T)
    k_out, v_out, count = window_compact(keep, k_in, v_in)
    assert int(count) == 3
    np.testing.assert_array_equal(np.asarray(k_out)[:3], [0, 1, 0])
    np.testing.assert_array_equal(np.asarray(v_out[1])[:3], [0, 1, 2])


# the same invariants at the widest window a launch fires (8 requests),
# on rings sized as DeviceProgram sizes them: cap 4W, pad W (2W where a
# reduce output's 2W window feeds the ring)
W = MAX_WIDTH


def _tokens(rng, n, nv=3):
    ks = rng.integers(0, 3, n)
    vs = rng.integers(-2 ** 31, 2 ** 31, (n, nv), dtype=np.int64)
    vs[:, -1] = rng.integers(0, 8, n)        # the request-id column
    return ks, vs


def test_ring_wide_fifo_roundtrip():
    cap = 4 * W
    kinds, vals = _ring(cap, nv=3, pad=W)
    ks, vs = _tokens(np.random.default_rng(0), W)
    kinds, vals, over = _push(kinds, vals, 0, 0, cap, ks, vs)
    assert not over
    k, v = _peek(kinds, vals, 0, cap, W)
    np.testing.assert_array_equal(k, ks)
    np.testing.assert_array_equal(v, vs)


@pytest.mark.parametrize("width", [W, 2 * W])
def test_ring_wide_wraparound_keeps_fifo_order(width):
    """A window of ``width`` lanes pushed across the seam (half past cap)
    comes back in order, rid row included, and the wrapped half lands in
    the authoritative front slots."""
    cap = 4 * W
    kinds, vals = _ring(cap, nv=3, pad=width)
    rng = np.random.default_rng(1)
    tail = 3 * cap - width // 2        # absolute counters past two laps
    ks, vs = _tokens(rng, width)
    kinds, vals, over = _push(kinds, vals, tail, 0, cap, ks, vs)
    assert not over
    for lo in range(0, width, W):       # peeks are at most W wide
        k, v = _peek(kinds, vals, tail + lo, cap, W)
        np.testing.assert_array_equal(k, ks[lo: lo + W])
        np.testing.assert_array_equal(
            v[:, -1], vs[lo: lo + W, -1],
            err_msg="rid row lost across the wrap")
        np.testing.assert_array_equal(v, vs[lo: lo + W])
    front = np.stack(vals)[:, : width // 2].T
    np.testing.assert_array_equal(front, vs[width // 2:])


def test_ring_wide_overflow_writes_nothing():
    cap = 4 * W
    kinds, vals = _ring(cap, nv=3, pad=2 * W)
    rng = np.random.default_rng(2)
    ks, vs = _tokens(rng, W)
    kinds, vals, _ = _push(kinds, vals, cap - W // 2, 0, cap, ks, vs)
    before_k, before_v = np.asarray(kinds).copy(), np.stack(vals)
    ks, vs = _tokens(rng, 2 * W)
    used = cap - 2 * W + 1
    kinds, vals, over = _push(kinds, vals, cap + W // 2, used, cap, ks, vs)
    assert over, "one slot short of room for a 2W window must overflow"
    np.testing.assert_array_equal(np.asarray(kinds), before_k,
                                  err_msg="overflow corrupted the ring")
    np.testing.assert_array_equal(np.stack(vals), before_v)


def test_window_compact_wide_preserves_order_and_rid():
    rng = np.random.default_rng(3)
    keep = rng.random(W) < 0.4
    ks, vs = _tokens(rng, W)
    k_out, v_out, count = window_compact(
        jnp.asarray(keep), jnp.asarray(ks.astype(np.int32)),
        tuple(jnp.asarray(c) for c in vs.T.astype(np.int32)))
    n = int(keep.sum())
    assert int(count) == n and len(v_out) == 3
    np.testing.assert_array_equal(np.asarray(k_out)[:n], ks[keep])
    np.testing.assert_array_equal(np.stack(v_out)[:, :n], vs[keep].T)


# ---------------------------------------------------------------------------
# host-side capacity pre-check + overflow diagnostics
# ---------------------------------------------------------------------------

def _dfg(name="murmur3"):
    app = ALL_APPS[name]()
    return app, compile_program(app.prog).dfg


def test_capacity_precheck_names_link():
    app, g = _dfg()
    lid = sorted(g.links)[0]
    with pytest.raises(QueueOverflow) as ei:
        DeviceProgram(g, queue_caps={lid: 64})
    err = ei.value
    assert err.link == lid and err.capacity == 64
    assert f"link {lid}" in str(err)


def test_capacity_precheck_rejects_non_pow2():
    app, g = _dfg()
    lid = sorted(g.links)[0]
    with pytest.raises(QueueOverflow):
        DeviceProgram(g, queue_caps={lid: 4 * VLEN + 1})


def test_launch_width_follows_the_requests():
    """A launch fires one VLEN-lane slice per request, up to MAX_WIDTH,
    and a DeviceProgram takes its width from the rule."""
    assert [launch_width(r) for r in (1, 2, 8, 16)] == [128, 256, 512, 512]
    assert VLEN == 128 and MAX_WIDTH == 512
    _, g = _dfg()
    for r in (1, 2, 8, 16):
        assert DeviceProgram(g, n_requests=r).vlen == launch_width(r)


def test_capacity_precheck_floors_at_four_windows():
    """At the widest window (8 requests, W = 512) a ring below 4W is
    refused, 4W is accepted, the default rings sit at 4W at every width,
    and each ring's scratch pad is its widest push: 2W where a reduce
    output feeds it."""
    app, g = _dfg("strlen")
    w = launch_width(8)
    lid = sorted(g.links)[0]
    with pytest.raises(QueueOverflow) as ei:
        DeviceProgram(g, n_requests=8, queue_caps={lid: 2 * w})
    assert ei.value.link == lid and ei.value.capacity == 2 * w
    assert f"floor {4 * w}" in str(ei.value)
    dp = DeviceProgram(g, n_requests=8, queue_caps={lid: 4 * w})
    assert dp.caps[lid] == 4 * w
    assert DeviceProgram(g, n_requests=8).caps == queue_capacities(g, None, w)
    assert set(queue_capacities(g, None, w).values()) == {4 * w}
    assert [ring_floor(v) for v in (128, 256, 512, 1024)] == \
        [512, 1024, 2048, 4096]
    reduce_fed = {o.link for c in g.contexts.values() for o in c.outs
                  if o.kind == "reduce"}
    assert reduce_fed, "strlen should carry a reduce output"
    assert dp.pad_of == {l: (2 * w if l in reduce_fed else w)
                         for l in g.links}
    st, _, _ = dp._init_state(dict(app.dram_init), [dict(app.params)] * 8)
    for l in g.links:
        cols = st[f"qv{l}"]
        assert len(cols) == len(g.links[l].vars) + 1
        assert {c.shape for c in cols} == {(dp.caps[l] + dp.pad_of[l],)}


def _search_batch(n_requests, rows):
    import revet
    from repro.apps.search import build
    apps = [build(n_chunks=rows, chunk=56, seed=s) for s in range(n_requests)]
    app = apps[0]
    compiled = revet.compile(
        app.fn, **app.dram_init, **app.params, **app.statics,
        options=revet.CompileOptions(backend="jax", execution="resident"))
    g = compiled.result.dfg
    inits = [a.dram_init for a in apps]
    shared = shared_dram(g, inits)
    fused = revet.fuse_dram_images(g, inits, shared)
    pools = {p: pool.n_bufs * n_requests for p, pool in g.pools.items()}
    return apps, g, fused, [dict(a.params) for a in apps], pools, shared


def test_wide_launch_takes_fewer_ticks():
    """``search`` at 8 requests x 128 rows: the 512-lane launch the
    width rule picks takes fewer ticks than 128-lane windows, with equal
    outputs (each request's count of matches, as the reference counts
    them), and its report carries the width and the lanes fired."""
    apps, g, fused, params, pools, shared = _search_batch(8, 128)
    runs = {}
    for vlen in (VLEN, None):
        dp = DeviceProgram(g, n_requests=8, vlen=vlen, pool_override=pools,
                           shared=shared)
        runs[dp.vlen] = dp.run_batch(params, fused)
    narrow, wide = runs[VLEN], runs[MAX_WIDTH]
    for rid, a in enumerate(apps):
        want = a.expected["matches"]
        for run in (narrow, wide):
            np.testing.assert_array_equal(
                run.request_dram(rid)["matches"][: len(want)], want)
    assert wide.stats["ticks"] < narrow.stats["ticks"]
    for w, run in runs.items():
        assert run.stats["width"] == w
        assert 0 < run.stats["lanes_fired"] <= \
            run.stats["ticks"] * w * len(g.contexts)


@pytest.mark.parametrize("n_requests,vlen", [(8, VLEN), (2, None), (4, None)])
def test_search_rings_at_four_windows_hold(n_requests, vlen):
    """``search`` at 1,024 rows a request on rings of 4W slots, the
    pre-check's floor: 8 requests in 128-lane windows, and the default
    widths of 2 and 4 requests.  Every lane a loop header routes in a tick
    (a released group barrier, the backedge run, a wave marker, the
    forward run, an opened group) comes out of its ring's room, so no ring
    overflows and every request counts its matches as the reference
    does."""
    apps, g, fused, params, pools, shared = _search_batch(n_requests, 1024)
    dp = DeviceProgram(g, n_requests=n_requests, vlen=vlen,
                       pool_override=pools, shared=shared)
    assert set(dp.caps.values()) == {ring_floor(dp.vlen)}
    run = dp.run_batch(params, fused)
    for rid, a in enumerate(apps):
        want = a.expected["matches"]
        np.testing.assert_array_equal(
            run.request_dram(rid)["matches"][: len(want)], want)


def test_allocating_merge_at_the_widest_window():
    """A merge whose context allocates (a ReadIt opened after an if/else
    join) at 8 requests x 2,048 threads, each request's pool at the size
    one request needs: the launch fires its widest windows and completes
    with every answer right (an alloc stall inside a merge would fail
    it)."""
    import revet
    from repro.core.dfg import ForwardMergeHead

    @revet.program(name="alloc_merge", outputs={"out": lambda env: env["count"]})
    def alloc_merge(m, src, out, *, count):
        with m.foreach(count) as (b, i):
            s = b.let(0, "s")
            with b.if_else((i & 1) == 1) as (t, e):
                t.set(s, t.dram_load(src, i) * 3)
                e.set(s, e.dram_load(src, i) + 7)
            it = b.read_it(src, i, tile=16)
            b.set(s, s + b.deref(it))
            b.dram_store(out, i, s)

    n, R = 2048, 8
    src = np.arange(n + 64, dtype=np.int32)
    g = alloc_merge.lower(src=src, count=n).compile("jax").result.dfg
    assert any(isinstance(c.head, ForwardMergeHead) and
               any(op.op == "alloc" for op in c.body)
               for c in g.contexts.values())
    init = {"src": src, "out": np.zeros(n, np.int32)}
    want = np.where(np.arange(n) & 1, src[:n] * 3, src[:n] + 7) + src[:n]
    dp = DeviceProgram(g, n_requests=R, pool_override={
        p: pool.n_bufs * R for p, pool in g.pools.items()})
    run = dp.run_batch([{"count": n}] * R,
                       revet.fuse_dram_images(g, [init] * R, frozenset()))
    assert run.stats["width"] == MAX_WIDTH
    for rid in range(R):
        np.testing.assert_array_equal(run.request_dram(rid)["out"][:n], want)


def test_runtime_overflow_decode_names_link_and_capacity():
    """The jit loop latches `err = ring_row + 1`; the host decode must name
    the link's variables and capacity, not an opaque code."""
    app, g = _dfg()
    dp = DeviceProgram(g)
    lid = dp.lids[0]
    with pytest.raises(QueueOverflow) as ei:
        dp._raise_err(dp.row_of[lid] + 1)
    err = ei.value
    assert err.link == lid and err.capacity == dp.caps[lid]
    assert "queue_caps=" in str(err)


def test_queue_capacities_follow_placement_budgets():
    """Placement-derived ring sizing: the same deadlock/retiming buffer
    budgets that size the physical FIFOs scale the device rings
    (Placement.queue_capacities <- machine.map_graph)."""
    app = ALL_APPS["kdtree"]()       # has loop headers -> nonzero margins
    res = compile_program(app.prog, CompileOptions(place=True))
    g, pl = res.dfg, res.placement
    assert pl is not None
    caps_pl = queue_capacities(g, pl)
    assert caps_pl == pl.queue_capacities(g, floor=ring_floor(VLEN))
    caps_default = queue_capacities(g, None)
    for lid, cap in caps_pl.items():
        assert cap & (cap - 1) == 0, f"link {lid}: cap {cap} not a pow2"
        assert cap >= caps_default[lid]
    margined = [cm.ctx_id for cm in pl.report.per_context
                if cm.mu_deadlock + cm.mu_retime > 0]
    boosted = [lid for lid, l in g.links.items() if l.dst in margined]
    assert any(caps_pl[lid] > caps_default[lid] for lid in boosted), \
        "placement margins never widened a ring"


# ---------------------------------------------------------------------------
# fallback rules (DESIGN.md §9)
# ---------------------------------------------------------------------------

def test_unsupported_reduce_falls_back_to_windowed():
    from repro.api import run_fused
    from repro.core.backend import JaxBackend
    app = ALL_APPS["strlen"]()
    res = compile_program(app.prog)
    # force an unsupported reduce combiner on a private compile result
    red_outs = [o for c in res.dfg.contexts.values() for o in c.outs
                if o.kind == "reduce"]
    assert red_outs, "strlen should carry a reduce output"
    orig = red_outs[0].reduce_op
    red_outs[0].reduce_op = "xor"
    try:
        reasons = resident_unsupported(res.dfg)
        assert reasons and "xor" in "; ".join(reasons)
        with pytest.raises(Exception):
            DeviceProgram(res.dfg)
        vm, _wall = run_fused(res, JaxBackend(), [(dict(app.dram_init),
                                                   dict(app.params))],
                              execution="resident")
        assert isinstance(vm, VectorVM), "fallback must be the windowed VM"
        assert vm.resident_fallback and "xor" in vm.resident_fallback
    finally:
        red_outs[0].reduce_op = orig


def test_resident_on_numpy_backend_raises():
    from repro.api import run_fused
    app = ALL_APPS["murmur3"]()
    res = compile_program(app.prog)
    with pytest.raises(ValueError, match="resident"):
        run_fused(res, "numpy", [(dict(app.dram_init), dict(app.params))],
                  execution="resident")


# ---------------------------------------------------------------------------
# names a device trace reads
# ---------------------------------------------------------------------------

def test_loop_module_name_and_scopes():
    """The resident loop compiles to the XLA module ``jit_run`` (the
    benchmark's ``loop_ms`` finds it by that prefix), and the program's
    named scopes reach its HLO metadata: ``revet.loop`` around the tick,
    ``revet.ready`` around the ready snapshot, and one
    ``revet.ctx.<name>.<id>`` around each context's cond."""
    app, g = _dfg()
    dp = DeviceProgram(g, shared=shared_dram(g, [app.dram_init]))
    assert dp.shared        # murmur3's read-only input rides outside the carry
    dp._build()
    st, shared, _ = dp._init_state(dict(app.dram_init), [dict(app.params)])
    text = dp._jit_run.lower(st, shared).compile().as_text()
    assert text.startswith("HloModule jit_run")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("/revet.loop/revet.ready/" in n for n in names)
    for c in g.contexts.values():
        assert any(f"/revet.loop/revet.ctx.{c.name}.{c.id}/cond" in n
                   for n in names), c.name
