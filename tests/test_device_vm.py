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
from repro.core.device_vm import (DeviceProgram, QueueOverflow,
                                  queue_capacities, resident_unsupported,
                                  shared_dram)
from repro.core.vector_vm import VLEN, VectorVM
from repro.kernels.device_loop import ring_peek, ring_push, window_compact


# ---------------------------------------------------------------------------
# ring invariants (kinds/vals rings indexed by absolute head/tail & (cap-1);
# the trailing PAD slots mirror the front so peek/push are contiguous slices)
# ---------------------------------------------------------------------------

PAD = 8


def _ring(cap: int, nv: int = 2):
    return (jnp.zeros(cap + PAD, jnp.int32),
            jnp.zeros((cap + PAD, nv), jnp.int32))


def _push(kinds, vals, tail, used, cap, ks, vs):
    """Push a concrete batch through ring_push (fixed-width buffers)."""
    w = len(ks)
    kb = jnp.asarray(np.asarray(ks, np.int32))
    vb = jnp.asarray(np.asarray(vs, np.int32))
    kinds, vals, over = ring_push(kinds, vals, jnp.int32(tail),
                                  jnp.int32(used), cap, kb, vb,
                                  jnp.int32(w))
    return kinds, vals, bool(over)


def test_ring_fifo_roundtrip():
    cap = 8
    kinds, vals = _ring(cap)
    ks = [0, 0, 1, 2]
    vs = [[10, 0], [11, 1], [0, 2], [0, 0]]
    kinds, vals, over = _push(kinds, vals, 0, 0, cap, ks, vs)
    assert not over
    k, v = ring_peek(kinds, vals, jnp.int32(0), cap, 4)
    np.testing.assert_array_equal(np.asarray(k), ks)
    np.testing.assert_array_equal(np.asarray(v), vs)


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
    k, v = ring_peek(kinds, vals, jnp.int32(6), cap, 4)
    np.testing.assert_array_equal(np.asarray(k), ks)
    np.testing.assert_array_equal(np.asarray(v)[:, 1], [0, 1, 2, 3],
                                  err_msg="rid column lost across the wrap")


def test_ring_overflow_writes_nothing():
    cap = 8
    kinds, vals = _ring(cap)
    kinds, vals, over = _push(kinds, vals, 0, 0, cap,
                              [0] * 7, [[i, 0] for i in range(1, 8)])
    assert not over
    before_k, before_v = np.asarray(kinds).copy(), np.asarray(vals).copy()
    kinds, vals, over = _push(kinds, vals, 7, 7, cap,
                              [0, 0], [[8, 0], [9, 0]])
    assert over, "7 used + 2 pushed > cap 8 must overflow"
    np.testing.assert_array_equal(np.asarray(kinds), before_k,
                                  err_msg="overflow corrupted the ring")
    np.testing.assert_array_equal(np.asarray(vals), before_v)


def test_window_compact_preserves_order_and_rid():
    keep = jnp.asarray(np.array([1, 0, 1, 1, 0], bool))
    k_in = jnp.asarray(np.array([0, 9, 1, 0, 9], np.int32))
    v_in = jnp.asarray(np.array([[5, 0], [0, 0], [0, 1], [7, 2], [0, 0]],
                                np.int32))
    k_out, v_out, count = window_compact(keep, k_in, v_in)
    assert int(count) == 3
    np.testing.assert_array_equal(np.asarray(k_out)[:3], [0, 1, 0])
    np.testing.assert_array_equal(np.asarray(v_out)[:3, 1], [0, 1, 2])


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
    assert caps_pl == pl.queue_capacities(g)
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
