"""Differential test matrix — the standing oracle for every execution path.

Each Table III app is rebuilt with a non-default seed (randomized DRAM
inputs whose reference outputs the builder recomputes), then run through the
full executor matrix — Golden language oracle, token-level reference VM, and
the vectorized VM on both the numpy and jax backends — asserting bit-identical
DRAM everywhere and consistent stats (numpy vs jax identical in full;
token vs vector identical on every lane-attributable counter). The batched
execution path (`execute_batch`) plugs into the same oracle: a fused launch
must de-interleave to exactly what the matrix produced per request.
"""
import numpy as np
import pytest

from repro.apps import ALL_APPS
from repro.apps.common import check_app
from repro.core.compiler import compile_program
from repro.core.golden import Golden
from repro.core.token_vm import TokenVM
from repro.core.vector_vm import LANE_STATS, VectorVM

# one non-default seed per app: deterministic, but none of the DRAM images
# the rest of the suite pins
_SEEDS = {name: 1000 + i for i, name in enumerate(sorted(ALL_APPS))}


def _build(name):
    return ALL_APPS[name](seed=_SEEDS[name])


def _lane_stats(vm) -> dict:
    return {k: int(vm.stats.get(k, 0)) for k in LANE_STATS}


@pytest.mark.parametrize("name", sorted(ALL_APPS))
def test_executor_matrix(name, jax_backend):
    """golden == token == vector[numpy] == vector[jax], values and stats."""
    app = _build(name)
    res = compile_program(app.prog)

    golden = Golden(app.prog.ir, app.dram_init)
    want = {k: v.copy() for k, v in golden.run(**app.params).items()}
    check_app(app, want)          # the builder's reference implementation

    tvm = TokenVM(res.dfg, app.dram_init)
    token = tvm.run(**app.params)
    vm_np = VectorVM(res.dfg, app.dram_init, backend="numpy")
    vec_np = vm_np.run(**app.params)
    vm_jx = VectorVM(res.dfg, app.dram_init, backend=jax_backend)
    vec_jx = vm_jx.run(**app.params)

    for arr in want:
        if arr.startswith("__"):
            continue
        np.testing.assert_array_equal(
            token[arr], want[arr],
            err_msg=f"{name}: '{arr}' TokenVM vs golden")
        np.testing.assert_array_equal(
            vec_np[arr], want[arr],
            err_msg=f"{name}: '{arr}' VectorVM[numpy] vs golden")
        np.testing.assert_array_equal(
            vec_jx[arr], want[arr],
            err_msg=f"{name}: '{arr}' VectorVM[jax] vs golden")

    # backend contract: identical stats in full (token counts included)
    assert vm_np.stats == vm_jx.stats, f"{name}: numpy vs jax stats"
    # executor contract: token- and lane-level accounting agree on every
    # per-lane counter (scheduling counters legitimately differ)
    assert _lane_stats(tvm) == _lane_stats(vm_np), \
        f"{name}: TokenVM vs VectorVM lane stats"


@pytest.mark.parametrize("name", sorted(ALL_APPS))
def test_batched_matches_matrix(name):
    """A fused batched launch de-interleaves to the matrix's outputs."""
    app = _build(name)
    compiled = app.fn.lower(**app.dram_init, **app.params,
                            **app.statics).compile("numpy")
    ref = compiled.execute(dict(app.dram_init), app.params)
    batch = compiled.execute_batch(
        [(app.dram_init, app.params)] * 3)
    for rid, ex in enumerate(batch):
        for arr in ref.dram:
            np.testing.assert_array_equal(
                ex.dram[arr], ref.dram[arr],
                err_msg=f"{name}: request {rid} '{arr}' batched vs solo")
        assert ex.report.stats == ref.vm.request_stats(0), \
            f"{name}: request {rid} lane stats"


def check_resident_batch(compiled, reqs, name):
    """One resident launch of ``reqs`` against the windowed numpy oracle's
    fused batch: one launch, each request's DRAM bit-identical, aggregate
    lane stats equal.  Returns ``(oracle, resident)``."""
    bw = compiled.execute_batch(reqs, backend="numpy", replicas=1)
    br = compiled.execute_batch(reqs, execution="resident")
    b = len(reqs)
    assert br.report.execution == "resident"
    assert br.vm.launches == 1
    for rid, (ew, er) in enumerate(zip(bw, br)):
        for arr in ew.dram:
            np.testing.assert_array_equal(
                er.dram[arr], ew.dram[arr],
                err_msg=f"{name} b={b}: request {rid} '{arr}' resident batch")
    assert {k: int(br.report.stats.get(k, 0)) for k in LANE_STATS} == \
        {k: int(bw.report.stats.get(k, 0)) for k in LANE_STATS}, \
        f"{name} b={b}: resident batch aggregate lane stats"
    return bw, br


@pytest.mark.parametrize("name", sorted(ALL_APPS))
def test_resident_matches_oracle(name, jax_backend):
    """Resident execution (one fused device launch, DESIGN.md §9) vs the
    windowed numpy oracle — DRAM bit-identity plus aggregate lane stats on
    every serving shape: single request, fused batch, and against the
    placed/replicated windowed executor.  Per-link token counts are part of
    the windowed contract but not the resident one: loop headers emit wave
    markers per recirculation round, and round structure is
    schedule-dependent (module docstring, core/device_vm.py)."""
    app = _build(name)
    compiled = app.fn.lower(**app.dram_init, **app.params,
                            **app.statics).compile(jax_backend)

    # single request
    ref = compiled.execute(dict(app.dram_init), app.params, backend="numpy")
    res = compiled.execute(dict(app.dram_init), app.params,
                           execution="resident")
    assert res.report.execution == "resident", \
        f"{name}: resident fell back ({getattr(res.vm, 'resident_fallback', None)})"
    assert res.vm.launches == 1
    for arr in ref.dram:
        np.testing.assert_array_equal(
            res.dram[arr], ref.dram[arr],
            err_msg=f"{name}: '{arr}' resident vs windowed oracle")
    assert {k: int(res.report.stats.get(k, 0)) for k in LANE_STATS} == \
        _lane_stats(ref.vm), f"{name}: resident lane stats"

    # fused batch: de-interleaves to the same per-request images
    reqs = [(app.dram_init, app.params)] * 3
    bw, br = check_resident_batch(compiled, reqs, name)

    # replicated windowed executor agrees too (it is itself bit-identical
    # to the fused path; this closes the triangle on the resident launch)
    rw = compiled.execute_batch(reqs, backend="numpy", replicas=2)
    for rid, (ew, er) in enumerate(zip(rw, br)):
        for arr in ew.dram:
            np.testing.assert_array_equal(
                er.dram[arr], ew.dram[arr],
                err_msg=f"{name}: request {rid} '{arr}' resident vs "
                        f"replicated")


@pytest.mark.parametrize("name", sorted(ALL_APPS))
def test_batched_bit_identity_jax(name, jax_backend):
    """Fused launches through the jax kernel route: the wider fused windows
    must stay bit-identical at every batch size."""
    app = ALL_APPS[name]()
    compiled = app.fn.lower(**app.dram_init, **app.params,
                            **app.statics).compile(jax_backend)
    ref = compiled.execute(dict(app.dram_init), app.params)
    for batch in (2, 5):
        check_windowed_batch(compiled, ref, app, batch, name)


def check_windowed_batch(compiled, ref, app, batch, name):
    """A windowed fused launch of ``batch`` copies of ``app``'s request
    de-interleaves to the solo run ``ref``: DRAM and per-request stats."""
    bx = compiled.execute_batch([(app.dram_init, app.params)] * batch)
    for rid, ex in enumerate(bx):
        for arr in ref.dram:
            np.testing.assert_array_equal(
                ex.dram[arr], ref.dram[arr],
                err_msg=f"{name} b={batch} req={rid}: '{arr}' (jax)")
        assert ex.report.stats == ref.vm.request_stats(0)
