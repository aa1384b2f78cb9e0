"""Compile the resident device loop for a TPU v5e without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip's compiler
would refuse (unaligned blocks, unlowerable primitives, programs that do
not fit), which interpret mode and XLA:CPU never check.  Each test lowers
one app's :class:`~repro.core.device_vm.DeviceProgram` from shapes placed
on one described chip.  The topology is described inside a fixture so that
only the test process that runs these tests loads the TPU library.
"""
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import revet
from benchmarks.common import BENCH_SIZES
from repro.apps import ALL_APPS
from repro.core.device_vm import shared_dram


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_v5e(fn, arrays, params, statics, n_requests, one_chip,
                     shared_store=None):
    """Lower and compile ``fn``'s resident :class:`DeviceProgram` for one
    described v5e chip, at the launch shape the serving path builds
    (``api.run_fused``): pools scale with the batch, arrays every request
    carries equal and the program only reads are laid out once, the rest
    are fused per request.  Returns the compiled program's DRAM sizes, the
    shared set and the executable's memory analysis."""
    compiled = revet.compile(
        fn, **arrays, **params, **statics,
        options=revet.CompileOptions(backend="jax", place=True,
                                     execution="resident"))
    result = compiled.result
    inits = [arrays] * n_requests
    shared = shared_dram(result.dfg, inits)
    dp = compiled.backend.compile_resident(
        result, placement=compiled.placement, n_requests=n_requests,
        pool_override={p: pool.n_bufs * n_requests
                       for p, pool in result.dfg.pools.items()},
        shared=shared, shared_store=shared_store)
    dp._build()
    fused = revet.fuse_dram_images(result.dfg, inits, shared)
    state, operands, _ = dp._init_state(fused, [dict(params)] * n_requests)

    def specs(tree):
        return {k: jax.ShapeDtypeStruct(np.shape(v), v.dtype,
                                        sharding=one_chip)
                for k, v in tree.items()}

    exe = dp._jit_run.lower(specs(state), specs(operands)).compile()
    sizes = {n: d.size for n, d in result.dfg.dram.items()}
    assert set(operands) == {f"d_{n}" for n in shared}
    assert not any(f"d_{n}" in state for n in shared)
    return sizes, shared, exe.memory_analysis()


def _assert_dram_rides_as_arguments(sizes, shared, mem, n_requests):
    assert mem.argument_size_in_bytes > 0
    # every DRAM array rides in as an argument: a shared one once, the
    # others once per request
    dram_bytes = sum(4 * sz * (1 if n in shared else n_requests)
                     for n, sz in sizes.items())
    assert mem.argument_size_in_bytes >= dram_bytes
    # and the loop hands back only the per-request ones
    assert mem.output_size_in_bytes >= sum(
        4 * sz * n_requests for n, sz in sizes.items() if n not in shared)


@pytest.mark.parametrize("name,n_requests", [("hash_table", 8),
                                             ("murmur3", 1)])
def test_resident_program_compiles_for_v5e(name, n_requests, one_chip,
                                           no_persistent_cache):
    app = ALL_APPS[name](**BENCH_SIZES[name])
    sizes, shared, mem = _compile_for_v5e(
        app.fn, app.dram_init, app.params, app.statics, n_requests,
        one_chip)
    _assert_dram_rides_as_arguments(sizes, shared, mem, n_requests)


class _ShapeStore:
    """A shared-array store that holds each array as its shape alone: the
    compile needs no column on the host or on a device."""

    def held(self, name, value):
        return None, 0

    def put(self, name, dtype, size, value):
        from types import SimpleNamespace
        return SimpleNamespace(n=None, host=None,
                               dev=jax.ShapeDtypeStruct((size,), np.int32))


def test_like_program_compiles_for_v5e_at_the_q13_cell_shapes(
        one_chip, no_persistent_cache):
    """``like_program`` at ``q13_like.closed``'s shapes: 8 requests of
    1,024 rows over an SF1 O_COMMENT column of 1,500,000 rows, every
    array but the output shared."""
    from repro.apps.search import like_program
    orders, rows, n_requests = 1_500_000, 1024, 8
    column = 72_919_683                 # the configuration's capacity
    arrays = {"text": np.zeros(column + 32, np.uint8),
              "offsets": np.zeros(orders + 1, np.int32),
              "terms": np.frombuffer(b"specialrequests", np.uint8).copy(),
              "term_len": np.array([7, 8], np.int32),
              "shift": np.zeros(2 * 256, np.int32)}
    sizes, shared, mem = _compile_for_v5e(
        like_program, arrays, {"first": 0, "count": rows},
        {"n_terms": 2, "peek": 32}, n_requests, one_chip,
        shared_store=_ShapeStore())
    assert shared == set(arrays)
    assert sizes["kept"] == rows
    _assert_dram_rides_as_arguments(sizes, shared, mem, n_requests)
    # the column is one operand of 4 bytes a byte, about 0.27 GiB
    assert mem.argument_size_in_bytes < 2 ** 30
