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


@pytest.mark.parametrize("name,n_requests", [("hash_table", 8),
                                             ("murmur3", 1)])
def test_resident_program_compiles_for_v5e(name, n_requests, one_chip,
                                           no_persistent_cache):
    app = ALL_APPS[name](**BENCH_SIZES[name])
    compiled = revet.compile(
        app.fn, **app.dram_init, **app.params, **app.statics,
        options=revet.CompileOptions(backend="jax", place=True,
                                     execution="resident"))
    result = compiled.result
    # the launch shape the serving path builds (api.run_fused): pools scale
    # with the batch, DRAM images are fused per request
    dp = compiled.backend.compile_resident(
        result, placement=compiled.placement, n_requests=n_requests,
        pool_override={p: pool.n_bufs * n_requests
                       for p, pool in result.dfg.pools.items()})
    dp._build()
    fused = revet.fuse_dram_images(result.dfg, [app.dram_init] * n_requests)
    state = dp._init_state(fused, [dict(app.params)] * n_requests)
    specs = {k: jax.ShapeDtypeStruct(np.shape(v), v.dtype, sharding=one_chip)
             for k, v in state.items()}
    exe = dp._jit_run.lower(specs).compile()
    mem = exe.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    # every DRAM array rides in as an argument
    dram_bytes = sum(4 * d.size * n_requests for d in result.dfg.dram.values())
    assert mem.argument_size_in_bytes >= dram_bytes
