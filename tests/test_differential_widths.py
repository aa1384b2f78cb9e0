"""The differential matrix at every resident window width.

A resident launch of ``R`` requests fires ``launch_width(R)`` lanes per
context per tick (DESIGN.md §9): 256, 512 and 512 for the batches of 2,
4 and 8 below, where ``tests/test_differential.py`` launches one request
(128 lanes) and a batch of 3.  Each app's resident batch goes through that
module's oracle comparison, and the windowed jax route's batch through its
de-interleaving check.  These cases sit in a module of their own so that a
test runner that distributes by module runs them beside the matrix rather
than after it.
"""
import pytest
from test_differential import check_resident_batch, check_windowed_batch

import revet
from repro.apps import ALL_APPS
from repro.core.device_vm import launch_width


@pytest.fixture(autouse=True)
def _release_programs():
    """Drop each case's compiled programs when it ends.  Every XLA:CPU
    executable keeps thousands of memory mappings while a cached program
    holds it, and 27 resident loops in one process would pass the
    kernel's per-process map limit (``vm.max_map_count``, 65,530 by
    default), which crashes the compiler."""
    yield
    revet.clear_cache()


@pytest.mark.parametrize("batch", [2, 4, 8])
@pytest.mark.parametrize("name", sorted(ALL_APPS))
def test_resident_batch_matches_oracle(name, batch, jax_backend):
    app = ALL_APPS[name]()
    compiled = app.fn.lower(**app.dram_init, **app.params,
                            **app.statics).compile(jax_backend)
    reqs = [(app.dram_init, app.params)] * batch
    _, resident = check_resident_batch(compiled, reqs, name)
    assert resident.report.stats["width"] == launch_width(batch)
    ref = compiled.execute(dict(app.dram_init), app.params)
    check_windowed_batch(compiled, ref, app, batch, name)
