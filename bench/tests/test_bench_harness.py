"""The harness: cells found by name, traffic arithmetic, metric readers,
and a small serving pass on the CPU, sound and with the timed path
broken."""
import json
import math

import numpy as np
import pytest

from bench.drive import percentile, poisson_offsets
from bench.harness import BENCH, ROOT, Cell, read_metric, run_cell

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_cell_finds_its_files_by_name(name):
    cell = Cell(name)
    assert cell.cfg["name"] == cell.entry["config"]
    assert cell.config_module.is_file()
    assert cell.mix["kind"] in ("open", "closed")
    if cell.mix["kind"] == "open":
        assert cell.mix["rate_rps"] > 0
    for m in cell.metrics(False) + cell.metrics(True):
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert "setup_s" in [m["name"] for m in cell.metrics(False)]


def test_configs_state_their_cuts():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["name"] == c["name"]


def test_poisson_offsets_give_every_seed_the_same_gaps():
    a = poisson_offsets(100, 2.0, np.random.default_rng(1))
    b = poisson_offsets(100, 2.0, np.random.default_rng(2))
    assert a[0] == b[0] == 0.0 and np.all(np.diff(a) > 0)
    assert not np.array_equal(a, b)
    # the same 100 gaps in another order; each schedule uses 99 of them
    ga, gb = np.round(np.diff(a), 9), np.round(np.diff(b), 9)
    assert np.isin(ga, gb).sum() >= 98
    assert math.isclose(a[-1], 50.0, rel_tol=0.1)


def test_percentile_is_linear_interpolation():
    assert percentile([1, 2, 3, 4], 50) == 2.5
    assert math.isclose(percentile([10.0] * 9 + [20.0], 90), 11.0)


def _rec():
    reqs = [{"due": 0.1 * i, "submit": 0.1 * i, "admit": 0.1 * i + 0.05,
             "done": 0.1 * i + 1.0, "correct": True} for i in range(10)]
    reqs[3]["correct"] = False
    return {"setup": {"setup_s": 12.0, "compile_s": 0.5, "warm_s": 6.0},
            "seconds": 1.0, "rows_per_request": 1024, "requests": reqs,
            "launches": [{"size": 8, "served": 8, "wall_s": 0.5},
                         {"size": 4, "served": 2, "wall_s": 0.3}],
            "image_bytes_per_slot": 1 << 20, "memory_peak_bytes": 3 << 29,
            "trace": {"window_s": 2.0, "busy_s": 0.5,
                      "loop_s": [0.2, 0.4]}}


@pytest.mark.parametrize("name,want", [
    ("rows_per_s", 9 * 1024 / 1.9),
    ("p50_ms", 1000.0),
    ("p90_ms", None),           # the wrong answer is beyond any limit
    ("hbm_peak_gib", 1.5),
    ("setup_s", 12.0),
    ("compile_s", 0.5),
    ("warm_s", 6.0),
    ("queue_ms", 50.0),
    ("pad_share", 2 / 12),
    ("launch_ms", 400.0),
    ("image_mib", 6.0),
    ("loop_ms", 300.0),
    ("idle_share", 0.75),
])
def test_metric_readers(name, want):
    got = read_metric(name, _rec())
    if want is None:
        assert got is None
    else:
        assert math.isclose(got, want, rel_tol=1e-9)


def test_every_metric_has_a_reader():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


# A small rehearsal of a whole run on the CPU, with one program compiled:
# closed, two clients, so every launch is of bucket 2; open (the same
# generator a later open-loop cell reads), two requests a second, far apart
# against a launch of this size, so every launch is of bucket 1.
SMALL = {
    "q12_probe.closed": dict(orders=2000, n_slots=1 << 13,
                             rows_per_request=64, warm_rows=64),
}
SMALL_MIX = {
    "closed": {"clients": 2, "warm_buckets": [2]},
    "open": {"kind": "open", "rate_rps": 2.0, "warm_buckets": [1]},
}


def small_run(cell, monkeypatch, fault=None, seed=2 ** 31 + 5,
              kind="closed"):
    # keep the rehearsal's compiles out of any persistent cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    import time
    return run_cell(cell, seed, 1.0, False, t_start=time.perf_counter(),
                    require_tpu=False, overrides=SMALL[cell],
                    mix_overrides=SMALL_MIX[kind], fault=fault)


class _Broken:
    """The compiled program with its launches' results altered."""

    def __init__(self, inner, alter):
        self._inner, self._alter = inner, alter

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def execute_batch(self, *a, **k):
        bx = self._inner.execute_batch(*a, **k)
        for ex in bx:
            self._alter(ex)
        return bx


def _answer_altered(ex):
    ex.dram["results"][0] += 1


def _state_unchanged(ex):
    ex.dram["results"][:] = 0       # what the request's image held


def _off_path(ex):
    ex.report.execution = "windowed"


@pytest.mark.parametrize("kind", ["closed", "open"])
def test_small_run_is_correct(monkeypatch, kind):
    out = small_run("q12_probe.closed", monkeypatch, kind=kind)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    names = {m["name"] for m in SPEC["end_to_end"]} - {"hbm_peak_gib"}
    assert names <= set(out["metrics"])


@pytest.mark.parametrize("alter,check", [
    (_answer_altered, "mismatched_rows"),
    (_state_unchanged, "mismatched_rows"),
    (_off_path, "off_path_requests"),
])
def test_small_run_with_broken_timed_path_is_not_correct(
        monkeypatch, alter, check):
    def fault(engine):
        engine.compiled = _Broken(engine.compiled, alter)
    out = small_run("q12_probe.closed", monkeypatch, fault=fault)
    assert not out["correct"]
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]
    assert out["failed"] > 0


def test_degraded_engine_is_not_correct(monkeypatch):
    def fault(engine):
        engine.supervisor.degraded = True
    out = small_run("q12_probe.closed", monkeypatch, fault=fault)
    assert not out["correct"]
    assert out["checks"]["fallback_launches"]["value"] > 0


def test_run_refuses_a_platform_without_a_chip(capsys, monkeypatch):
    from bench import run
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")  # run sets it
    assert run.main(["--workload", "q12_probe.closed", "--seed", "1",
                     "--seconds", "1"]) == 1
    cap = capsys.readouterr()
    assert cap.out == "" and "needs a TPU" in cap.err
