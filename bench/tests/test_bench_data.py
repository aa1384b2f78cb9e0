"""The configurations' generators, tables, references and controls at a
small scale, against plain Python."""
import json
import math

import numpy as np
import pytest

from bench.harness import BENCH, load_module


def config(name, **sizes):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(sizes)
    return cfg, load_module(BENCH / "configs" / f"{name}.py", f"t_{name}")


PROBE = dict(orders=4000, n_slots=1 << 14, rows_per_request=256)
LIKE = dict(parts=3000, rows_per_request=256)


def test_orders_follow_the_generation_rules():
    cfg, mod = config("tpch_q12_probe_sf1", **PROBE)
    keys, prio, lines = mod.orders(cfg, np.random.default_rng(5))
    assert len(keys) == 4000 and np.all(np.diff(keys) > 0)
    # of every 32 consecutive keys the first 8 are used
    assert set(((keys - 1) % 32).tolist()) == set(range(8))
    assert keys.max() == (4000 // 8) * 32 - 24
    assert set(prio.tolist()) == {1, 2, 3, 4, 5}
    assert lines.min() == 1 and lines.max() == 7


def test_table_build_places_every_key_for_linear_probing():
    cfg, mod = config("tpch_q12_probe_sf1", **PROBE)
    keys, prio, _ = mod.orders(cfg, np.random.default_rng(6))
    n = cfg["n_slots"]
    tk, tv = mod.build_table(keys, prio, n)
    assert np.count_nonzero(tk) == len(keys)
    for k, v in zip(keys.tolist(), prio.tolist()):
        h = mod.mix(np.array([k]))[0] % n
        while tk[h] != k:
            assert tk[h] != 0, f"key {k} not reachable from its home slot"
            h = (h + 1) % n
        assert tv[h] == v


def test_mix_is_the_programs_hash():
    from repro.apps.hash_table import _mix
    _cfg, mod = config("tpch_q12_probe_sf1")
    xs = np.random.default_rng(0).integers(1, 1 << 31, 500)
    assert mod.mix(xs).tolist() == [_mix(int(x)) for x in xs]


def test_probe_keys_all_hit_and_reference_is_the_join():
    cfg, mod = config("tpch_q12_probe_sf1", **PROBE)
    wl = mod.load(cfg, 2 ** 31 + 3)
    keys, prio, _ = mod.orders(cfg, np.random.default_rng([2 ** 31 + 3, 0]))
    kv = dict(zip(keys.tolist(), prio.tolist()))
    arrays, scalars = wl.request(4, 256)
    assert scalars == {"count": 256}
    assert arrays["table_k"].size == 2 * cfg["n_slots"]
    want = [kv[int(q)] for q in arrays["queries"]]
    assert wl.reference(4).tolist() == want
    assert np.array_equal(wl.request(4, 256)[0]["queries"], arrays["queries"])


def test_part_names_follow_the_generation_rules():
    cfg, mod = config("tpch_q9_like_sf1", **LIKE)
    names = mod.part_names(cfg, np.random.default_rng(7))
    words = set(cfg["words"])
    assert names.shape == (3000, 56)
    for row in names:
        raw = bytes(row)
        text = raw.rstrip(b"\0")
        assert len(text) <= 55 and set(raw[len(text):]) <= {0}
        parts = text.decode().split(" ")
        assert len(parts) == 5 and len(set(parts)) == 5
        assert set(parts) <= words


def test_like_reference_counts_the_pattern_per_row():
    cfg, mod = config("tpch_q9_like_sf1", **LIKE)
    wl = mod.load(cfg, 9)
    arrays, _ = wl.request(1, 256)
    text = arrays["text"]
    rows = [bytes(text[i * 56:(i + 1) * 56]) for i in range(256)]
    assert wl.reference(1).tolist() == [r.count(b"green") for r in rows]
    assert text.size == 256 * 56 + 64 and not text[256 * 56:].any()
    share = np.mean([wl.reference(i).astype(bool).mean() for i in range(8)])
    assert math.isclose(share, 5 / 92, abs_tol=0.02)
    shift = arrays["shift"]
    assert shift[ord("n")] == 5 and shift[ord("e")] == 1 \
        and shift[ord("g")] == 4 and shift[ord("x")] == 5


@pytest.mark.parametrize("name,sizes,requests", [
    ("tpch_q12_probe_sf1", dict(orders=40000, n_slots=1 << 17,
                                rows_per_request=1024), 12),
    ("tpch_q9_like_sf1", dict(parts=8192, rows_per_request=1024), 8),
])
def test_control_fails_the_comparison(name, sizes, requests):
    """The control (the reference with a guarantee broken, in the
    program's place) fails the run's comparison, as on the chip."""
    from bench.control import control_window
    from bench.harness import check
    cfg, mod = config(name, **sizes)
    wl = mod.load(cfg, 2 ** 31 + 11)
    checks, failed = check(control_window(wl, requests), wl)
    c = checks["mismatched_rows"]
    assert c["value"] > c["limit"] and failed > 0
    assert all(v["value"] <= v["limit"] for k, v in checks.items()
               if k != "mismatched_rows")
