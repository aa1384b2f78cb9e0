"""The Q13 configuration (``tpch_q13_like_sf1``): O_COMMENT's generator,
the plain reference of ``NOT LIKE '%special%requests%'``, its control, and
a small serving pass of ``q13_like.closed`` on the CPU."""
import json
import re
import time

import numpy as np
import pytest

from bench.harness import BENCH, load_module, run_cell

NAME = "tpch_q13_like_sf1"
SMALL = dict(orders=6000, text_pool_bytes=1 << 17, rows_per_request=256)


def config(**sizes):
    cfg = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
    cfg.update(sizes)
    return cfg, load_module(BENCH / "configs" / f"{NAME}.py", f"t_{NAME}")


def rows_of(arrays) -> list[bytes]:
    text, off = arrays["text"], arrays["offsets"]
    return [text[off[r]:off[r + 1]].tobytes() for r in range(len(off) - 1)]


def test_o_comment_follows_the_generation_rules():
    cfg, mod = config(**SMALL)
    wl = mod.load(cfg, 2 ** 31 + 21)
    arrays, scalars = wl.request(0, 256)
    off = arrays["offsets"]
    assert off.dtype == np.int32 and off.size == 6001 and off[0] == 0
    lens = np.diff(off)
    # 0.4 and 1.6 times the column's mean of 49, uniform
    assert lens.min() == 19 and lens.max() == 78
    assert abs(lens.mean() - 48.5) < 1.0
    text = arrays["text"]
    assert text.dtype == np.uint8
    assert text.size == mod.column_capacity(cfg) + cfg["peek"]
    assert off[-1] <= mod.column_capacity(cfg)
    assert not text[off[-1]:].any()
    # every byte is a byte of the grammar's words, a space or punctuation
    alphabet = set(mod.Grammar(cfg).word_bytes.tolist()) | {ord(" ")}
    assert set(np.unique(text[:off[-1]]).tolist()) <= alphabet
    # a comment is a substring of the pool: whole words inside it
    words = {w for ws in cfg["words"].values() for w, _ in ws
             for w in w.split()} | {"the"}
    inner = [t for r in rows_of(arrays)[:500]
             for t in re.split(rb"[ .;:?!,]+|--", r)[1:-1] if t]
    assert {t.decode() for t in inner} <= words
    # the same seed gives the same column and morsels, another seed not
    again, s2 = mod.load(cfg, 2 ** 31 + 21).request(0, 256)
    assert np.array_equal(again["text"], text) and s2 == scalars
    other, _ = mod.load(cfg, 2 ** 31 + 22).request(0, 256)
    assert not np.array_equal(other["text"], text)


@pytest.mark.parametrize("chunk", [64, 1 << 16])
def test_text_pool_is_grammar_text_of_its_size(chunk):
    """The pool is clause 4.2.2's 300 MB, and generating it a few sentences
    at a time gives whole sentences of grammar text, joined by spaces."""
    cfg, mod = config()
    assert cfg["text_pool_bytes"] == 300 << 20
    size = 1 << 15
    pool = mod.text_pool(dict(cfg, text_pool_bytes=size),
                         np.random.default_rng(2 ** 31 + 31), chunk=chunk)
    assert pool.dtype == np.uint8 and pool.size == size
    words = {w for ws in cfg["words"].values() for w, _ in ws
             for w in w.split()} | {"the"}
    inner = re.split(rb"[ .;:?!,]+|--", pool.tobytes())[1:-1]
    assert {t.decode() for t in inner if t} <= words
    assert b"  " not in pool.tobytes()
    again = mod.text_pool(dict(cfg, text_pool_bytes=size),
                          np.random.default_rng(2 ** 31 + 31), chunk=chunk)
    assert np.array_equal(again, pool)


def test_requests_share_the_column_and_name_morsels():
    cfg, mod = config(**SMALL)
    wl = mod.load(cfg, 2 ** 40 + 3)
    a0, s0 = wl.request(0, 256)
    a1, s1 = wl.request(1, 256)
    assert set(a0) == {"text", "offsets", "terms", "term_len", "shift"}
    assert all(a0[k] is a1[k] for k in a0)
    n_morsels = 6000 // 256
    assert s0["count"] == 256 and s0["first"] % 256 == 0
    assert s1["first"] == (s0["first"] + 256) % (n_morsels * 256)
    assert wl.request(2 ** 31 + 5, 64)[1]["count"] == 64
    assert a0["terms"].tobytes() == b"specialrequests"
    assert a0["term_len"].tolist() == [7, 8]
    shift = a0["shift"].reshape(2, 256)
    assert shift[0, ord("s")] == 6 and shift[0, ord("l")] == 7
    assert shift[1, ord("t")] == 1 and shift[1, ord("s")] == 2 \
        and shift[1, ord("x")] == 8
    assert wl.statics == {"n_terms": 2, "peek": 32}
    assert wl.output == "kept"


def test_reference_is_the_not_like_semantics():
    cfg, mod = config(**SMALL)
    wl = mod.load(cfg, 2 ** 31 + 23)
    arrays, scalars = wl.request(3, 256)
    rows = rows_of(arrays)[scalars["first"]:scalars["first"] + 256]
    pat = re.compile(rb"special.*requests", re.S)
    assert wl.reference(3).tolist() == \
        [0 if pat.search(r) else 1 for r in rows]
    assert not mod.like(b"requests special", [b"special", b"requests"])
    assert mod.like(b"specialrequests", [b"special", b"requests"])
    assert not mod.like(b"aab", [b"aa", b"ab"])
    # about 1% of O_COMMENT matches, as Q13's filter finds in TPC-H data
    cfg, mod = config(orders=40000, text_pool_bytes=1 << 20,
                      rows_per_request=1024)
    wl = mod.load(cfg, 2 ** 31 + 24)
    share = 1 - np.mean([wl.reference(i).mean() for i in range(39)])
    assert 0.004 < share < 0.02


def test_control_fails_the_comparison():
    """The control (LIKE '%special%': the second term dropped), answered in
    the program's place, fails the run's comparison."""
    from bench.control import control_window
    from bench.harness import check
    cfg, mod = config(orders=12000, text_pool_bytes=1 << 18,
                      rows_per_request=1024)
    wl = mod.load(cfg, 2 ** 31 + 11)
    checks, failed = check(control_window(wl, 8), wl)
    c = checks["mismatched_rows"]
    assert c["value"] > c["limit"] and failed > 0
    assert all(v["value"] <= v["limit"] for k, v in checks.items()
               if k != "mismatched_rows")


@pytest.mark.parametrize("seed", [2 ** 31 + 9, 2 ** 33 + 1])
def test_small_run_of_the_cell_is_correct(monkeypatch, seed):
    """``q13_like.closed`` through the harness at a small size: every
    request answered on the resident path, equal to the reference, the
    column shared and resident across the window's launches."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    out = run_cell("q13_like.closed", seed, 1.0, False,
                   t_start=time.perf_counter(), require_tpu=False,
                   overrides=dict(orders=2048, text_pool_bytes=1 << 16,
                                  rows_per_request=64, warm_rows=16),
                   mix_overrides={"clients": 2, "warm_buckets": [2]})
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"rows_per_s", "p50_ms", "p90_ms", "setup_s"} <= \
        set(out["metrics"])
