"""``like_program``: ``NOT LIKE '%t1%t2%...%'`` over a packed column of
variable-length rows, one thread a row, one BMH sweep carrying the index of
the term it seeks (``apps/search.py``).

Each case is checked bit for bit against the plain reference of the TPC-H
Q13 configuration (``bytes.find`` per row), the ``Golden`` interpreter and
the windowed numpy oracle; the last tests run it as one resident fused
launch of 8 requests.  ``search_program`` shares the BMH alignment step
with it, and its IR must stay what it was before the step was shared.
"""
import json

import numpy as np
import pytest

from repro import api as revet
from repro.apps.search import like_program, search_program
from repro.core.compiler import compile_program
from repro.core.lang import select

PEEK = 32


@pytest.fixture(scope="module")
def q13():
    """The Q13 configuration's module and settings: its generator, its
    plain reference ``like`` and its shift tables."""
    from bench.harness import BENCH, load_module
    cfg = json.loads((BENCH / "configs" / "tpch_q13_like_sf1.json")
                     .read_text())
    return cfg, load_module(BENCH / "configs" / "tpch_q13_like_sf1.py",
                            "t_q13_like")


def column(rows: list[bytes]):
    """``(text, offsets)``: the rows end to end, padded by the peek
    window, and their ``len + 1`` offsets."""
    offsets = np.zeros(len(rows) + 1, np.int32)
    np.cumsum([len(r) for r in rows], out=offsets[1:])
    text = np.zeros(int(offsets[-1]) + PEEK, np.uint8)
    text[:offsets[-1]] = np.frombuffer(b"".join(rows), np.uint8)
    return text, offsets


def like_arrays(mod, rows: list[bytes], terms: list[bytes]) -> dict:
    text, offsets = column(rows)
    return {"text": text, "offsets": offsets,
            "terms": np.frombuffer(b"".join(terms), np.uint8).copy(),
            "term_len": np.array([len(t) for t in terms], np.int32),
            "shift": np.concatenate([mod.horspool_shift(t)
                                     for t in terms])}


def expected(mod, rows, terms, first, count) -> np.ndarray:
    return np.array([0 if mod.like(r, terms) else 1
                     for r in rows[first:first + count]])


def check_all_executors(mod, rows, terms, first=0, count=None):
    """``kept`` from Golden and the numpy oracle, each equal to the plain
    reference; returns it."""
    count = len(rows) - first if count is None else count
    arrays = like_arrays(mod, rows, terms)
    scalars = {"first": first, "count": count}
    compiled = revet.compile(like_program, **arrays, **scalars,
                             n_terms=len(terms), peek=PEEK,
                             options=revet.CompileOptions(backend="numpy"))
    want = expected(mod, rows, terms, first, count)
    for executor in ("golden", "vector"):
        got = compiled.execute(arrays, scalars, executor=executor)
        np.testing.assert_array_equal(got.outputs[0], want,
                                      err_msg=executor)
    return want


SP, RQ, DP = b"special", b"requests", b"deposits"

# case -> (terms, rows, kept): kept is 1 where a row does NOT match
CASES = {
    # the first term crosses the ReadIt's first tile, the second its third
    "term_straddles_two_peek_tiles": (
        [SP, RQ],
        [b"a" * 27 + SP + b"bbb" + RQ,
         SP + b"c" * 52 + RQ + b"ddddd",
         b"e" * 29 + SP + b"f" * 22 + RQ],
        [0, 0, 0]),
    "requests_before_special_only": (
        [SP, RQ],
        [RQ + b" " + SP, b"furious " + RQ + b" are " + SP + b" deposits",
         RQ + SP],
        [1, 1, 1]),
    "term_ends_at_the_rows_end": (
        [SP, RQ],
        [b"slyly " + SP + b" " + RQ, SP + RQ, b"x" * 11 + SP + b"y" + RQ,
         b"x" * 12 + SP + b"y" + RQ[:-1]],
        [0, 0, 0, 1]),
    "row_shorter_than_a_term": (
        [SP, RQ],
        [b"", b"spec", b"special", b"request", SP + b"reques"],
        [1, 1, 1, 1, 1]),
    "repeated_first_terms": (
        [SP, RQ],
        [SP + b" " + SP + b" " + SP + b" " + RQ, SP * 3, b"sp" + SP + RQ,
         SP + b" specia " + RQ, b"specspecial " + RQ + b"s", SP + SP],
        [0, 1, 0, 0, 0, 1]),
    "one_term": (
        [SP],
        [b"pending " + SP + b" accounts", b"ironic " + RQ, b"specia",
         SP],
        [0, 1, 1, 0]),
    "three_terms": (
        [SP, RQ, DP],
        [SP + b" " + RQ + b" " + DP, SP + b" " + DP + b" " + RQ,
         RQ + b" " + SP + b" " + RQ + b" " + DP,
         SP + RQ + DP, SP + b" " + RQ + DP[:-1]],
        [0, 1, 0, 0, 1]),
    # the second match may not overlap the first: '%aa%ab%' is false on
    # "aab", true on "aaab"
    "matches_do_not_overlap": (
        [b"aa", b"ab"], [b"aab", b"aaab", b"abaa", b"aaaab", b"aa ab"],
        [1, 0, 1, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_like_program_matches_reference_golden_and_oracle(q13, case):
    _cfg, mod = q13
    terms, rows, kept = CASES[case]
    assert check_all_executors(mod, rows, terms).tolist() == kept


@pytest.mark.parametrize("length", [19, 78])
@pytest.mark.parametrize("n_terms", [1, 2, 3])
def test_like_program_on_seeded_comment_rows(q13, length, n_terms):
    """Rows of one length cut from the configuration's grammar text, a
    third of them with the terms planted in order at seeded places."""
    cfg, mod = q13
    rng = np.random.default_rng([length, n_terms])
    pool = mod.text_pool(dict(cfg, text_pool_bytes=1 << 15), rng)
    terms = [SP, RQ, DP][:n_terms]
    rows = []
    for i in range(48):
        s = int(rng.integers(0, pool.size - length))
        row = bytearray(pool[s:s + length].tobytes())
        if i % 3 == 0 and sum(map(len, terms)) <= length:
            at = np.sort(rng.choice(length - sum(map(len, terms)) + 1,
                                    n_terms))
            shift = 0
            for a, t in zip(at, terms):
                row[a + shift:a + shift + len(t)] = t
                shift += len(t)
        rows.append(bytes(row))
    want = check_all_executors(mod, rows, terms, first=5, count=40)
    assert want.size == 40 and 1 in want.tolist()
    # three terms take 23 bytes: a row of 19 cannot hold them
    assert (0 in want.tolist()) == (sum(map(len, terms)) <= length)


@pytest.fixture(scope="module")
def comments(q13):
    """A small seeded O_COMMENT column from the configuration's generator,
    and like_program compiled resident for morsels of 64 rows."""
    cfg, mod = q13
    small = dict(cfg, orders=1024, text_pool_bytes=1 << 16)
    text, offsets = mod.comments(small, np.random.default_rng(13))
    rows = [text[offsets[r]:offsets[r + 1]].tobytes()
            for r in range(len(offsets) - 1)]
    # plant the pattern at the start of every 16th row (each is 19 bytes
    # or more), so that each morsel holds matches
    for r in range(0, len(rows), 16):
        rows[r] = (SP + b" " + RQ + rows[r])[:len(rows[r])]
    arrays = like_arrays(mod, rows, [SP, RQ])
    compiled = revet.compile(
        like_program, **arrays, first=0, count=64, n_terms=2, peek=PEEK,
        options=revet.CompileOptions(backend="jax", place=True,
                                     execution="resident"))
    return mod, rows, arrays, compiled


def test_resident_fused_launch_of_8_morsels(comments):
    """One resident launch of 8 requests, each a morsel from its own first
    row: the column and the terms are laid out once and shared, and every
    request's rows agree with the reference and the numpy oracle."""
    mod, rows, arrays, compiled = comments
    firsts = [0, 64, 128, 960, 7, 333, 512, 900]
    reqs = [(arrays, {"first": f, "count": 64}) for f in firsts]
    bx = compiled.execute_batch(reqs, execution="resident")
    assert bx.report.execution == "resident"
    assert bx.vm.shared == {"text", "offsets", "terms", "term_len",
                            "shift"}
    for ex, (a, s) in zip(bx, reqs):
        want = expected(mod, rows, [SP, RQ], s["first"], 64)
        np.testing.assert_array_equal(ex.outputs[0], want)
        ref = compiled.execute(dict(a), s, backend="numpy",
                               execution="windowed")
        np.testing.assert_array_equal(ex.outputs[0], ref.outputs[0])
    assert sum(1 - ex.outputs[0] for ex in bx).sum() >= 8
    # the next launch finds the column resident and compares it
    again = compiled.execute_batch(reqs[::-1], execution="resident")
    assert again.report.stats["shared_reuses"] == 1
    for ex, got in zip(again, list(bx)[::-1]):
        np.testing.assert_array_equal(ex.outputs[0], got.outputs[0])


def test_morsels_through_the_serving_engine(comments):
    """The cell's path: 16 requests of one compiled program through
    ``AsyncServeEngine`` in launches of 8, each answered from the shared
    column."""
    from repro.serve.async_engine import AsyncRequest, AsyncServeEngine
    mod, rows, arrays, compiled = comments
    eng = AsyncServeEngine(compiled, max_wave=8, queue_cap=32,
                           execution="resident")
    firsts = [(37 * i) % 960 for i in range(16)]
    for f in firsts:
        eng.submit(AsyncRequest(params={"first": f, "count": 64},
                                dram_init=dict(arrays)))
    done = {r.request.params["first"]: r for r in eng.run_until_idle()}
    assert len(done) == 16 and all(r.ok for r in done.values())
    for f in firsts:
        assert done[f].report.execution == "resident"
        np.testing.assert_array_equal(
            done[f].dram["kept"][:64], expected(mod, rows, [SP, RQ], f, 64))
    st = eng.stats()
    assert st["launches"] == 2 and st["resident_fallbacks"] == 0
    assert st["shared_uploads"] + st["shared_reuses"] == 2


# ---------------------------------------------------------------------------
# search_program after the BMH step was shared
# ---------------------------------------------------------------------------

@revet.program(name="search", outputs={"matches": "count"},
               statics=("chunk", "pat_len"))
def _search_unshared(m_, text, pattern, shift, matches, *, count,
                     chunk=256, pat_len=5):
    """search_program as it was written before like_program shared its
    alignment step."""
    m = pat_len
    with m_.foreach(count) as (b, t):
        base = b.let(t * chunk)
        pos = b.let(0, "pos")
        found = b.let(0, "found")
        it = b.read_it(text, base, tile=32, peek=True)
        with b.while_(pos <= chunk - m) as w:
            j = w.let(m - 1, "j")
            ok = w.let(1, "ok")
            with w.while_((j >= 0) & (ok == 1)) as inner:
                cc = inner.let(inner.deref(it, ahead=j))
                pc = inner.let(inner.dram_load(pattern, j))
                inner.set(ok, select(cc == pc, 1, 0))
                inner.set(j, j - select(cc == pc, 1, 0))
            adv = w.let(0)
            with w.if_else(j < 0) as (hit, miss):
                hit.set(found, found + 1)
                hit.set(adv, m)
                last = miss.let(miss.deref(it, ahead=m - 1))
                miss.set(adv, miss.dram_load(shift, last))
            w.set(pos, pos + adv)
            w.advance(it, adv)
        b.dram_store(matches, t, found)


@pytest.mark.parametrize("rows,chunk,pattern", [
    (1024, 56, b"green"),       # q9_like.closed's shapes
    (16, 256, b"whale"),        # the app's validation instance
])
def test_search_program_ir_is_byte_identical(rows, chunk, pattern):
    """The traced and the compiled IR text of search_program equal those
    of the program before the shared step, so ``q9_like.closed`` compiles
    the same program."""
    kw = dict(text=np.zeros(rows * chunk + 64, np.uint8),
              pattern=np.frombuffer(pattern, np.uint8).copy(),
              shift=np.full(256, len(pattern), np.int32), count=rows,
              chunk=chunk, pat_len=len(pattern))
    now, before = search_program.trace(**kw), _search_unshared.trace(**kw)
    assert now.prog.ir.as_text() == before.prog.ir.as_text()
    assert compile_program(now.prog).prog.as_text() == \
        compile_program(before.prog).prog.as_text()
