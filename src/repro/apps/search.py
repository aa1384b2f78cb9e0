"""search — exact-match substring search with Boyer-Moore-Horspool
(Table III: 'PeekReadIt, while (x2)').

The nested data-dependent while loops (outer alignment sweep, inner backwards
match) are exactly what MapReduce cannot express and what gives the
asymptotic win over the GPU baseline (§VI-B(b)). Each thread scans one chunk.

``like_program`` runs the same alignment step (:func:`bmh_step`) over a
packed column of variable-length rows for a pattern of several terms, SQL's
``NOT LIKE '%t1%t2%...%'`` (TPC-H Q13's ``o_comment`` filter).
"""
from __future__ import annotations

import numpy as np

from .. import api as revet
from ..core.lang import select
from .common import App, make_app

_PAD = 64  # peek-window overfetch padding appended to the text


def bmh_step(w, it, m, pos, pattern, shift, on_hit, pat_base=None,
             shift_base=None) -> None:
    """One Boyer-Moore-Horspool alignment at ``it``'s cursor, the body of a
    sweep whose cursor sits at ``pos``: compare the ``m`` pattern bytes
    backwards through the peeking ReadIt; on a full match call
    ``on_hit(block)`` and step past the match, else shift by the table's
    entry for the window's last byte.  ``pattern[pat_base + j]`` is the
    pattern's ``j``-th byte and ``shift[shift_base + c]`` the shift of byte
    ``c`` (a base of ``None`` reads from 0); ``m`` is the pattern length,
    a constant or a variable, and must stay below the ReadIt's tile."""
    def at(base, off):
        return off if base is None else base + off

    j = w.let(m - 1, "j")
    ok = w.let(1, "ok")
    with w.while_((j >= 0) & (ok == 1)) as inner:
        cc = inner.let(inner.deref(it, ahead=j))
        pc = inner.let(inner.dram_load(pattern, at(pat_base, j)))
        inner.set(ok, select(cc == pc, 1, 0))
        inner.set(j, j - select(cc == pc, 1, 0))
    adv = w.let(0)
    with w.if_else(j < 0) as (hit, miss):
        on_hit(hit)
        hit.set(adv, m)
        last = miss.let(miss.deref(it, ahead=m - 1))
        miss.set(adv, miss.dram_load(shift, at(shift_base, last)))
    w.set(pos, pos + adv)
    w.advance(it, adv)


@revet.program(name="search", outputs={"matches": "count"},
               statics=("chunk", "pat_len"))
def search_program(m_, text, pattern, shift, matches, *, count,
                   chunk=256, pat_len=5):
    m = pat_len
    with m_.foreach(count) as (b, t):
        base = b.let(t * chunk)
        pos = b.let(0, "pos")          # alignment start within chunk
        found = b.let(0, "found")
        # peek window covers pattern + shift lookahead
        it = b.read_it(text, base, tile=32, peek=True)
        with b.while_(pos <= chunk - m) as w:
            bmh_step(w, it, m, pos, pattern, shift,
                     lambda hit: hit.set(found, found + 1))
        b.dram_store(matches, t, found)


@revet.program(name="like", outputs={"kept": "count"},
               statics=("n_terms", "peek"))
def like_program(m_, text, offsets, terms, term_len, shift, kept, *,
                 first, count, n_terms=2, peek=32):
    """``NOT LIKE '%t1%t2%...%'`` over a packed column of variable-length
    rows: row ``r`` is ``text[offsets[r]:offsets[r + 1]]``, and the thread
    of request row ``t`` (column row ``first + t``) stores ``kept[t] = 1``
    where the row does not hold the terms in order, else 0.

    The terms' bytes lie end to end in ``terms``, their lengths in
    ``term_len`` (each below ``peek``), and ``shift`` holds one 256-word
    Horspool table per term.  One BMH sweep carries the index ``k`` of the
    term it seeks: a match of term ``k`` moves the sweep past it and on to
    term ``k + 1``.  Taking each term's leftmost occurrence after the
    previous one leaves the longest rest of the row, so the sweep matches
    exactly where the pattern does.  ``text`` runs ``peek`` bytes past the
    last row, for the ReadIt's look-ahead."""
    with m_.foreach(count) as (b, t):
        row = b.let(first + t, "row")
        start = b.dram_load(offsets, row)
        end = b.dram_load(offsets, row + 1)
        n = b.let(end - start, "n")     # the row's length
        k = b.let(0, "k")               # the term sought
        tb = b.let(0, "tb")             # its first byte in terms
        m = b.let(b.dram_load(term_len, 0), "m")
        m_next = b.let(m, "m_next")
        pos = b.let(0, "pos")
        it = b.read_it(text, start, tile=peek, peek=True)
        with b.while_((k < n_terms) & (pos <= n - m)) as w:
            def next_term(hit):
                hit.set(k, k + 1)
                hit.set(tb, tb + m)
                hit.set(m_next, hit.dram_load(term_len,
                                              k.min_(n_terms - 1)))
            bmh_step(w, it, m, pos, terms, shift, next_term, pat_base=tb,
                     shift_base=k * 256)
            w.set(m, m_next)
        b.dram_store(kept, t, select(k < n_terms, 1, 0))


def build(n_chunks: int = 16, chunk: int = 256, pattern: bytes = b"whale",
          seed: int = 0) -> App:
    rng = np.random.default_rng(seed)
    m = len(pattern)
    # text with planted occurrences (moby-dick-ish alphabet)
    alphabet = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)
    text = rng.choice(alphabet, size=n_chunks * chunk).astype(np.uint8)
    for _ in range(n_chunks * 2):
        pos = int(rng.integers(0, n_chunks * chunk - m))
        text[pos: pos + m] = np.frombuffer(pattern, np.uint8)

    # Horspool bad-character shift table
    shift = np.full(256, m, np.int64)
    for j, ch in enumerate(pattern[:-1]):
        shift[ch] = m - 1 - j

    # reference: non-overlapping-after-match count (matches `adv = m` on hit)
    expected = []
    for t in range(n_chunks):
        s = bytes(text[t * chunk:(t + 1) * chunk])
        cnt = 0
        i = 0
        while i <= chunk - len(pattern):
            if s[i:i + len(pattern)] == pattern:
                cnt += 1
                i += len(pattern)
            else:
                i += int(shift[s[i + len(pattern) - 1]])
        expected.append(cnt)

    padded = np.concatenate([text, np.zeros(_PAD, np.uint8)])
    return make_app(
        search_program, name="search",
        inputs={"text": padded,
                "pattern": np.frombuffer(pattern, np.uint8),
                "shift": shift},
        params={"count": n_chunks},
        statics={"chunk": chunk, "pat_len": m},
        expected={"matches": np.array(expected)},
        bytes_processed=n_chunks * chunk,
        meta={"threads": n_chunks, "features": "PeekReadIt, while(x2), "
              "Boyer-Moore-Horspool"})
