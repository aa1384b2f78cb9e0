"""TPC-H SF1 ``O_COMMENT`` filtered by Q13's
``o_comment NOT LIKE '%special%requests%'``.

Data (TPC-H clauses 4.2.2 and 4.2.3): a pool of text is generated from the
specification's sentence grammar, each word drawn by its weight; each
order's comment is a substring of the pool at a uniform offset, of a length
uniform in 19-78 bytes (0.4 and 1.6 times the column's mean of 49).  The
comments are packed end to end into one column, with an offsets array of
``orders + 1`` words.  Every request carries the same column, offsets,
terms and shift tables (the program lays them out once and keeps them on
the device); a request is one morsel of consecutive rows, named by its
first row.  The window scans morsel after morsel from a start drawn from
the seed.
"""
from __future__ import annotations

import math

import numpy as np

from bench.workload import Workload


def _weighted(rng: np.random.Generator, weights, n: int) -> np.ndarray:
    """``n`` indices drawn by ``weights``."""
    cum = np.cumsum(np.asarray(weights, np.float64))
    return np.searchsorted(cum, rng.random(n) * cum[-1], side="right")


def _padded(seqs) -> tuple[np.ndarray, np.ndarray]:
    """``(table, mask)``: the sequences as rows of one zero-padded table,
    and which of its cells hold a sequence's items."""
    width = max(len(s) for s in seqs)
    table = np.zeros((len(seqs), width), np.int32)
    mask = np.zeros((len(seqs), width), bool)
    for i, s in enumerate(seqs):
        table[i, :len(s)] = s
        mask[i, :len(s)] = True
    return table, mask


def _ragged(rows: np.ndarray, table: np.ndarray, mask: np.ndarray
            ) -> np.ndarray:
    """The rows ``table[r]`` for each ``r`` in ``rows``, each cut to its
    ``mask[r]``, end to end."""
    return table[rows][mask[rows]]


class Grammar:
    """The pseudo-text grammar of clause 4.2.2 as tables of codes.

    A symbol is a rule (``sentence``, ``N``, ``V``, ``P``: each a list of
    weighted alternatives), a word category (``n``, ``v``, ``j``, ``d``,
    ``p``, ``x``, ``T``: weighted words) or a literal (``the``, ``,``).
    Generation expands rules until only categories and literals are left,
    then draws a word for each category; every step is one vectorised pass
    over all sentences."""

    def __init__(self, cfg: dict):
        rules = cfg["grammar"]
        cats = cfg["words"]
        lits: list[str] = []
        for alts in rules.values():
            for alt, _w in alts:
                for item in alt.split():
                    if item.endswith(","):
                        lits.append(",")
                        item = item[:-1]
                    if item not in rules and item not in cats:
                        lits.append(item)
        self.symbols = list(rules) + list(cats) + sorted(set(lits))
        code = {s: i for i, s in enumerate(self.symbols)}
        self.rules = [code[r] for r in rules]
        # alternatives: one row each, and one identity row per symbol
        rows, self.alt_weights, self.alt_first = [], [], {}
        for r in rules:
            self.alt_first[code[r]] = (len(rows), len(rules[r]))
            for alt, w in rules[r]:
                seq = []
                for item in alt.split():
                    if item.endswith(","):
                        seq += [code[item[:-1]], code[","]]
                    else:
                        seq.append(code[item])
                rows.append(seq)
                self.alt_weights.append(w)
        self.identity = len(rows)
        rows += [[i] for i in range(len(self.symbols))]
        self.alts, self.alt_mask = _padded(rows)
        # the vocabulary: every category's words, then the literals
        words: list[str] = []
        self.cat_words = {}
        for c, ws in cats.items():
            self.cat_words[code[c]] = (len(words), [w for _s, w in ws])
            words += [s for s, _w in ws]
        self.lit_word = {}
        for s in self.symbols[len(rules) + len(cats):]:
            self.lit_word[code[s]] = len(words)
            words.append(s)
        raw = [w.encode() for w in words]
        self.word_bytes = np.frombuffer(b"".join(raw), np.uint8)
        # no space before a terminator or a comma
        self.attached = np.zeros(len(words), bool)
        t0, tw = self.cat_words[code["T"]]
        self.attached[t0:t0 + len(tw)] = True
        self.attached[self.lit_word[code[","]]] = True
        # token 2w is word w alone, token 2w + 1 the word after a space
        tokens, self.token_mask = _padded(
            [list(x) for w in raw for x in (w, b" " + w)])
        self.tokens = tokens.astype(np.uint8)
        self.sentence = code["sentence"]

    def text(self, rng: np.random.Generator, n_sentences: int) -> np.ndarray:
        """``n_sentences`` sentences as one uint8 text."""
        seq = np.full(n_sentences, self.sentence, np.int32)
        is_rule = np.zeros(len(self.symbols), bool)
        is_rule[self.rules] = True
        while is_rule[seq].any():
            row = self.identity + seq.astype(np.int64)
            for r in self.rules:
                at = np.flatnonzero(seq == r)
                if at.size:
                    first, n = self.alt_first[r]
                    row[at] = first + _weighted(
                        rng, self.alt_weights[first:first + n], at.size)
            seq = _ragged(row, self.alts, self.alt_mask)
        word = np.empty(seq.size, np.int64)
        for c, (first, weights) in self.cat_words.items():
            at = np.flatnonzero(seq == c)
            word[at] = first + _weighted(rng, weights, at.size)
        for c, w in self.lit_word.items():
            word[seq == c] = w
        # each word with the space before it, where it takes one
        token = 2 * word + ~self.attached[word]
        token[0] &= ~1
        return _ragged(token, self.tokens, self.token_mask)


def text_pool(cfg: dict, rng: np.random.Generator,
              chunk: int = 1 << 16) -> np.ndarray:
    """The pool of ``text_pool_bytes`` bytes the comments are cut from,
    generated ``chunk`` sentences at most at a time."""
    g = Grammar(cfg)
    size = int(cfg["text_pool_bytes"])
    pool = np.empty(size, np.uint8)
    have = 0
    while have < size:
        # sentences average about 45 bytes; draw a little more than needed
        part = g.text(rng, min(chunk, max(64, (size - have) // 40)))
        part = part[:size - have]
        pool[have:have + part.size] = part
        have += part.size
        if have < size:
            pool[have] = ord(" ")
            have += 1
    return pool


def comments(cfg: dict, rng: np.random.Generator
             ) -> tuple[np.ndarray, np.ndarray]:
    """``(text, offsets)``: every comment end to end (uint8), and the
    ``orders + 1`` offsets of their starts and of the end (int32)."""
    pool = text_pool(cfg, rng)
    n = int(cfg["orders"])
    lo, hi = (int(x) for x in cfg["comment_len"])
    lens = rng.integers(lo, hi + 1, n).astype(np.int32)
    starts = rng.integers(0, pool.size - lens + 1).astype(np.int32)
    offsets = np.zeros(n + 1, np.int32)
    np.cumsum(lens, out=offsets[1:])
    src = np.repeat(starts - offsets[:-1], lens)
    src += np.arange(int(offsets[-1]), dtype=np.int32)
    return pool[src], offsets


def column_capacity(cfg: dict) -> int:
    """Bytes the column holds whatever the seed draws: the lengths' mean
    sum and eight standard deviations more, so that every seed runs the
    same shapes and so the same compiled program."""
    n = int(cfg["orders"])
    lo, hi = (int(x) for x in cfg["comment_len"])
    sd = math.sqrt(((hi - lo + 1) ** 2 - 1) / 12)
    return math.ceil(n * (lo + hi) / 2 + 8 * sd * math.sqrt(n))


def horspool_shift(term: bytes) -> np.ndarray:
    m = len(term)
    shift = np.full(256, m, np.int32)
    for j, ch in enumerate(term[:-1]):
        shift[ch] = m - 1 - j
    return shift


def like(row: bytes, terms: list[bytes]) -> bool:
    """``row LIKE '%t1%t2%...%'``: each term found at or after the end of
    the one before."""
    pos = 0
    for t in terms:
        i = row.find(t, pos)
        if i < 0:
            return False
        pos = i + len(t)
    return True


def load(cfg: dict, seed: int) -> Workload:
    from repro.apps.search import like_program

    rows = int(cfg["rows_per_request"])
    peek = int(cfg["peek"])
    terms = [t.encode() for t in cfg["terms"]]
    rng = np.random.default_rng([seed, 0])
    column, offsets = comments(cfg, rng)
    n_morsels = (len(offsets) - 1) // rows
    start = int(rng.integers(n_morsels))
    cap = column_capacity(cfg)
    if column.size > cap:
        raise ValueError(f"comments of {column.size} bytes overflow the "
                         f"column's {cap}")
    text = np.zeros(cap + peek, np.uint8)
    text[:column.size] = column
    del column
    arrays = {
        "text": text, "offsets": offsets,
        "terms": np.frombuffer(b"".join(terms), np.uint8).copy(),
        "term_len": np.array([len(t) for t in terms], np.int32),
        "shift": np.concatenate([horspool_shift(t) for t in terms])}

    def first_row(i: int) -> int:
        return (start + i) % n_morsels * rows

    def morsel(i: int) -> list[bytes]:
        r0 = first_row(i)
        return [text[offsets[r]:offsets[r + 1]].tobytes()
                for r in range(r0, r0 + rows)]

    def request(i: int, count: int):
        return dict(arrays), {"first": first_row(i), "count": count}

    def reference(i: int) -> np.ndarray:
        return np.array([0 if like(r, terms) else 1 for r in morsel(i)])

    def control(i: int) -> np.ndarray:
        # LIKE '%special%': the second term dropped
        return np.array([0 if like(r, terms[:1]) else 1 for r in morsel(i)])

    return Workload(program=like_program,
                    statics={"n_terms": len(terms), "peek": peek},
                    output="kept", rows_per_request=rows,
                    request=request, reference=reference, control=control)
