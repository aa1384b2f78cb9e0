"""TPC-H SF1 ``P_NAME`` scanned by Q9's ``p_name LIKE '%green%'``.

Data (TPC-H clause 4.2.3): each name is five distinct words of the
specification's 92-word list, joined by single spaces.  Names are stored
at a fixed stride of 56 bytes (VARCHAR(55) and a NUL); one thread scans
one row (``chunk`` = 56) with Boyer-Moore-Horspool.  A request is one
morsel of consecutive rows; the window scans morsel after morsel from a
start drawn from the seed.
"""
from __future__ import annotations

import numpy as np

from bench.workload import Workload

_PAD = 64              # the program's peek window reads past the last row
# The control scans this many bytes of each row: a narrower stride than
# the column's VARCHAR(55), the cut that would halve the scan.
CONTROL_ROW_BYTES = 32


def part_names(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """``(parts, stride)`` uint8: each row one NUL-padded name."""
    words = [w.encode() for w in cfg["words"]]
    n, k, stride = int(cfg["parts"]), int(cfg["words_per_name"]), \
        int(cfg["name_stride"])
    r = rng.random((n, len(words)), dtype=np.float32)
    pick = np.argpartition(r, k, axis=1)[:, :k]
    # the k smallest of independent uniforms, in the order of their values:
    # k distinct words in a uniformly random order
    pick = np.take_along_axis(
        pick, np.take_along_axis(r, pick, 1).argsort(1, kind="stable"), 1)
    out = np.zeros((n, stride), np.uint8)
    for row, p in enumerate(pick):
        name = b" ".join(words[j] for j in p)
        out[row, :len(name)] = np.frombuffer(name, np.uint8)
    return out


def horspool_shift(pattern: bytes) -> np.ndarray:
    m = len(pattern)
    shift = np.full(256, m, np.int32)
    for j, ch in enumerate(pattern[:-1]):
        shift[ch] = m - 1 - j
    return shift


def load(cfg: dict, seed: int) -> Workload:
    from repro.apps.search import search_program

    rows = int(cfg["rows_per_request"])
    stride = int(cfg["name_stride"])
    pattern = cfg["pattern"].encode()
    rng = np.random.default_rng([seed, 0])
    names = part_names(cfg, rng)
    n_morsels = len(names) // rows
    start = int(rng.integers(n_morsels))
    pat = np.frombuffer(pattern, np.uint8).copy()
    shift = horspool_shift(pattern)

    def morsel(i: int) -> np.ndarray:
        m = (start + i) % n_morsels
        return names[m * rows:(m + 1) * rows]

    def request(i: int, count: int):
        text = np.zeros(rows * stride + _PAD, np.uint8)
        text[:rows * stride] = morsel(i).ravel()
        return ({"text": text, "pattern": pat, "shift": shift},
                {"count": count})

    def reference(i: int) -> np.ndarray:
        return np.array([bytes(r).count(pattern) for r in morsel(i)])

    def control(i: int) -> np.ndarray:
        return np.array([bytes(r[:CONTROL_ROW_BYTES]).count(pattern)
                         for r in morsel(i)])

    return Workload(program=search_program,
                    statics={"chunk": stride, "pat_len": len(pattern)},
                    output="matches", rows_per_request=rows,
                    request=request, reference=reference, control=control)
