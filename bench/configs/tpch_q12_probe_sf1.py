"""TPC-H SF1 ORDERS hash table probed with Q12's LINEITEM join keys.

Data (TPC-H clause 4.2.3): ``o_orderkey`` is sparse, of every 32
consecutive keys the first 8 are used; each order has 1-7 lines, uniform.
Q12's LINEITEM filter (ship mode, dates) does not depend on
``l_orderkey``, so the keys that reach the probe are a uniform sample of
LINEITEM rows: each order is drawn with weight equal to its line count.
The build side stores ``o_orderpriority`` as its code 1-5.

The table is laid out as ``repro.apps.hash_table.build`` lays it: linear
probing from ``mix(key) % n_slots``, doubled at the wrap so that a probe
never wraps.  It is built here, vectorised, with a copy of that hash.
"""
from __future__ import annotations

import numpy as np

from bench.workload import Workload

# Probes the control follows before it gives up: one 8-slot tile, the
# width the program's ReadIt fetches at a time.
CONTROL_PROBE_LIMIT = 8


def mix(x: np.ndarray) -> np.ndarray:
    """The table's hash (``hash_table._mix``), on arrays of keys."""
    x = np.asarray(x).astype(np.uint64) & 0xFFFFFFFF
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x45D9F3B)) & np.uint64(0xFFFFFFFF)
    x ^= x >> np.uint64(16)
    return x


def orders(cfg: dict, rng: np.random.Generator):
    """``(o_orderkey, o_orderpriority code, line count)`` of every order,
    keys ascending."""
    n = int(cfg["orders"])
    used, block = int(cfg["orderkeys_used_per_block"]), \
        int(cfg["orderkey_block"])
    idx = np.arange(n, dtype=np.int64)
    keys = (idx // used) * block + idx % used + 1
    prio = rng.integers(1, int(cfg["priorities"]) + 1, n)
    lines = rng.integers(int(cfg["lines_per_order_min"]),
                         int(cfg["lines_per_order_max"]) + 1, n)
    return keys, prio, lines


def build_table(keys: np.ndarray, vals: np.ndarray, n_slots: int):
    """Open-addressing table with linear probing: every key sits at its
    home slot or after an unbroken run of occupied slots from it.  Keys
    are placed in rounds: each round, every unplaced key tries its current
    slot, the first of those that want one free slot takes it, and the rest
    move one slot on."""
    tk = np.zeros(n_slots, np.int32)
    tv = np.zeros(n_slots, np.int32)
    slot = (mix(keys) % np.uint64(n_slots)).astype(np.int64)
    pending = np.arange(len(keys))
    while pending.size:
        s = slot[pending]
        free = tk[s] == 0
        cand, cs = pending[free], s[free]
        taken, first = np.unique(cs, return_index=True)
        winners = cand[first]
        tk[taken] = keys[winners]
        tv[taken] = vals[winners]
        won = np.zeros(len(keys), bool)
        won[winners] = True
        pending = pending[~won[pending]]
        slot[pending] = (slot[pending] + 1) % n_slots
    return tk, tv


def load(cfg: dict, seed: int) -> Workload:
    from repro.apps.hash_table import hash_table_program

    n_slots = int(cfg["n_slots"])
    rows = int(cfg["rows_per_request"])
    keys, prio, lines = orders(cfg, np.random.default_rng([seed, 0]))
    tk, tv = build_table(keys, prio, n_slots)
    table_k = np.concatenate([tk, tk])
    table_v = np.concatenate([tv, tv])
    cum_lines = np.cumsum(lines)

    def probe_keys(i: int) -> np.ndarray:
        line = np.random.default_rng([seed, 1, i]).integers(
            0, cum_lines[-1], rows)
        return keys[np.searchsorted(cum_lines, line, side="right")]

    def request(i: int, count: int):
        q = probe_keys(i).astype(np.int32)
        return ({"table_k": table_k, "table_v": table_v, "queries": q},
                {"count": count})

    def reference(i: int) -> np.ndarray:
        q = probe_keys(i)
        pos = np.minimum(np.searchsorted(keys, q), len(keys) - 1)
        return np.where(keys[pos] == q, prio[pos], 0)

    def control(i: int) -> np.ndarray:
        q = probe_keys(i)
        home = (mix(q) % np.uint64(n_slots)).astype(np.int64)
        out = np.zeros(len(q), np.int64)
        for off in range(CONTROL_PROBE_LIMIT):
            hit = (out == 0) & (table_k[home + off] == q)
            out[hit] = table_v[home + off][hit]
        return out

    return Workload(program=hash_table_program,
                    statics={"n_slots": n_slots}, output="results",
                    rows_per_request=rows, request=request,
                    reference=reference, control=control)
