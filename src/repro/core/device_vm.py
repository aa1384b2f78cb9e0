"""Device-resident execution — the whole program as **one fused launch**.

The windowed executor (``vector_vm.py``) keeps the superstep scheduler on
the host: every context firing is a separate ``vm_*`` dispatch, so a run
pays ~``ticks`` host round-trips (92–6700 on the Table III apps).  This
module compiles a placed program's *entire* superstep schedule into a
single ``jax.jit``-ed ``lax.while_loop`` over ticks:

* every inter-context queue is a fixed-capacity device ring (a kinds
  vector, one vector per payload column with the hidden request id last,
  and a row in the shared head/tail vectors — see
  ``kernels/device_loop.py``);
* each context fires windows of up to ``W`` lanes a tick, ``W`` following
  the launch's request count (:func:`launch_width`);
* each context's fire/stall decision is a masked tensor computation inside
  the loop body (readiness is evaluated against the tick-start head/tail
  snapshot, exactly like the host scheduler's ready-set snapshot);
* protocol state (counter expansions, loop-header wave sessions, reduce
  accumulators, allocator free lists) lives in small device arrays.

One launch runs the graph to quiescence; the host gets back the DRAM
image, the aggregate stats vector, and an error code it decodes into the
same :class:`~repro.core.vector_vm.VectorDeadlock` diagnostics the
windowed path raises (:class:`QueueOverflow` names the link and capacity).

**Equivalence contract** (DESIGN.md §9): the resident path must be
bit-identical to the windowed oracle in DRAM outputs and aggregate
:data:`~repro.core.vector_vm.LANE_STATS` (every data lane's body ops and
memory effects).  It need *not* replicate the host tick schedule — every
per-link stream is FIFO either way, and per-context windows partition the
same token streams, so window boundaries (and therefore ``ticks``) may
differ while every consumed value and memory effect stays the same.
Per-link token counts also match on loop-free graphs; loop headers emit
one Ω1 *wave marker* per recirculation round, and round structure is
schedule-dependent when parallel sessions overlap, so wave-marker counts
(never data tokens) may differ there.  The ``ticks`` stat reports device
loop iterations; ``launches`` is 1.

Programs using constructs the fused loop cannot express yet
(:func:`resident_unsupported`) fall back to the per-window path; the
Table III apps all run resident.
"""
from __future__ import annotations

import collections
import math
from typing import Optional

import numpy as np

from . import ir
from .dfg import (DFG, Context, CounterHead, ForwardMergeHead,
                  FwdBwdMergeHead, SingleHead, SourceHead, ZipHead,
                  head_links)
from .vector_vm import (LANE_STATS, RID, VLEN, VectorDeadlock,
                        loop_mixing_hazards)
from ..kernels.device_loop import SCATTER_REDUCE_OPS

_I64 = np.int64


class QueueOverflow(VectorDeadlock):
    """A fixed-capacity device queue overflowed (or would, per the host-side
    pre-check).  Names the link and its capacity instead of silently
    wrapping or dying inside an opaque jit abort."""

    def __init__(self, msg: str, link: Optional[int] = None,
                 capacity: Optional[int] = None):
        super().__init__(msg)
        self.link = link
        self.capacity = capacity


# error codes latched by the device loop (state["err"]); 0 = no error.
# Overflow codes name the ring row so the host can report the link.
_ERR_OVERFLOW = 1          # 1..n_rings: overflow on ring row err-1
_ERR_ZIP = 1 << 20         # + ctx id: zip structural mismatch
_ERR_MERGE = 2 << 20       # + ctx id: merge barrier mismatch
_ERR_MERGE_ALLOC = 3 << 20  # + ctx id: alloc stall inside a merge
_ERR_FB = 4 << 20          # + ctx id: loop-header protocol violation


def _next_pow2(n: int) -> int:
    return 1 << max(1, (int(n) - 1).bit_length())


# Default launch-size buckets for resident execution: the same ladder the
# windowed jax engine uses for batch-size bucketing (serve/dataflow.py), so
# one cached DeviceProgram jit trace per bucket serves every batch size in
# between (pad slots replay the last request; see api.run_fused).
RESIDENT_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def bucket_launch_size(n: int, buckets="auto") -> int:
    """Smallest configured bucket >= ``n`` (or ``n`` itself when it exceeds
    every bucket).  ``buckets`` may be ``"auto"``/``True`` for
    :data:`RESIDENT_BUCKETS` or an explicit iterable of sizes."""
    if buckets in ("auto", True):
        buckets = RESIDENT_BUCKETS
    n = int(n)
    for b in sorted(int(b) for b in buckets):
        if b >= n:
            return b
    return n


def resident_unsupported(g: DFG) -> list[str]:
    """Static reasons a DFG cannot run on the fused device loop.  Empty
    means :class:`DeviceProgram` supports it; otherwise the backend falls
    back to the per-window path (fallback rules, DESIGN.md §9)."""
    reasons: list[str] = []
    for c in g.contexts.values():
        for op in c.body:
            if op.op == "rr_counter":
                reasons.append(
                    f"{c.name}: rr_counter (replicate steering) has no "
                    f"fused-loop form yet")
            if op.op == "atomic_add" and \
                    g.dram[op.space].dtype != "i32":
                reasons.append(
                    f"{c.name}: atomic_add on {g.dram[op.space].dtype} "
                    f"DRAM needs a re-masking scatter")
        for o in c.outs:
            if o.kind == "reduce" and o.reduce_op not in SCATTER_REDUCE_OPS:
                reasons.append(
                    f"{c.name}: reduce op {o.reduce_op!r} has no jax "
                    f"scatter combiner (supported: "
                    f"{', '.join(SCATTER_REDUCE_OPS)})")
    return reasons


# Widest window a resident launch fires.  On a TPU v5e, a launch of 8
# requests ran its loop in 1,069 ms at 1,024 lanes and 1,386 ms at 512 (the
# q9 scan; DESIGN.md §9), but its rings, 4W slots or more a link, then
# raised the process's peak device memory above the 128-lane loop's, where
# 512 lanes keep it under.
MAX_WIDTH = 512


def launch_width(n_requests: int) -> int:
    """Lanes a resident launch of ``n_requests`` fires per context per
    tick: each request brings one ``VLEN``-lane slice of issue width, as
    the replicated VM's replicas do, up to :data:`MAX_WIDTH`."""
    return min(max(int(n_requests), 1) * VLEN, MAX_WIDTH)


def ring_floor(vlen: int) -> int:
    """Fewest slots a ring takes for windows of ``vlen`` lanes, and the
    :class:`DeviceProgram` pre-check's floor: a peek needs ``2*vlen``, and
    a ``2*vlen`` reduce push must not meet the front window it wraps
    onto.  Every push is gated by its ring's room, so nothing more is
    needed for the loop not to overflow."""
    return 4 * vlen


def queue_capacities(g: DFG, placement=None, vlen: int = VLEN
                     ) -> dict[int, int]:
    """Ring capacity per link for the resident executor, for windows of
    ``vlen`` lanes: :func:`ring_floor`, scaled, when a placement is given,
    by its per-context deadlock/retiming buffer attribution
    (``machine.map_graph``) — delegated to
    :meth:`~repro.core.place.Placement.queue_capacities`, so the budgets
    that size the physical FIFOs size the device rings.
    """
    floor = ring_floor(vlen)
    if placement is not None:
        return placement.queue_capacities(g, floor=floor)
    return {lid: min(1 << 16, _next_pow2(floor)) for lid in g.links}


_DTYPE_MASK = {"i8": 0xFF, "i16": 0xFFFF, "i32": None}


def written_dram(g: DFG) -> frozenset:
    """DRAM arrays some ``dram_store`` or ``atomic_add`` of the graph
    writes (read once per graph, cached on it)."""
    w = getattr(g, "_written_dram", None)
    if w is None:
        w = g._written_dram = frozenset(
            op.space for c in g.contexts.values() for op in c.body
            if op.op in ("dram_store", "atomic_add"))
    return w


def shared_dram(g: DFG, inits) -> frozenset:
    """The arrays a resident launch lays out once instead of once per
    request (DESIGN.md §9): the program never writes them, and every
    request of the launch carries the same value — absent from all (all
    zeros) or present with equal contents."""
    written = written_dram(g)
    shared = []
    for name in g.dram:
        if name in written:
            continue
        vals = [init.get(name) for init in inits]
        first = vals[0]
        if first is None:
            same = all(v is None for v in vals)
        else:
            flat = np.ravel(first)
            same = all(v is first or (v is not None and np.array_equal(
                np.ravel(v), flat)) for v in vals)
        if same:
            shared.append(name)
    return frozenset(shared)


class _SharedArray:
    """One shared array: its device buffer, the int32 host image it was
    uploaded from (``d.size`` words; a launch's value is compared with
    it), and that image as requests read it back (int64, read-only)."""

    def __init__(self, host: np.ndarray, n: Optional[int], dev):
        self.host = host
        self.n = n              # words the caller gave; None when absent
        self.dev = dev
        self.view = host.astype(np.int64)
        self.view.setflags(write=False)


# One launch's host-to-device account: the shared arrays it read (name ->
# _SharedArray), the bytes it copied from the host, how many shared arrays
# it had to make anew instead of reusing, and the bytes of the launch's
# values that the residency check compared.
_Upload = collections.namedtuple("_Upload", "arrays sent fresh compared")


class SharedDram:
    """Device buffers of the shared arrays, at most one per array name,
    kept across launches by every :class:`DeviceProgram` of one compiled
    program.  A buffer is reused while the launch's value equals the host
    image it was uploaded from; the check is one compare per array per
    launch and is never skipped, so a caller that mutates the array in
    place between launches gets the new contents."""

    def __init__(self):
        self._arrays: dict[str, _SharedArray] = {}

    def held(self, name: str, value) -> tuple[Optional[_SharedArray], int]:
        """The resident entry of ``name`` if it holds ``value`` (None:
        absent, all zeros), else None; and the bytes of ``value`` that the
        residency check compared to find out."""
        e = self._arrays.get(name)
        if e is None:
            return None, 0
        if value is None:
            return (e if e.n is None else None), 0
        v = np.ravel(value)
        if e.n != v.size:
            return None, 0
        return (e if np.array_equal(v, e.host[: v.size]) else None), v.nbytes

    def put(self, name: str, dtype: str, size: int, value) -> _SharedArray:
        """A resident buffer made anew for ``value``, in place of the one
        held.  An absent value is zeroed on the device and copies nothing
        from the host."""
        import jax.numpy as jnp
        from .backend import wrap_dram_init
        self._arrays.pop(name, None)    # free the old buffer first
        if value is None:
            e = _SharedArray(np.zeros(size, np.int32), None,
                             jnp.zeros(size, jnp.int32))
        else:
            w = wrap_dram_init(value, dtype)
            host = np.zeros(size, np.int32)
            host[: w.size] = w
            e = _SharedArray(host, w.size, jnp.asarray(host))
        self._arrays[name] = e
        return e


class DeviceProgram:
    """One DFG compiled to a single resident device launch.

    Specialized per ``(n_requests, vlen, queue capacities, pool sizes,
    shared arrays)``; ``vlen``, the lanes a context fires per tick, follows
    from ``n_requests`` (:func:`launch_width`) unless given.  The front-end
    caches instances per shape
    (``CompiledProgram``), so a serving deployment jit-compiles once per
    launch shape, exactly like the windowed jax path's per-window kernel
    cache but with *one* cache entry for the whole program.

    ``shared`` names the arrays laid out once for the whole launch
    (:func:`shared_dram`); their buffers live in ``shared_store``, which
    the programs of every launch shape share.
    """

    def __init__(self, g: DFG, *, n_requests: int = 1,
                 vlen: Optional[int] = None,
                 queue_caps: dict[int, int] | None = None, placement=None,
                 pool_override: dict[str, int] | None = None,
                 max_ticks: int = 1_000_000, shared=frozenset(),
                 shared_store: SharedDram | None = None):
        reasons = resident_unsupported(g)
        if reasons:
            raise VectorDeadlock(
                "resident execution unsupported: " + "; ".join(reasons))
        self.shared = frozenset(shared)
        bad = sorted(self.shared - (set(g.dram) - written_dram(g)))
        if bad:
            raise ValueError(f"arrays {bad} cannot be shared: the program "
                             f"writes them or does not declare them")
        self.shared_store = shared_store if shared_store is not None \
            else SharedDram()
        self.g = g
        self.n_requests = int(n_requests)
        self.vlen = launch_width(self.n_requests) if vlen is None \
            else int(vlen)
        self.max_ticks = int(max_ticks)
        self.launches = 1
        self.backend = None      # ExecutorBackend, set by compile_resident
        caps = dict(queue_capacities(g, placement, self.vlen))
        caps.update(queue_caps or {})
        # host-side capacity pre-check: a ready context can push up to two
        # tokens per input lane (reduce emissions), within its ring's room
        # (DESIGN.md §9)
        floor = ring_floor(self.vlen)
        for lid, cap in caps.items():
            if cap < floor or cap & (cap - 1):
                l = g.links[lid]
                raise QueueOverflow(
                    f"link {lid} ({l.vars}): capacity {cap} below the "
                    f"resident floor {floor} (or not a power of two) — "
                    f"the fused loop could overflow mid-tick",
                    link=lid, capacity=cap)
        self.caps = caps
        # ring rows: one per link plus the source queue as the last row
        self.lids = sorted(g.links)
        self.row_of = {lid: i for i, lid in enumerate(self.lids)}
        self.src_row = len(self.lids)
        self.src_cap = _next_pow2(max(64, self.n_requests + 1,
                                      2 * self.vlen))
        # scratch pad past each ring's capacity: its widest push, 2W where
        # a reduce output feeds it, else W (a window or a peek)
        reduce_fed = {o.link for c in g.contexts.values() for o in c.outs
                      if o.kind == "reduce"}
        self.pad_of = {lid: (2 if lid in reduce_fed else 1) * self.vlen
                       for lid in self.lids}
        self.source_vars = tuple(getattr(g, "source_vars", ()))
        self._dram_lim = {name: d.size for name, d in g.dram.items()}
        self._dram_mask = {name: _DTYPE_MASK[d.dtype]
                           for name, d in g.dram.items()}
        self.pool_names = sorted(g.pools)
        self.pool_row = {p: i for i, p in enumerate(self.pool_names)}
        self.pool_bufs = {
            p: (pool_override or {}).get(p, g.pools[p].n_bufs)
            for p in self.pool_names}
        self.pool_words = {p: g.pools[p].buf_words for p in self.pool_names}
        if self.n_requests > 1:
            hazards = getattr(g, "_mixing_hazards", None)
            if hazards is None:
                hazards = g._mixing_hazards = loop_mixing_hazards(g)
            self.parallel_loops = not hazards
        else:
            self.parallel_loops = False
        self.order = list(g.contexts.values())
        self.cnt_ctxs = [c.id for c in self.order
                         if isinstance(c.head, CounterHead)]
        self.cnt_row = {cid: i for i, cid in enumerate(self.cnt_ctxs)}
        self.fb_ctxs = [c.id for c in self.order
                        if isinstance(c.head, FwdBwdMergeHead)]
        self.fb_row = {cid: i for i, cid in enumerate(self.fb_ctxs)}
        self.red_keys = [(c.id, oi) for c in self.order
                         for oi, o in enumerate(c.outs) if o.kind == "reduce"]
        self.red_row = {k: i for i, k in enumerate(self.red_keys)}
        self._stat_keys = ("ticks",) + LANE_STATS
        self._stat_row = {k: i for i, k in enumerate(self._stat_keys)}
        self._ctx_alloc_pools = {
            c.id: collections.Counter(op.space for op in c.body
                                      if op.op == "alloc")
            for c in self.order}
        self._jit_run = None    # built lazily on first run

    # ------------------------------------------------------------ host state
    def _init_state(self, dram_init: dict[str, np.ndarray] | None,
                    params_list: list[dict]):
        """Device state of one launch: the loop carry; the shared arrays'
        resident buffers, keyed like the carry's DRAM entries and passed to
        the loop outside it; and the launch's :class:`_Upload` account.
        ``dram_init`` holds the fused per-request images and, for each
        shared array, the one value every request carries."""
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation
        from .backend import wrap_dram_init
        g = self.g
        if len(params_list) != self.n_requests:
            raise ValueError(
                f"run_batch: got {len(params_list)} parameter sets for a "
                f"device program with n_requests={self.n_requests}")
        dram_init = dram_init or {}
        sent = fresh = 0

        def put(a: np.ndarray):
            nonlocal sent
            sent += a.nbytes
            return jnp.asarray(a)

        st: dict = {}
        n_rings = len(self.lids) + 1
        qh = np.zeros(n_rings, np.int32)
        qt = np.zeros(n_rings, np.int32)
        # a ring is a kinds vector and one vector per payload column, the
        # request id last (kernels/device_loop.py)
        for lid in self.lids:
            l = g.links[lid]
            size = self.caps[lid] + self.pad_of[lid]
            st[f"qk{lid}"] = jnp.zeros(size, jnp.int32)
            st[f"qv{lid}"] = tuple(jnp.zeros(size, jnp.int32)
                                   for _ in range(len(l.vars) + 1))
        # source ring: one parameter slot per request, then the closing Ω1
        size = self.src_cap + self.vlen
        sk = np.zeros(size, np.int32)
        sv = np.zeros((len(self.source_vars) + 1, size), np.int32)
        for r, params in enumerate(params_list):
            sv[: len(self.source_vars), r] = [
                ir.wrap32(int(params[p])) for p in self.source_vars]
            sv[-1, r] = r
        sk[self.n_requests] = 1
        qt[self.src_row] = self.n_requests + 1
        st["qkS"] = put(sk)
        st["qvS"] = tuple(put(c) for c in sv)
        st["qh"], st["qt"] = put(qh), put(qt)
        st["lt"] = jnp.zeros(len(self.lids), jnp.int32)
        shared: dict = {}
        arrays: dict = {}
        compared = 0
        if self.shared:
            with TraceAnnotation("revet.launch.upload.check"):
                for name in sorted(self.shared):
                    arrays[name], n = self.shared_store.held(
                        name, dram_init.get(name))
                    compared += n
        for name, d in g.dram.items():
            if name in self.shared:
                e = arrays[name]
                if e is None:
                    e = arrays[name] = self.shared_store.put(
                        name, d.dtype, d.size, dram_init.get(name))
                    fresh += 1
                    if e.n is not None:
                        sent += e.host.nbytes
                shared[f"d_{name}"] = e.dev
                continue
            a = np.zeros(d.size * self.n_requests, np.int32)
            if name in dram_init:
                w = wrap_dram_init(dram_init[name], d.dtype)
                a[: w.size] = w.astype(np.int32)
            st[f"d_{name}"] = put(a)
        n_pools = len(self.pool_names)
        st["fh"] = jnp.zeros(max(n_pools, 1), jnp.int32)
        ft = np.zeros(max(n_pools, 1), np.int32)
        for p in self.pool_names:
            nb, bw = self.pool_bufs[p], self.pool_words[p]
            st[f"p_{p}"] = jnp.zeros(nb * bw, jnp.int32)
            flcap = _next_pow2(nb)
            st[f"fr_{p}"] = put(
                np.resize(np.arange(nb, dtype=np.int32), flcap))
            ft[self.pool_row[p]] = nb
        st["ft"] = put(ft)
        n_cnt = max(len(self.cnt_ctxs), 1)
        st["cnt_act"] = jnp.zeros(n_cnt, bool)
        for key in ("cnt_cur", "cnt_hi", "cnt_step"):
            st[key] = jnp.zeros(n_cnt, jnp.int32)
        for cid in self.cnt_ctxs:
            h = g.contexts[cid].head
            nv = len(g.links[h.link].vars) + 1
            st[f"cb_{cid}"] = jnp.zeros(nv, jnp.int32)
        n_fb = max(len(self.fb_ctxs), 1)
        nr = self.n_requests
        for cid in self.fb_ctxs:
            st[f"fb_mode_{cid}"] = jnp.zeros(nr, jnp.int32)
            st[f"fb_pend_{cid}"] = jnp.zeros(nr, jnp.int32)
            st[f"fb_got_{cid}"] = jnp.zeros(nr, bool)
            st[f"fb_seq_{cid}"] = jnp.zeros(nr, jnp.int32)
        st["fb_nseq"] = jnp.zeros(n_fb, jnp.int32)
        n_red = max(len(self.red_keys), 1)
        racc = np.zeros(n_red, np.int32)
        for (cid, oi), i in self.red_row.items():
            racc[i] = ir.wrap32(g.contexts[cid].outs[oi].reduce_init)
        st["red_acc"] = put(racc)
        st["red_open"] = jnp.zeros(n_red, bool)
        st["stats"] = jnp.zeros(len(self._stat_keys), jnp.int32)
        st["lanes"] = jnp.zeros((), jnp.int32)
        st["prog"] = jnp.asarray(True)
        st["err"] = jnp.zeros((), jnp.int32)
        st["tick"] = jnp.zeros((), jnp.int32)
        return st, shared, _Upload(arrays, sent, fresh, compared)

    # ------------------------------------------------------------- jit build
    def _build(self) -> None:
        import jax
        import jax.numpy as jnp
        from ..kernels import device_loop as dl

        g = self.g
        W = self.vlen
        nreq = self.n_requests
        batched = nreq > 1
        row_of, caps = self.row_of, self.caps
        I32 = jnp.int32

        def ring_of(lid):
            if lid == "S":
                return "qkS", "qvS", self.src_row, self.src_cap
            return f"qk{lid}", f"qv{lid}", row_of[lid], caps[lid]

        def qlen(st, ridx):
            return st["qt"][ridx] - st["qh"][ridx]

        def peek(st, lid, width):
            kk, vk, ridx, cap = ring_of(lid)
            k, v = dl.ring_peek(st[kk], st[vk], st["qh"][ridx], cap, width)
            return k, v, qlen(st, ridx)

        def pop(st, lid, n):
            st["qh"] = st["qh"].at[ring_of(lid)[2]].add(n)

        def push(st, lid, kbuf, vbuf, count):
            kk, vk, ridx, cap = ring_of(lid)
            k2, v2, over = dl.ring_push(
                st[kk], st[vk], st["qt"][ridx], qlen(st, ridx), cap,
                kbuf, vbuf, count)
            st[kk], st[vk] = k2, v2
            ok = jnp.where(over, 0, count)
            st["qt"] = st["qt"].at[ridx].add(ok)
            if lid != "S":
                st["lt"] = st["lt"].at[row_of[lid]].add(ok)
            st["err"] = jnp.where(over & (st["err"] == 0),
                                  _ERR_OVERFLOW + ridx, st["err"])

        def room(st, ctx):
            r = I32(1 << 20)
            for o in ctx.outs:
                r = jnp.minimum(r, caps[o.link] - qlen(st, row_of[o.link]))
            return r

        # a context with reduce outputs can emit up to two tokens per lane,
        # so its window budget halves (back-pressure at window granularity)
        room_div = {c.id: (2 if any(o.kind == "reduce" for o in c.outs)
                           else 1) for c in self.order}

        def stat_add(st, key, amount):
            st["stats"] = st["stats"].at[self._stat_row[key]].add(
                jnp.asarray(amount, jnp.int32))

        def alloc_limit(st, ctx, kinds, n):
            per_pool = self._ctx_alloc_pools[ctx.id]
            if not per_pool:
                return n
            avail = None
            for p, cnt in per_pool.items():
                a = (st["ft"] - st["fh"])[self.pool_row[p]] // cnt
                avail = a if avail is None else jnp.minimum(avail, a)
            lanes = jnp.arange(kinds.shape[0], dtype=I32)
            data = (kinds == 0) & (lanes < n)
            exceeds = (jnp.cumsum(data.astype(I32)) > avail) & (lanes < n)
            return jnp.where(exceeds.any(),
                             jnp.minimum(n, jnp.argmax(exceeds).astype(I32)),
                             n)

        def last_wins(ok, addr):
            # keep only the last ok lane per duplicate address, so the
            # masked scatter-set is deterministic (numpy's fancy-index
            # assignment is later-lane-wins; XLA scatter order is not)
            eq = (addr[None, :] == addr[:, None]) & ok[None, :] & ok[:, None]
            return ok & ~jnp.triu(eq, k=1).any(axis=1)

        def exec_body(st, ctx, kinds, regs, n):
            P = kinds.shape[0]
            lanes = jnp.arange(P, dtype=I32)
            data = (lanes < n) & (kinds == 0)
            rid = regs[RID]
            # per-op counter bumps accumulate locally and flush as one
            # scatter — a handful of 1-element scatters per fire is pure
            # per-tick overhead on CPU
            pend: dict = {}

            def stat_add(st_, key, amount):
                a = jnp.asarray(amount, I32)
                pend[key] = pend[key] + a if key in pend else a

            for op in ctx.body:
                k = op.op
                if k == "const":
                    regs[op.dst] = jnp.full(P, ir.wrap32(op.imm), I32)
                elif k == "mov":
                    regs[op.dst] = regs[op.srcs[0]]
                elif k == "select":
                    c, a, b = (regs[s] for s in op.srcs)
                    regs[op.dst] = jnp.where(c != 0, a, b)
                elif k == "not":
                    regs[op.dst] = (regs[op.srcs[0]] == 0).astype(I32)
                elif k == "neg":
                    regs[op.dst] = -regs[op.srcs[0]]
                elif k in ir.BINOPS:
                    regs[op.dst] = dl.dev_binop(
                        k, regs[op.srcs[0]], regs[op.srcs[1]])
                elif k == "sram_load":
                    mem = st[f"p_{op.space}"]
                    addr = regs[op.srcs[0]] * I32(g.pools[op.space].buf_words) \
                        + regs[op.srcs[1]]
                    ok = data & (addr >= 0) & (addr < mem.shape[0])
                    regs[op.dst] = jnp.where(ok, mem[jnp.where(ok, addr, 0)], 0)
                    stat_add(st, "sram_reads", ok.sum())
                elif k == "sram_store":
                    mem = st[f"p_{op.space}"]
                    addr = regs[op.srcs[0]] * I32(g.pools[op.space].buf_words) \
                        + regs[op.srcs[1]]
                    ok = data & (addr >= 0) & (addr < mem.shape[0])
                    if op.pred is not None:
                        ok &= regs[op.pred] != 0
                    okl = last_wins(ok, addr)
                    st[f"p_{op.space}"] = mem.at[
                        jnp.where(okl, addr, mem.shape[0])].set(
                        regs[op.srcs[2]], mode="drop")
                    stat_add(st, "sram_writes", ok.sum())
                elif k == "dram_load":
                    a = st[f"d_{op.space}"]
                    lim = self._dram_lim[op.space]
                    addr = regs[op.srcs[0]]
                    ok = data & (addr >= 0) & (addr < lim)
                    # a shared array is laid out once: no per-request base
                    if batched and op.space not in self.shared:
                        addr = addr + rid * I32(lim)
                    regs[op.dst] = jnp.where(ok, a[jnp.where(ok, addr, 0)], 0)
                    stat_add(st, "dram_reads", ok.sum())
                elif k == "dram_store":
                    a = st[f"d_{op.space}"]
                    lim = self._dram_lim[op.space]
                    addr = regs[op.srcs[0]]
                    ok = data & (addr >= 0) & (addr < lim)
                    if batched:
                        addr = addr + rid * I32(lim)
                    if op.pred is not None:
                        ok &= regs[op.pred] != 0
                    val = regs[op.srcs[1]]
                    m = self._dram_mask[op.space]
                    if m is not None:
                        val = val & m
                    okl = last_wins(ok, addr)
                    st[f"d_{op.space}"] = a.at[
                        jnp.where(okl, addr, a.shape[0])].set(val, mode="drop")
                    stat_add(st, "dram_writes", ok.sum())
                elif k == "atomic_add":
                    a = st[f"d_{op.space}"]
                    lim = self._dram_lim[op.space]
                    addr = regs[op.srcs[0]]
                    ok = data & (addr >= 0) & (addr < lim)
                    if batched:
                        addr = addr + rid * I32(lim)
                    a2, old = dl.atomic_add_window(
                        a, jnp.where(ok, addr, 0), regs[op.srcs[1]], ok, lanes)
                    st[f"d_{op.space}"] = a2
                    regs[op.dst] = old
                    stat_add(st, "atomics", ok.sum())
                elif k == "alloc":
                    pi = self.pool_row[op.space]
                    ring = st[f"fr_{op.space}"]
                    flcap = ring.shape[0]
                    lane_idx = jnp.cumsum(data.astype(I32)) - 1
                    ptr = ring[(st["fh"][pi] + lane_idx) & (flcap - 1)]
                    regs[op.dst] = jnp.where(data, ptr, 0)
                    need = data.sum().astype(I32)
                    st["fh"] = st["fh"].at[pi].add(need)
                    stat_add(st, "allocs", need)
                elif k == "free":
                    pi = self.pool_row[op.space]
                    ring = st[f"fr_{op.space}"]
                    flcap = ring.shape[0]
                    lane_idx = jnp.cumsum(data.astype(I32)) - 1
                    pos = (st["ft"][pi] + lane_idx) & (flcap - 1)
                    st[f"fr_{op.space}"] = ring.at[
                        jnp.where(data, pos, flcap)].set(
                        regs[op.srcs[0]], mode="drop")
                    cnt = data.sum().astype(I32)
                    st["ft"] = st["ft"].at[pi].add(cnt)
                    stat_add(st, "frees", cnt)
                else:
                    raise NotImplementedError(k)
            if ctx.body:
                stat_add(st, "body_ops",
                         data.sum().astype(I32) * len(ctx.body))
            if pend:
                rows = jnp.asarray([self._stat_row[k] for k in pend], I32)
                st["stats"] = st["stats"].at[rows].add(
                    jnp.stack(list(pend.values())))
            return regs

        LANES = jnp.arange(W, dtype=I32)

        def rget(regs, v, P):
            # protocol (barrier-only) windows route without running the
            # body, so body-computed value names are absent; barrier lanes
            # never read payload, zeros suffice (host pushes zeros too)
            r = regs.get(v)
            return r if r is not None else jnp.zeros(P, I32)

        def route_window(st, ctx, kinds, regs, n):
            # every window a context fires passes here once: count its
            # data and barrier lanes (occupancy = lanes / (ticks * W))
            st["lanes"] = st["lanes"] + n
            P = kinds.shape[0]
            lanes = jnp.arange(P, dtype=I32)
            valid = lanes < n
            data = valid & (kinds == 0)
            rid = regs[RID]
            for oi, o in enumerate(ctx.outs):
                nv = len(g.links[o.link].vars) + 1
                if o.kind == "reduce":
                    ri = self.red_row[(ctx.id, oi)]
                    vals = regs.get(o.values[0]) if o.values else None
                    ok_, ov, orid, cnt, nacc, nopen = dl.segment_reduce_window(
                        kinds, vals, rid, n, o.reduce_op,
                        ir.wrap32(o.reduce_init), st["red_acc"][ri],
                        st["red_open"][ri])
                    st["red_acc"] = st["red_acc"].at[ri].set(nacc)
                    st["red_open"] = st["red_open"].at[ri].set(nopen)
                    cols = ([ov] if nv > 1 else []) + [orid]
                    push(st, o.link, ok_, cols, cnt)
                    continue
                cols = [rget(regs, v, P) for v in o.values] + [rid]
                while len(cols) < nv:       # valueless outs: zero payload
                    cols.insert(0, jnp.zeros(P, I32))
                if o.kind == "pass" and not o.lower_barrier:
                    # pass-through: lanes [0, n) are already contiguous, so
                    # the compaction scatter is a no-op — push directly
                    push(st, o.link, kinds, cols, n)
                    continue
                if o.kind == "discard":
                    keep = valid & ~data
                elif o.kind == "filter":
                    keep = valid & (~data | (rget(regs, o.pred, P) != 0))
                else:
                    keep = valid
                out_kinds = kinds
                if o.lower_barrier:
                    keep = keep & (kinds != 1)
                    out_kinds = jnp.where(kinds > 1, kinds - 1, kinds)
                kb, vb, cnt = dl.window_compact(keep, out_kinds, cols)
                push(st, o.link, kb, vb, cnt)

        def empty_regs1(vars_, rid):
            regs = {v: jnp.zeros(1, I32) for v in vars_}
            regs[RID] = jnp.reshape(rid, (1,)).astype(I32)
            return regs

        # ------------------------------------------------- head fire bodies
        # Each mirrors the host ``_fire_*`` exactly, except that decisions
        # are masked scalars and a bounded slice of the host's per-fire
        # while-loop runs per tick (window partitioning may differ; the
        # token sequence per link cannot — DESIGN.md §9).

        def fire_window(st, ctx, lid, vars_, rdy):
            kk, vk, ridx, cap = ring_of(lid)
            r = room(st, ctx)
            gate = rdy & (r > 0)
            budget = jnp.where(gate, jnp.clip(r // room_div[ctx.id], 0, W), 0)
            n = jnp.minimum(budget, qlen(st, ridx))
            kinds, vals = dl.ring_peek(st[kk], st[vk], st["qh"][ridx], cap, W)
            n = alloc_limit(st, ctx, kinds, n)
            regs = {v: vals[i] for i, v in enumerate(vars_)}
            regs[RID] = vals[-1]
            regs = exec_body(st, ctx, kinds, regs, n)
            route_window(st, ctx, kinds, regs, n)
            st["qh"] = st["qh"].at[ridx].add(n)
            return n > 0

        def fire_zip(st, ctx, h, rdy):
            r = room(st, ctx)
            gate = rdy & (r > 0)
            budget = jnp.where(gate, jnp.clip(r // room_div[ctx.id], 0, W), 0)
            peeks = [peek(st, l, W) for l in h.links]
            n = budget
            for _, _, ln in peeks:
                n = jnp.minimum(n, ln)
            ref = peeks[0][0]
            mism = jnp.zeros(W, bool)
            for ko, _, _ in peeks[1:]:
                mism |= ko != ref
            mism &= LANES < n
            L = dl.first_index(mism, n)
            bad = gate & (n > 0) & (L == 0)
            st["err"] = jnp.where(bad & (st["err"] == 0),
                                  _ERR_ZIP + ctx.id, st["err"])
            L = alloc_limit(st, ctx, ref, L)
            regs = {}
            for (ko, vo, _), l in zip(peeks, h.links):
                for i, v in enumerate(g.links[l].vars):
                    regs[v] = vo[i]
            regs[RID] = peeks[0][1][-1]
            regs = exec_body(st, ctx, ref, regs, L)
            route_window(st, ctx, ref, regs, L)
            for l in h.links:
                pop(st, l, L)
            return L > 0

        def fire_merge(st, ctx, h, rdy):
            nv = len(g.links[h.a].vars) + 1
            r = room(st, ctx)
            gate = rdy & (r > 0)
            # an alloc stall in a merge fails the launch, so an allocating
            # merge keeps VLEN-lane windows, as the replicated VM's does:
            # wider ones would raise the pool size a program that completes
            # on one request needs
            mw = min(VLEN, W) if self._ctx_alloc_pools[ctx.id] else W
            budget = jnp.where(gate, jnp.clip(r // room_div[ctx.id], 0, mw),
                               0)
            fired = jnp.asarray(False)
            # two greedy sub-steps per tick: a-run, else b-run, else the
            # leading equal-barrier-pair run (host assembles these into one
            # window per fire; the emitted token sequence is identical)
            for _ in range(2):
                ka, va, la = peek(st, h.a, W)
                kb, vb, lb = peek(st, h.b, W)
                ca = jnp.minimum(la, budget)
                cb = jnp.minimum(lb, budget)
                ra = dl.leading_run(ka == 0, ca)
                rb = dl.leading_run(kb == 0, cb)
                pair = (ka > 0) & (ka == kb)
                npair = dl.leading_run(pair, jnp.minimum(ca, cb))
                mismatch = (budget > 0) & (ra == 0) & (rb == 0) & \
                    (npair == 0) & (la > 0) & (lb > 0)
                st["err"] = jnp.where(mismatch & (st["err"] == 0),
                                      _ERR_MERGE + ctx.id, st["err"])
                take_a = ra > 0
                take_b = ~take_a & (rb > 0)
                take_p = ~take_a & ~take_b & (npair > 0)
                n = jnp.where(take_a, ra,
                              jnp.where(take_b, rb,
                                        jnp.where(take_p, npair, 0)))
                kinds = jnp.where(take_b, kb, ka)
                # pair barriers keep only their request id
                zero = jnp.zeros(W, I32)
                vsel = [jnp.where(take_p, zero if i < nv - 1 else a_,
                                  jnp.where(take_b, b_, a_))
                        for i, (a_, b_) in enumerate(zip(va, vb))]
                nl = alloc_limit(st, ctx, kinds, n)
                astall = nl < n
                st["err"] = jnp.where(astall & (st["err"] == 0),
                                      _ERR_MERGE_ALLOC + ctx.id, st["err"])
                n = jnp.where(astall, 0, n)
                regs = {v: vsel[i]
                        for i, v in enumerate(g.links[h.a].vars)}
                regs[RID] = vsel[-1]
                regs = exec_body(st, ctx, kinds, regs, n)
                route_window(st, ctx, kinds, regs, n)
                pop(st, h.a, jnp.where(take_a | take_p, n, 0))
                pop(st, h.b, jnp.where(take_b | take_p, n, 0))
                budget = budget - n
                fired = fired | (n > 0)
            return fired

        def fire_counter_vec(st, ctx, h, rdy):
            """Counter without allocations: carried-expansion prefix plus a
            vectorized multi-row intake (the replicated host path's window
            assembly, as one gather)."""
            ci = self.cnt_row[ctx.id]
            vars_in = g.links[h.link].vars
            lo_i = vars_in.index(h.lo)
            hi_i = vars_in.index(h.hi)
            st_i = vars_in.index(h.step)
            add_i = 1 if h.add_level else 0
            r = room(st, ctx)
            gate = rdy & (r > 0)
            budget = jnp.where(gate, jnp.clip(r // room_div[ctx.id], 0, W), 0)
            act = st["cnt_act"][ci]
            cur = st["cnt_cur"][ci]
            hi = st["cnt_hi"][ci]
            step = st["cnt_step"][ci]
            base = st[f"cb_{ctx.id}"]
            # carried expansion first (host emission order)
            rem = jnp.where(act & (step > 0),
                            jnp.maximum(-((cur - hi) // jnp.where(
                                step == 0, 1, step)), 0), 0)
            c_emit = jnp.minimum(rem, budget)
            # the close barrier occupies a lane of its own: when the final
            # expansion chunk exactly fills the budget (rem == budget == W)
            # the counter must stay active one more tick to emit it
            c_complete = gate & act & (c_emit == rem) & \
                (c_emit + add_i <= budget)
            c_close = c_complete & (add_i == 1)
            prefix = c_emit + c_close.astype(I32)
            # whole-row intake: take every queue row whose full emission
            # (expansion + close, or 1 for a pass-through barrier) fits
            can_intake = gate & (~act | c_complete)
            kin, vin, lin = peek(st, h.link, W)
            in_valid = LANES < jnp.minimum(lin, W)
            is_d = in_valid & (kin == 0)
            lo_v = vin[lo_i]
            hi_v = vin[hi_i]
            sp_v = jnp.where(vin[st_i] == 0, 1, vin[st_i])
            e_i = jnp.where(is_d & (sp_v > 0),
                            jnp.maximum(-((lo_v - hi_v) // sp_v), 0), 0)
            sz = jnp.where(is_d, e_i + add_i, jnp.where(in_valid, 1, 0))
            csz = jnp.cumsum(sz)
            ibudget = jnp.where(can_intake, jnp.maximum(budget - prefix, 0), 0)
            fit = in_valid & (csz <= ibudget)
            rows_taken = fit.sum().astype(I32)
            total_in = jnp.where(
                rows_taken > 0, csz[jnp.clip(rows_taken - 1, 0, W - 1)], 0)
            # oversized data row (expansion wider than the window): load it
            # as the carried state without emitting — it streams out over
            # the following ticks exactly like the host's budget loop
            load_big = can_intake & (rows_taken == 0) & (lin > 0) & \
                (kin[0] == 0) & (prefix == 0)
            new_act = jnp.where(load_big, True, act & ~c_complete)
            new_cur = jnp.where(load_big, lo_v[0], cur + step * c_emit)
            new_hi = jnp.where(load_big, hi_v[0], hi)
            new_step = jnp.where(load_big, sp_v[0], step)
            new_base = jnp.where(load_big, jnp.stack([c[0] for c in vin]),
                                 base)
            pop_n = jnp.where(load_big, 1, rows_taken)
            # assemble the output window: carried prefix, then intake rows
            n_win = prefix + total_in
            k_car = jnp.where(LANES < c_emit, 0,
                              jnp.where((LANES == c_emit) & c_close, 1, 0))
            iv_car = cur + step * LANES
            j2 = LANES - prefix
            rowi = jnp.clip(jnp.searchsorted(csz, j2, side="right"), 0, W - 1)
            start = csz[rowi] - sz[rowi]
            off = j2 - start
            row_d = kin[rowi] == 0
            k_int = jnp.where(row_d, jnp.where(off < e_i[rowi], 0, 1),
                              kin[rowi] + add_i)
            iv_int = lo_v[rowi] + sp_v[rowi] * off
            use_car = LANES < prefix
            kinds = jnp.where(use_car, k_car, k_int)
            ivar = jnp.where(use_car, iv_car, iv_int)
            pl = [jnp.where(use_car, base[i], c[rowi])
                  for i, c in enumerate(vin)]
            regs = {v: pl[i] for i, v in enumerate(vars_in)}
            regs[h.ivar] = ivar
            regs[RID] = pl[-1]
            regs = exec_body(st, ctx, kinds, regs, n_win)
            route_window(st, ctx, kinds, regs, n_win)
            pop(st, h.link, pop_n)
            st["cnt_act"] = st["cnt_act"].at[ci].set(new_act)
            st["cnt_cur"] = st["cnt_cur"].at[ci].set(new_cur)
            st["cnt_hi"] = st["cnt_hi"].at[ci].set(new_hi)
            st["cnt_step"] = st["cnt_step"].at[ci].set(new_step)
            st[f"cb_{ctx.id}"] = new_base
            return (n_win > 0) | (pop_n > 0)

        def fire_counter_alloc(st, ctx, h, rdy):
            """Allocating counter: one input token + one alloc-limited
            expansion chunk per tick (the host's serial budget loop,
            narrowed to a bounded slice)."""
            ci = self.cnt_row[ctx.id]
            vars_in = g.links[h.link].vars
            lo_i = vars_in.index(h.lo)
            hi_i = vars_in.index(h.hi)
            st_i = vars_in.index(h.step)
            add_i = 1 if h.add_level else 0
            r = room(st, ctx)
            gate = rdy & (r > 0)
            budget = jnp.where(gate, jnp.clip(r // room_div[ctx.id], 0, W), 0)
            act = st["cnt_act"][ci]
            cur = st["cnt_cur"][ci]
            hi = st["cnt_hi"][ci]
            step = st["cnt_step"][ci]
            base = st[f"cb_{ctx.id}"]
            kin, vin, lin = peek(st, h.link, 1)
            have = gate & ~act & (lin > 0)
            tok_data = have & (kin[0] == 0)
            tok_bar = have & (kin[0] > 0)
            # pass-through barrier: 1-lane route, no body
            route_window(st, ctx, jnp.reshape(kin[0] + add_i, (1,)),
                         empty_regs1(list(vars_in) + [h.ivar], vin[-1][0]),
                         jnp.where(tok_bar, 1, 0))
            act2 = act | tok_data
            cur2 = jnp.where(tok_data, vin[lo_i][0], cur)
            hi2 = jnp.where(tok_data, vin[hi_i][0], hi)
            sraw = vin[st_i][0]
            step2 = jnp.where(tok_data, jnp.where(sraw == 0, 1, sraw), step)
            base2 = jnp.where(tok_data, jnp.stack([c[0] for c in vin]), base)
            pop(st, h.link, jnp.where(tok_data | tok_bar, 1, 0))
            rem = jnp.where(act2 & (step2 > 0) & gate,
                            jnp.maximum(-((cur2 - hi2) // jnp.where(
                                step2 == 0, 1, step2)), 0), 0)
            emit_try = jnp.minimum(rem, budget)
            emit = alloc_limit(st, ctx, jnp.zeros(W, I32), emit_try)
            blocked = (emit_try > 0) & (emit == 0)
            cur3 = cur2 + step2 * emit
            # as in fire_counter_vec: the close barrier needs its own lane,
            # so a chunk that exactly fills the budget defers completion
            complete = gate & act2 & ~blocked & \
                ((cur3 >= hi2) | (step2 <= 0)) & (emit + add_i <= budget)
            close = complete & (add_i == 1)
            n_win = emit + close.astype(I32)
            kinds = jnp.where(LANES < emit, 0,
                              jnp.where((LANES == emit) & close, 1, 0))
            regs = {v: jnp.broadcast_to(base2[i], (W,))
                    for i, v in enumerate(vars_in)}
            regs[h.ivar] = cur2 + step2 * LANES
            regs[RID] = jnp.broadcast_to(base2[-1], (W,))
            regs = exec_body(st, ctx, kinds, regs, n_win)
            route_window(st, ctx, kinds, regs, n_win)
            st["cnt_act"] = st["cnt_act"].at[ci].set(act2 & ~complete)
            st["cnt_cur"] = st["cnt_cur"].at[ci].set(cur3)
            st["cnt_hi"] = st["cnt_hi"].at[ci].set(hi2)
            st["cnt_step"] = st["cnt_step"].at[ci].set(step2)
            st[f"cb_{ctx.id}"] = base2
            return tok_data | tok_bar | (n_win > 0)

        def fire_fwdbwd(st, ctx, h, rdy):
            cid = ctx.id
            fi = self.fb_row[cid]
            vars_f = g.links[h.fwd].vars
            r = room(st, ctx)
            gate = rdy & (r > 0)
            budget0 = jnp.where(gate, jnp.clip(r // room_div[cid], 0, W), 0)
            mode = st[f"fb_mode_{cid}"]
            pend = st[f"fb_pend_{cid}"]
            got = st[f"fb_got_{cid}"]
            seq = st[f"fb_seq_{cid}"]
            BIG = jnp.int32(1 << 30)
            # -- ordered release: oldest non-echo session, if it is waiting
            sess = (mode == 1) | (mode == 2)
            rid_old = jnp.argmin(jnp.where(sess, seq, BIG)).astype(I32)
            can_rel = gate & (budget0 > 0) & sess.any() & \
                (mode[rid_old] == 2)
            route_window(st, ctx, jnp.reshape(pend[rid_old] + 1, (1,)),
                         empty_regs1(vars_f, rid_old),
                         jnp.where(can_rel, 1, 0))
            mode = mode.at[rid_old].set(jnp.where(can_rel, 3, mode[rid_old]))
            # every lane routed below comes out of the budget, as the host's
            # running ``budget -= ...`` does: the release's lane first
            budget = budget0 - jnp.where(can_rel, 1, 0)
            # -- backedge: leading data run, then one head barrier
            kb, vb, lb = peek(st, h.back, W)
            brun = dl.leading_run(kb == 0, jnp.minimum(lb, budget))
            bn = alloc_limit(st, ctx, kb, brun)
            regsb = {v: vb[i] for i, v in enumerate(vars_f)}
            regsb[RID] = vb[-1]
            regsb = exec_body(st, ctx, kb, regsb, bn)
            route_window(st, ctx, kb, regsb, bn)
            wrids = jnp.clip(vb[-1], 0, nreq - 1)
            wmask = (LANES < bn) & (mode[wrids] > 0)
            got = got.at[jnp.where(wmask, wrids, nreq)].set(True, mode="drop")
            hb = gate & (budget > 0) & (brun == 0) & (lb > 0) & (kb[0] > 0)
            lvl = kb[0]
            brid = jnp.clip(vb[-1][0], 0, nreq - 1)
            m_r = mode[brid]
            bad = hb & ((m_r == 0) | (m_r == 2) |
                        ((m_r == 1) & (lvl != 1)) |
                        ((m_r == 3) & (lvl != pend[brid] + 1)))
            st["err"] = jnp.where(bad & (st["err"] == 0),
                                  _ERR_FB + cid, st["err"])
            d_case = hb & (m_r == 1) & (lvl == 1)
            e_case = hb & (m_r == 3) & (lvl == pend[brid] + 1)
            emit_wave = d_case & got[brid]
            route_window(st, ctx, jnp.ones(1, I32),
                         empty_regs1(vars_f, brid),
                         jnp.where(emit_wave, 1, 0))
            got = got.at[brid].set(jnp.where(emit_wave, False, got[brid]))
            mode = mode.at[brid].set(
                jnp.where(d_case & ~emit_wave, 2,
                          jnp.where(e_case, 0, mode[brid])))
            pop_b = bn + jnp.where(d_case | e_case, 1, 0)
            budget = budget - bn - jnp.where(emit_wave, 1, 0)
            pop(st, h.back, pop_b)
            # -- forward intake only once the backedge is drained (or its
            # run is alloc-stalled) — host drains qb before touching qf
            back_stalled = (brun > 0) & (bn == 0)
            allow_fwd = gate & (((lb - pop_b) == 0) | back_stalled)
            fbudget = jnp.clip(budget0 - bn - 3, 0, W)
            kf, vf, lf = peek(st, h.fwd, W)
            frun = dl.leading_run(kf == 0, jnp.minimum(lf, fbudget))
            frun = jnp.where(allow_fwd, frun, 0)
            frids = jnp.clip(vf[-1], 0, nreq - 1)
            if self.parallel_loops:
                fblocked = (mode[frids] > 0) & (LANES < frun)
                admit = dl.first_index(fblocked, frun)
            else:
                admit = jnp.where((mode > 0).any(), 0, frun)
            fn = alloc_limit(st, ctx, kf, admit)
            regsf = {v: vf[i] for i, v in enumerate(vars_f)}
            regsf[RID] = vf[-1]
            regsf = exec_body(st, ctx, kf, regsf, fn)
            route_window(st, ctx, kf, regsf, fn)
            # -- group barrier: open a session (serial: only when idle)
            ob = allow_fwd & (budget > 0) & (frun == 0) & (fn == 0) & \
                (lf > 0) & (kf[0] > 0)
            frid0 = frids[0]
            if self.parallel_loops:
                can_open = ob & (mode[frid0] == 0)
            else:
                can_open = ob & ~(mode > 0).any()
            route_window(st, ctx, jnp.ones(1, I32),
                         empty_regs1(vars_f, frid0),
                         jnp.where(can_open, 1, 0))
            nseq = st["fb_nseq"][fi]
            mode = mode.at[frid0].set(jnp.where(can_open, 1, mode[frid0]))
            pend = pend.at[frid0].set(jnp.where(can_open, kf[0], pend[frid0]))
            got = got.at[frid0].set(jnp.where(can_open, False, got[frid0]))
            seq = seq.at[frid0].set(jnp.where(can_open, nseq, seq[frid0]))
            st["fb_nseq"] = st["fb_nseq"].at[fi].add(
                jnp.where(can_open, 1, 0))
            pop(st, h.fwd, fn + jnp.where(can_open, 1, 0))
            st[f"fb_mode_{cid}"] = mode
            st[f"fb_pend_{cid}"] = pend
            st[f"fb_got_{cid}"] = got
            st[f"fb_seq_{cid}"] = seq
            return can_rel | (bn > 0) | d_case | e_case | (fn > 0) | can_open

        # --------------------------------------------------------- the tick
        def ready_of(st0):
            """Tick-start ready snapshot — the device form of the host
            scheduler's ``_ready`` over a frozen head/tail vector."""
            lens0 = st0["qt"] - st0["qh"]
            out = {}
            for ctx in self.order:
                rm = jnp.asarray(True)
                for o in ctx.outs:
                    rm &= (caps[o.link] - lens0[row_of[o.link]]) > 0
                h = ctx.head
                if isinstance(h, SourceHead):
                    c = lens0[self.src_row] > 0
                elif isinstance(h, SingleHead):
                    c = lens0[row_of[h.link]] > 0
                elif isinstance(h, ZipHead):
                    c = jnp.asarray(True)
                    for l in h.links:
                        c &= lens0[row_of[l]] > 0
                elif isinstance(h, ForwardMergeHead):
                    c = (lens0[row_of[h.a]] > 0) | (lens0[row_of[h.b]] > 0)
                elif isinstance(h, FwdBwdMergeHead):
                    c = (lens0[row_of[h.fwd]] > 0) | \
                        (lens0[row_of[h.back]] > 0) | \
                        (st0[f"fb_mode_{ctx.id}"] == 2).any()
                elif isinstance(h, CounterHead):
                    c = st0["cnt_act"][self.cnt_row[ctx.id]] | \
                        (lens0[row_of[h.link]] > 0)
                else:
                    raise TypeError(type(h))
                out[ctx.id] = rm & c
            return out

        def fire_ctx(st, ctx, f):
            h = ctx.head
            if isinstance(h, SourceHead):
                return fire_window(st, ctx, "S", self.source_vars, f)
            elif isinstance(h, SingleHead):
                return fire_window(st, ctx, h.link, g.links[h.link].vars, f)
            elif isinstance(h, ZipHead):
                return fire_zip(st, ctx, h, f)
            elif isinstance(h, ForwardMergeHead):
                return fire_merge(st, ctx, h, f)
            elif isinstance(h, FwdBwdMergeHead):
                return fire_fwdbwd(st, ctx, h, f)
            elif isinstance(h, CounterHead):
                if self._ctx_alloc_pools[ctx.id]:
                    return fire_counter_alloc(st, ctx, h, f)
                return fire_counter_vec(st, ctx, h, f)
            raise TypeError(type(h))

        class _Track(dict):
            """Trace-time probe: records which state keys a fire path reads
            and writes, so each context's lax.cond only round-trips the
            entries it can touch."""
            def __init__(self, base):
                super().__init__(base)
                self.wrote: set = set()

            def __setitem__(self, k, v):
                self.wrote.add(k)
                super().__setitem__(k, v)

        TRUE = jnp.ones((), bool)

        def write_set(ctx, st):
            """Abstract probe run of ``fire_ctx`` (no equations added to the
            enclosing jaxpr) to learn the context's written state keys."""
            shapes = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), st)
            wrote: set = set()

            def probe(s):
                tr = _Track(s)
                f = fire_ctx(tr, ctx, TRUE)
                tr["prog"] = tr["prog"] | f
                wrote.update(tr.wrote)
                return {k: tr[k] for k in tr.wrote}

            jax.eval_shape(probe, shapes)
            return sorted(wrote)

        def tick(st):
            # Every fire path is a value-level no-op when its ready flag is
            # false (complete rdy-masking is what the bit-identity matrix
            # pins), so non-ready contexts are skipped outright: one
            # lax.cond per context keeps the per-tick cost proportional to
            # the firing wavefront, not the whole graph.  Each cond carries
            # only the keys its context writes — read-only state (DRAM
            # images, other rings) is closed over, never copied through.
            # The named scopes reach the HLO's op_name metadata, so a
            # device trace attributes loop time to each context; context
            # names repeat ("foreach", "if.then"), the id keeps them apart.
            st = dict(st)
            with jax.named_scope("revet.ready"):
                rdy = ready_of(st)
            st["prog"] = jnp.zeros((), bool)
            for ctx in self.order:
                wkeys = write_set(ctx, st)
                sub = {k: st[k] for k in wkeys}

                def taken(sub, ctx=ctx, wkeys=wkeys, base=dict(st)):
                    s = dict(base)
                    s.update(sub)
                    # rdy is known True inside the branch: constant gate
                    f = fire_ctx(s, ctx, TRUE)
                    s["prog"] = s["prog"] | f
                    return {k: s[k] for k in wkeys}

                with jax.named_scope(f"revet.ctx.{ctx.name}.{ctx.id}"):
                    st.update(jax.lax.cond(rdy[ctx.id], taken,
                                           lambda s: dict(s), sub))
            st["tick"] = st["tick"] + 1
            stat_add(st, "ticks", 1)
            return st

        def cond(st):
            return st["prog"] & (st["err"] == 0) & \
                (st["tick"] < self.max_ticks)

        def run(st, shared):
            # the shared arrays are operands outside the carry: the loop
            # reads them like any DRAM array, never copies or returns them
            def body(st):
                with jax.named_scope("revet.loop"):
                    out = tick({**st, **shared})
                return {k: out[k] for k in st}

            return jax.lax.while_loop(cond, body, st)

        # the function's name fixes the XLA module's, ``jit_run``: the
        # benchmark's loop_ms reads that module from the device trace.
        # The carry is donated: _init_state builds it afresh for every
        # launch, so the loop's outputs reuse its buffers on the device.
        self._jit_run = jax.jit(run, donate_argnums=0)
        self._tick = tick           # uncompiled tick body, for diagnostics

    # ----------------------------------------------------------- host driver
    def run(self, dram_init=None, **params) -> "DeviceRun":
        return self.run_batch([params], dram_init)

    def run_batch(self, params_list: list[dict],
                  dram_init=None) -> "DeviceRun":
        """One launch: init state, run the jitted while-loop to quiescence,
        decode errors, unpack DRAM + stats.  Each stage is a profiler span
        (DESIGN.md §10, "Tracing a serving process")."""
        import jax
        from jax.profiler import TraceAnnotation
        if self._jit_run is None:
            self._build()
        with TraceAnnotation("revet.launch.upload") as span:
            st, shared, up = self._init_state(dram_init, params_list)
            span.set_metadata(shared=len(self.shared),
                              reused=int(bool(self.shared) and not up.fresh),
                              upload_mib=up.sent / 2 ** 20,
                              compared_mib=up.compared / 2 ** 20)
        with TraceAnnotation("revet.launch.loop", width=self.vlen):
            out = jax.block_until_ready(self._jit_run(st, shared))
        with TraceAnnotation("revet.launch.readback"):
            return self._finish(out, up)

    def _finish(self, out, up: _Upload) -> "DeviceRun":
        err = int(out["err"])
        if err:
            self._raise_err(err)
        if int(out["tick"]) >= self.max_ticks and bool(out["prog"]):
            raise VectorDeadlock("tick limit exceeded")
        lens = np.asarray(out["qt"]) - np.asarray(out["qh"])
        stuck = {lid: int(lens[self.row_of[lid]]) for lid in self.lids
                 if lens[self.row_of[lid]]
                 and self.g.contexts[self.g.links[lid].dst].outs}
        if stuck:
            raise VectorDeadlock(
                f"quiescent with tokens in flight: {stuck}")
        dram = {name: (up.arrays[name].view if name in self.shared else
                       np.asarray(out[f"d_{name}"]).astype(np.int64))
                for name in self.g.dram}
        stats = collections.Counter()
        sv = np.asarray(out["stats"])
        for k, i in self._stat_row.items():
            if sv[i]:
                stats[k] = int(sv[i])
        stats["width"] = self.vlen
        stats["lanes_fired"] = int(out["lanes"])
        if self.shared:
            stats["shared_uploads" if up.fresh else "shared_reuses"] = 1
        lt = np.asarray(out["lt"])
        for lid in self.lids:
            if lt[self.row_of[lid]]:
                stats["link_tokens", lid] = int(lt[self.row_of[lid]])
        return DeviceRun(dram=dram, stats=stats,
                         n_requests=self.n_requests,
                         dram_lim=dict(self._dram_lim),
                         backend=self.backend, shared=self.shared)

    def _raise_err(self, err: int) -> None:
        n_rings = len(self.lids) + 1

        def ctx_name(code):
            return self.g.contexts[err - code].name

        if err >= _ERR_FB:
            raise VectorDeadlock(
                f"{ctx_name(_ERR_FB)}: loop-header protocol violation "
                f"(bad backedge barrier or unknown session)")
        if err >= _ERR_MERGE_ALLOC:
            raise VectorDeadlock(
                f"alloc stall inside merge {ctx_name(_ERR_MERGE_ALLOC)}; "
                f"size the pool above the merge fan-in")
        if err >= _ERR_MERGE:
            raise VectorDeadlock(
                f"merge barrier mismatch in {ctx_name(_ERR_MERGE)}")
        if err >= _ERR_ZIP:
            raise VectorDeadlock(
                f"zip structural mismatch in {ctx_name(_ERR_ZIP)}")
        if 1 <= err <= n_rings:
            row = err - 1
            if row == self.src_row:
                raise QueueOverflow(
                    f"device source queue overflow at capacity "
                    f"{self.src_cap}", capacity=self.src_cap)
            lid = self.lids[row]
            cap = self.caps[lid]
            vars_ = ", ".join(self.g.links[lid].vars)
            raise QueueOverflow(
                f"device queue overflow on link {lid} ({vars_}) at "
                f"capacity {cap}; raise queue_caps= or fall back to "
                f"windowed execution", link=lid, capacity=cap)
        raise VectorDeadlock(f"device loop error code {err}")


class _BackendTag:
    """Minimal stand-in when a DeviceProgram is built outside a backend
    (tests, benchmarks) — reports carry a name either way."""

    def __init__(self, name: str):
        self.name = name


class DeviceRun:
    """Result of one resident launch — the slice of the ``VectorVM`` surface
    the serving/API layers read (DRAM image, stats, per-request views)."""

    launches = 1
    execution = "resident"

    def __init__(self, dram, stats, n_requests, dram_lim, backend=None,
                 shared=frozenset()):
        self.dram = dram
        self.stats = stats
        self.n_requests = n_requests
        self._dram_lim = dram_lim
        self.shared = frozenset(shared)
        self.backend = backend if backend is not None \
            else _BackendTag("jax[resident]")

    def request_dram(self, rid: int) -> dict[str, np.ndarray]:
        """One request's DRAM image: a copy of its slice of each
        per-request array, and the read-only array itself for each shared
        one (every request holds the same values there)."""
        if not 0 <= rid < self.n_requests:
            raise IndexError(f"request id {rid} out of range "
                             f"[0, {self.n_requests})")
        return {name: (self.dram[name] if name in self.shared else
                       self.dram[name][rid * sz: (rid + 1) * sz].copy())
                for name, sz in self._dram_lim.items()}

    def request_stats(self, rid: int) -> collections.Counter:
        """Lane stats for one request.  The device loop keeps only the
        launch-aggregate counters; a single-request launch attributes them
        all to request 0, a batched launch returns an empty Counter (the
        windowed path remains the source of per-request attribution)."""
        if not 0 <= rid < self.n_requests:
            raise IndexError(f"request id {rid} out of range "
                             f"[0, {self.n_requests})")
        if self.n_requests == 1:
            return collections.Counter(
                {k: int(self.stats[k]) for k in LANE_STATS
                 if self.stats.get(k)})
        return collections.Counter()
