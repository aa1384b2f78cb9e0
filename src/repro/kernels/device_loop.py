"""Device-resident tick primitives — the fused-loop analogue of ``ops.py``.

``kernels/ops.py`` exposes *per-window* executor entry points: the host
scheduler calls one jitted kernel per window and pays a host round-trip per
call.  This module is the other half of the bargain: fixed-shape jax
building blocks that are **traceable inside a single ``lax.while_loop``
body**, so the whole superstep schedule compiles into one device program
(``core/device_vm.py``) and one launch runs the graph to quiescence.

Every function here obeys the two rules that make that possible:

* **fixed shapes** — windows are always ``W`` lanes (invalid lanes masked;
  a payload is a tuple of ``(W,)`` registers, one per column),
  queues are fixed-capacity rings indexed modulo a power-of-two, and
  variable-length results come back as ``(buffer, count)`` pairs;
* **no control flow** — fire/stall decisions are masked tensor ops
  (``jnp.where``), never Python branches, so one traced tick body serves
  every machine state.

Values are int32 throughout: the IR's 32-bit wrap discipline is the
*native* overflow behavior, so the ``_w32`` boundary calls of the windowed
path disappear (XLA's int32 add/sub/mul/shl wrap exactly like ``ir.wrap32``).

The SLTF token encoding matches ``core/sltf.py``: kind 0 = data, k>0 = Ω_k.
Ring slots beyond ``tail-head`` hold garbage; every consumer masks by the
valid count.  The hidden request id rides as the last payload column of
every ring, exactly as in the windowed VM (DESIGN.md §7/§9).
"""
from __future__ import annotations

NOTHING = -1     # "no token" slot marker (mirrors kernels/segment_reduce)


def _jnp():
    import jax.numpy as jnp
    return jnp


# ---------------------------------------------------------------------------
# element-wise body ops (int32-native wrap semantics)
# ---------------------------------------------------------------------------

def dev_binop(op: str, a, b):
    """IR binop on int32 lanes. Bit-identical to ``backend._vec_binop``
    (whose int64 intermediates are wrapped to signed 32 at every step —
    int32-native arithmetic lands in the same place)."""
    jnp = _jnp()
    u = lambda x: x.astype(jnp.uint32)
    i = lambda x: x.astype(jnp.int32)
    if op == "add":
        return a + b
    if op == "sub":
        return a - b
    if op == "mul":
        return a * b
    if op == "sdiv":
        q = jnp.abs(a) // jnp.where(b == 0, 1, jnp.abs(b))
        q = jnp.where(b == 0, 0, q)
        return jnp.where((a < 0) != (b < 0), -q, q)
    if op == "udiv":
        q = u(a) // jnp.where(u(b) == 0, 1, u(b))
        return jnp.where(b == 0, 0, i(q))
    if op == "smod":
        r = jnp.abs(a) % jnp.where(b == 0, 1, jnp.abs(b))
        r = jnp.where(b == 0, 0, r)
        return jnp.where(a < 0, -r, r)
    if op == "umod":
        r = u(a) % jnp.where(u(b) == 0, 1, u(b))
        return jnp.where(b == 0, 0, i(r))
    if op == "and":
        return a & b
    if op == "or":
        return a | b
    if op == "xor":
        return a ^ b
    if op == "shl":
        return a << (b & 31)
    if op == "lshr":
        return i(u(a) >> u(b & 31))
    if op == "ashr":
        return a >> (b & 31)
    if op == "eq":
        return (a == b).astype(jnp.int32)
    if op == "ne":
        return (a != b).astype(jnp.int32)
    if op == "slt":
        return (a < b).astype(jnp.int32)
    if op == "sle":
        return (a <= b).astype(jnp.int32)
    if op == "sgt":
        return (a > b).astype(jnp.int32)
    if op == "sge":
        return (a >= b).astype(jnp.int32)
    if op == "ult":
        return (u(a) < u(b)).astype(jnp.int32)
    if op == "ule":
        return (u(a) <= u(b)).astype(jnp.int32)
    if op == "min":
        return jnp.minimum(a, b)
    if op == "max":
        return jnp.maximum(a, b)
    raise NotImplementedError(op)


# reduce ops expressible as a jax scatter mode (the device segment-reduce);
# and/or/xor have no scatter combiner, so programs using them fall back to
# the windowed path (``device_vm.resident_unsupported``)
SCATTER_REDUCE_OPS = ("add", "min", "max")


def _scatter_red(op: str, target, idx, vals):
    if op == "add":
        return target.at[idx].add(vals, mode="drop")
    if op == "min":
        return target.at[idx].min(vals, mode="drop")
    if op == "max":
        return target.at[idx].max(vals, mode="drop")
    raise NotImplementedError(op)


# ---------------------------------------------------------------------------
# fixed-capacity ring queues
# ---------------------------------------------------------------------------
# A ring is kinds:(cap+pad,) and one (cap+pad,) vector per payload column,
# plus absolute head/tail counters kept in a shared (n_links,) vector; cap
# is a power of two so position = counter & (cap-1).  head==tail means
# empty; tail-head is the live length.  Every array of a ring is 1-D, so
# its slots always run along the lanes: a TPU tiles it in 4 B a slot, and
# XLA has no second layout to move it into (a 2-D payload gets relaid out
# with its few columns padded to 128 lanes, 512 B a slot, around the
# dynamic slices).  A window is likewise a (W,) kinds vector and a tuple
# of (W,) registers, one per column.  The trailing ``pad`` slots are
# *scratch*: pushes write one contiguous window at the tail (spilling past
# ``cap`` into the pad) and re-issue the wrapped lanes at the front, which
# stays authoritative; peeks re-read wrapped lanes from the front.
# Contiguous dynamic-slice / dynamic-update-slice windows lower to memcpys
# on XLA CPU, while the modular gather/scatter form costs a bounds-checked
# loop per lane — the dominant per-tick cost of the fused loop.  Crucially,
# every read of the pre-push ring is scheduled before the first update, so
# XLA updates the ring buffers in place instead of copying them per push.

def _window_at(a, start):
    """``a``'s ``a.shape[0]`` lanes from ``start`` of ``a`` laid twice end
    to end, ``start`` clamped to ``[0, a.shape[0]]``: a rotation that
    costs two ops where ``jnp.roll`` traces a dozen."""
    import jax.lax as lax
    n = a.shape[0]
    return lax.dynamic_slice(lax.concatenate([a, a], 0), (start,), (n,),
                             allow_negative_indices=False)


def ring_peek(kinds, cols, head, cap: int, width: int):
    """Slice the front ``width`` slots: the ``(width,)`` kinds and a tuple
    of ``(width,)`` payload registers, one per column (garbage beyond the
    live length — callers mask with their own valid count).  ``width``
    must not exceed the ring's scratch pad, and ``cap >= 2*width`` (the
    capacity pre-check's ``4*width`` floor covers the widest 2W reduce
    window)."""
    jnp = _jnp()
    import jax.lax as lax
    pos = head & (cap - 1)
    lane = jnp.arange(width, dtype=jnp.int32)
    # lanes whose absolute position wraps past cap live at the ring front
    # (the pad is scratch): lane l >= cap - pos reads front slot
    # l - (cap - pos), so the static front window rotated by cap - pos
    # covers them (a rotation, where a gather would index every lane)
    shift = cap - pos
    wrapped = lane >= shift

    # (lax, not jnp: every op here is traced once per column of every
    # ring, and positions are never negative)
    def read(a):
        return lax.select(wrapped, _window_at(a[:width], width - shift),
                          lax.dynamic_slice(a, (pos,), (width,),
                                            allow_negative_indices=False))

    return read(kinds), tuple(read(c) for c in cols)


def ring_push(kinds, cols, tail, used, cap: int, k_buf, v_bufs, count):
    """Write ``count`` front lanes of ``k_buf`` and of each register of
    ``v_bufs`` (``(width,)`` each) at the tail.  Returns ``(kinds, cols,
    overflow)``; on overflow nothing is written (the caller latches an
    error flag and the loop halts, so the ring is never corrupted by a
    wrapped write).

    Two chained dynamic-update-slices per array — the tail window (which
    may spill into the scratch pad) and the front window for wrapped
    lanes — with every read of the pre-push ring scheduled before the
    first update.  XLA then aliases the ring buffer through both updates;
    the earlier mirror-maintenance form read the ring *after* updating
    it, which forced a full-ring copy per push inside the fire branches —
    the dominant per-fire cost on CPU."""
    jnp = _jnp()
    import jax.lax as lax
    width = k_buf.shape[0]
    lane = jnp.arange(width, dtype=jnp.int32)
    over = used + count > cap
    cnt = jnp.where(over, 0, count)
    keep = lane < cnt
    pos = tail & (cap - 1)
    # lanes written past cap landed in the scratch pad; re-issue them at
    # the front, which is authoritative for wrapped positions.  A wrap
    # implies pos >= cap - width, so with cap >= 2*width the front window
    # is disjoint from the tail window; front lane j takes pushed lane
    # j + cap - pos (the buffer rotated back by cap - pos).  When nothing
    # wrapped this rewrites the front unchanged (a[:width] reads the
    # post-update ring, so a pos==0 overlap also round-trips correctly).
    shift = cap - pos
    wr = (lane + shift) < cnt

    def write(a, buf):
        old = lax.dynamic_slice(a, (pos,), (width,),
                                allow_negative_indices=False)
        a = lax.dynamic_update_slice(a, lax.select(keep, buf, old), (pos,),
                                     allow_negative_indices=False)
        front = lax.select(wr, _window_at(buf, shift), a[:width])
        return lax.dynamic_update_slice(a, front, (0,))

    return (write(kinds, k_buf),
            tuple(write(c, b) for c, b in zip(cols, v_bufs)), over)


# ---------------------------------------------------------------------------
# window-level helpers
# ---------------------------------------------------------------------------

def window_compact(keep, k_in, v_in):
    """Stream compaction of a ``(W,)`` kinds window and its payload
    registers (a sequence of ``(W,)`` vectors): surviving lanes pack to the
    front, ``count`` reports how many; lanes past ``count`` are garbage
    (every consumer masks by the count). ``keep`` already folds validity.

    Formulated as one stable sort keyed by the dropped flag that carries
    every register with it, rather than a cumsum-indexed scatter (XLA CPU
    lowers the scatter to a bounds-checked per-row loop, ~10x the cost) or
    an argsort and a gather per register: compaction is on the per-fire
    critical path of the fused loop."""
    jnp = _jnp()
    import jax.lax as lax
    out = lax.sort(((~keep).astype(jnp.int32), k_in, *v_in),
                   is_stable=True, num_keys=1)
    return out[1], tuple(out[2:]), keep.sum().astype(jnp.int32)


def leading_run(mask, n):
    """Length of the leading True-run of ``mask`` within the first ``n``
    lanes (= ``backend.data_run`` when mask = kinds==0)."""
    jnp = _jnp()
    lane = jnp.arange(mask.shape[0], dtype=jnp.int32)
    stop = (~mask) & (lane < n)
    return jnp.where(stop.any(), jnp.argmax(stop).astype(jnp.int32),
                     n.astype(jnp.int32) if hasattr(n, "astype")
                     else jnp.int32(n))


def first_index(mask, default):
    """Index of the first True lane, else ``default``."""
    jnp = _jnp()
    return jnp.where(mask.any(), jnp.argmax(mask).astype(jnp.int32), default)


def segment_reduce_window(kinds, vals, rids, n, op: str, init: int,
                          acc, group_open):
    """One reduce-output window as fixed-shape tensor ops — the fused-loop
    form of ``backend.segment_reduce_window_np`` (bit-identical emissions).

    ``kinds/vals/rids`` are ``(W,)`` with ``n`` valid lanes; returns
    ``(out_kinds, out_vals, out_rids, count, acc', group_open')`` where the
    out buffers are ``(2W,)`` — two emission slots per input barrier: the
    data token carrying the accumulator, then the lowered barrier Ω(n-1).
    """
    jnp = _jnp()
    W = kinds.shape[0]
    lane = jnp.arange(W, dtype=jnp.int32)
    valid = lane < n
    is_bar = (kinds > 0) & valid
    is_data = (kinds == 0) & valid
    # segment id per position: barrier j closes segment j (W+1 segments max)
    seg = jnp.cumsum(is_bar.astype(jnp.int32)) - is_bar
    nbar = is_bar.sum().astype(jnp.int32)
    # per-segment data count -> open flag
    cnt = jnp.zeros(W + 1, jnp.int32).at[
        jnp.where(is_data, seg, W + 1)].add(1, mode="drop")
    open_ = cnt > 0
    open_ = open_.at[0].set(open_[0] | group_open)
    # barrier-slot arrays: slot j = j-th barrier of the window
    bslot = jnp.cumsum(is_bar.astype(jnp.int32)) - 1
    bidx = jnp.where(is_bar, bslot, W)
    bk = jnp.zeros(W, jnp.int32).at[bidx].set(kinds, mode="drop")
    brid = jnp.zeros(W, jnp.int32).at[bidx].set(rids, mode="drop")
    slot_live = jnp.arange(W, dtype=jnp.int32) < nbar
    # a barrier emits iff Ω1 or its group is open (segment j feeds slot j)
    emit = ((bk == 1) | open_[:W]) & slot_live
    lower = (bk > 1) & slot_live
    # per-segment start value: init once any earlier barrier emitted
    emitted_before = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(emit.astype(jnp.int32))]) > 0
    g = jnp.where(emitted_before, jnp.int32(init), acc)
    if vals is not None:
        # valueless reduce folds nothing — scattering zeros would corrupt
        # a min/max accumulator
        g = _scatter_red(op, g, jnp.where(is_data, seg, W + 1), vals)
    new_acc = g[nbar]
    new_open = open_[nbar]
    # interleave the two emission slots per barrier: [emit?, lower?]
    k2 = jnp.stack([jnp.where(emit, 0, NOTHING),
                    jnp.where(lower, bk - 1, NOTHING)], axis=1).reshape(-1)
    v2 = jnp.stack([jnp.where(emit, g[:W], 0),
                    jnp.zeros(W, jnp.int32)], axis=1).reshape(-1)
    r2 = jnp.stack([brid, brid], axis=1).reshape(-1)
    out_k, (out_v, out_r), count = window_compact(k2 != NOTHING, k2,
                                                  (v2, r2))
    return out_k, out_v, out_r, count, new_acc, new_open


def atomic_add_window(mem, addr, delta, ok, base_lane_key):
    """Vectorized fetch-and-add with sequential-within-window semantics:
    lane i observes the sum of all earlier ``ok`` lanes' deltas on its
    address (mirrors ``VectorVM._atomic_add``'s stable-sort prefix form).

    ``addr`` is already rebased/bounded; ``ok`` masks the participating
    lanes.  Returns ``(mem', old)`` with ``old`` zero on non-ok lanes.
    ``base_lane_key`` is a (W,) iota: the lane numbers, which mark where
    each address's run starts in the sorted window.
    """
    jnp = _jnp()
    W = addr.shape[0]
    big = jnp.int32(mem.shape[0] + 1)
    key = jnp.where(ok, addr, big)
    # stable sort by address: ok lanes grouped by address, lane order kept
    # (a stable sort, not ``key * W + lane``, which overflows int32 once
    # the array times the window passes 2^31)
    order = jnp.argsort(key, stable=True)
    sa = addr[order]
    sd = jnp.where(ok, delta, 0)[order]
    sok = ok[order]
    seg_start = jnp.concatenate(
        [jnp.ones(1, bool), sa[1:] != sa[:-1]]) & sok
    csum = jnp.cumsum(sd) - sd                     # exclusive global prefix
    start_pos = jax_cummax(jnp.where(seg_start, base_lane_key, -1))
    seg_base = csum[jnp.clip(start_pos, 0, W - 1)]
    prefix = csum - seg_base
    olds = jnp.where(sok, mem[jnp.clip(sa, 0, mem.shape[0] - 1)] + prefix, 0)
    old = jnp.zeros(W, jnp.int32).at[order].set(olds)
    mem = mem.at[jnp.where(ok, addr, mem.shape[0])].add(delta, mode="drop")
    return mem, old


def jax_cummax(a):
    import jax
    return jax.lax.cummax(a, axis=0)
