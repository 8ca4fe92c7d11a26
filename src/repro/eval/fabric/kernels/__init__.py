"""Backend-neutral array kernels of the fluid transfer model.

Every function takes an :class:`repro.eval.fabric.shim.ArrayOps` first and
treats channel (C) / chunk (K) structure as the trailing axes, so one
definition serves both the batched NumPy driver (leading scenario axis S)
and the JAX backend (no leading axis; ``vmap`` supplies it). The scalar
references these mirror live in ``core.netmodel`` (water-filling, dead
time) and :mod:`repro.eval.fabric.reference` (horizon, tick EMA);
``tests/test_fabric_kernels.py`` pins the correspondence on random inputs.

Nothing here may import from ``repro.core`` — ``core.netmodel`` re-exports
:func:`waterfill_batch` from this module and a core import would cycle.
"""
from __future__ import annotations

from ..shim import NO_CHUNK, ArrayOps, numpy_ops

_EPS = 1e-12


def waterfill(ops: ArrayOps, caps, pool):
    """Max-min fair allocation of ``pool`` across entities capped at ``caps``.

    ``caps``: (..., C) per-entity rate ceilings — absent/idle channels must
    carry 0 (a zero cap allocates zero, exactly like being excluded).
    ``pool``: (...,). Returns (..., C) allocations.

    Closed form of max-min fairness with ceilings: every entity gets
    ``min(cap, lam)`` for the water level ``lam`` solving
    ``sum_i min(cap_i, lam) = min(pool, sum_i cap_i)`` — the fixpoint the
    scalar progressive-filling loop (``netmodel.waterfill``) converges to,
    found here by sorting each row once instead of iterating.
    """
    xp = ops.xp
    C = caps.shape[-1]
    if C == 0:
        return xp.zeros_like(caps)
    caps_sorted = xp.sort(caps, axis=-1)
    prefix = xp.cumsum(caps_sorted, axis=-1)
    pool_eff = xp.clip(xp.minimum(pool, prefix[..., -1]), 0.0, None)
    # candidate level if the k smallest caps are filled outright:
    #   lam_k = (pool_eff - prefix[k-1]) / (C - k); valid when lam_k <= c_(k)
    prev = xp.concatenate(
        [xp.zeros_like(prefix[..., :1]), prefix[..., :-1]], axis=-1
    )
    denom = (C - xp.arange(C)).astype(caps_sorted.dtype)
    lam_k = (pool_eff[..., None] - prev) / denom
    valid = lam_k <= caps_sorted + 1e-9 * xp.maximum(caps_sorted, 1.0)
    # rows with pool >= sum(caps) have every candidate invalid except the
    # last; argmax picks the first valid k
    k = xp.argmax(valid, axis=-1)
    no_valid = ~xp.any(valid, axis=-1)
    lam = ops.table_lookup(lam_k, k[..., None])[..., 0]
    lam = xp.where(no_valid, caps_sorted[..., -1], lam)
    return xp.minimum(caps, lam[..., None])


def waterfill_batch(caps, pool):
    """NumPy instantiation of :func:`waterfill` over (S, C) scenario rows.

    Re-exported by ``core.netmodel`` as the batched form of its scalar
    ``waterfill`` reference.
    """
    import numpy as np

    caps = np.asarray(caps, dtype=np.float64)
    pool = np.asarray(pool, dtype=np.float64)
    return waterfill(numpy_ops(), caps, pool)


def waterfill_level(ops: ArrayOps, caps, pool):
    """The water level ``lam`` of :func:`waterfill`, not the allocation.

    ``caps``: (..., C) per-entity ceilings (idle entities carry 0);
    ``pool``: (...,). Returns (...,): the level solving
    ``sum_i min(cap_i, lam) = pool`` when the pool binds, and ``+inf``
    when it does not (``pool >= sum(caps)`` — every entity takes its full
    cap and the constraint is slack). The ``+inf`` convention is what the
    coupled water-fill needs: an unsaturated link imposes no ceiling on
    its members.
    """
    xp = ops.xp
    inf = float("inf")
    C = caps.shape[-1]
    if C == 0:
        return pool * 0.0 + inf
    caps_sorted = xp.sort(caps, axis=-1)
    prefix = xp.cumsum(caps_sorted, axis=-1)
    pool_eff = xp.clip(xp.minimum(pool, prefix[..., -1]), 0.0, None)
    prev = xp.concatenate(
        [xp.zeros_like(prefix[..., :1]), prefix[..., :-1]], axis=-1
    )
    denom = (C - xp.arange(C)).astype(caps_sorted.dtype)
    lam_k = (pool_eff[..., None] - prev) / denom
    valid = lam_k <= caps_sorted + 1e-9 * xp.maximum(caps_sorted, 1.0)
    k = xp.argmax(valid, axis=-1)
    no_valid = ~xp.any(valid, axis=-1)
    lam = ops.table_lookup(lam_k, k[..., None])[..., 0]
    lam = xp.where(no_valid, caps_sorted[..., -1], lam)
    return xp.where(pool >= prefix[..., -1], inf, lam)


#: fixed Jacobi sweep count of :func:`waterfill_coupled`. Constraint
#: information propagates one link-sharing hop per sweep, so this bounds
#: the fabric-graph diameter the relaxation resolves exactly; tenant
#: groups use 1-4 links, and the same constant on every backend keeps
#: event / NumPy / JAX allocations bit-aligned by construction.
COUPLED_ITERS = 12


def waterfill_coupled(ops: ArrayOps, demand, member, link_cap):
    """Max-min fair share *across* scenario rows coupled by shared links.

    Two-level fairness: each backbone link grants tenant-level max-min
    fair shares (a per-link water level), and each row then water-fills
    its grant across its own channels (:func:`waterfill`, done by the
    caller). ``demand``: (R,) per-row offered load (the rate the row
    could use this sweep — ``min(pool, sum of transferring caps)``);
    ``member``: (L, R) boolean link membership; ``link_cap``: (L,)
    capacities. Returns ``(x, levels)``: the (R,) per-row grant
    ``x_r = min(d_r, min over member links of level_l)`` and the (L,)
    final levels (``+inf`` on unsaturated links).

    Solved by Jacobi relaxation on the per-link levels: each sweep
    re-solves every link's single-link level (:func:`waterfill_level`)
    with members capped at ``min(demand, best level among the row's
    *other* links)``, starting from all-unsaturated. Fixed
    :data:`COUPLED_ITERS` sweeps on every backend — the fixpoint is the
    bottleneck-link characterization of progressive filling
    (``reference.coupled_fair_share``), and a fixed count keeps the
    computation identical across event / NumPy / JAX.

    Rows with no link membership pass through: ``x_r = demand_r``.
    """
    xp = ops.xp
    inf = float("inf")
    L = member.shape[0]
    if L == 0:
        return demand, xp.zeros((0,), dtype=demand.dtype)
    member = member != 0  # accept 0/1 tables
    levels = xp.full((L,), inf)
    # (L, L') exclusion mask: sweep l sees every link but itself
    off_diag = xp.arange(L)[:, None] != xp.arange(L)[None, :]
    for _ in range(COUPLED_ITERS):
        lvl_mat = xp.where(member, levels[:, None], inf)  # (L, R)
        # min over the row's OTHER links: (L, L', R) -> (L, R)
        excl = xp.min(
            xp.where(off_diag[:, :, None], lvl_mat[None, :, :], inf),
            axis=1,
        )
        caps = xp.where(member, xp.minimum(demand[None, :], excl), 0.0)
        levels = waterfill_level(ops, caps, link_cap)
    row_lvl = xp.min(xp.where(member, levels[:, None], inf), axis=0)
    return xp.minimum(demand, row_lvl), levels


def caps_total(ops: ArrayOps, caps):
    """Per-row cap total via the *same* sorted prefix sum ``waterfill``
    uses internally (not ``xp.sum``, whose pairwise accumulation can
    differ in the last ulp). The coupled drivers form a row's offered
    load as ``min(pool, caps_total)``; matching the summation order makes
    ``waterfill(caps, min(pool, caps_total))`` bit-identical to
    ``waterfill(caps, pool)`` — the single-tenant/unsaturated identity
    the coupled path's difftests pin.
    """
    xp = ops.xp
    C = caps.shape[-1]
    if C == 0:
        return xp.zeros(caps.shape[:-1], dtype=caps.dtype)
    return xp.cumsum(xp.sort(caps, axis=-1), axis=-1)[..., -1]


def disk_pool(
    ops: ArrayOps, n_transferring, bandwidth, disk_rate, saturation_cc,
    contention,
):
    """Shared rate pool: link capacity vs disk aggregate under contention.

    Mirrors ``netmodel.allocate_rates``'s pool =
    ``min(bandwidth, disk.aggregate_rate(active))`` with the DiskSpec
    contention penalty past saturation; 0 when nothing transfers.
    """
    xp = ops.xp
    over_sat = xp.maximum(0, n_transferring - saturation_cc)
    agg_disk = disk_rate / (1.0 + contention * over_sat)
    return xp.where(
        n_transferring > 0, xp.minimum(bandwidth, agg_disk), 0.0
    )


def file_dead_time(
    ops: ArrayOps, control_rtt, pipelining, unhidden_overhead,
    per_file_overhead,
):
    """Batched ``netmodel.file_start_dead_time``: serial per-file overhead.

    control gap ``control_rtt/(1+pipelining)`` + server-side processing the
    pipelining cannot hide + per-file disk overhead.
    """
    gap = control_rtt / (1.0 + pipelining)
    return gap + unhidden_overhead + per_file_overhead


def event_horizon(
    ops: ArrayOps, tick_dt, busy, dead, transferring, rem, rates,
    eps: float = _EPS,
):
    """Time to the next state change, capped by the controller tick.

    Batched ``fabric.reference.next_event_dt``: min over dead-time expiries
    and file completions of busy channels, floored at 0.
    """
    xp = ops.xp
    inf = float("inf")
    dead_evt = xp.where(busy & (dead > eps), dead, inf)
    xcond = transferring & (rates > eps)
    xfer_evt = xp.where(xcond, rem, inf) / xp.where(xcond, rates, 1.0)
    dt = xp.minimum(
        tick_dt,
        xp.minimum(xp.min(dead_evt, axis=-1), xp.min(xfer_evt, axis=-1)),
    )
    return xp.maximum(dt, 0.0)


def advance_channels(
    ops: ArrayOps, active, dt, busy, dead, transferring, rem, rates,
    eps: float = _EPS,
):
    """Advance channel state by ``dt``: burn dead time, move fluid bytes.

    ``active`` (...,) masks scenarios that advance this sweep. Returns
    ``(busy, dead, rem, moved, finished)`` — ``moved`` is the per-channel
    byte delta (0 on inactive rows), ``finished`` the channels that
    completed their file.
    """
    xp = ops.xp
    a = xp.expand_dims(active, -1)
    dtc = xp.expand_dims(dt, -1)
    in_dead = busy & (dead > eps) & a
    dead2 = xp.where(in_dead, xp.maximum(0.0, dead - dtc), dead)
    moving = transferring & (rates > eps) & a
    moved = xp.where(moving, xp.minimum(rem, rates * dtc), 0.0)
    rem2 = rem - moved
    finished = transferring & a & (rem2 <= eps)
    busy2 = busy & ~finished
    rem3 = xp.where(finished, 0.0, rem2)
    return busy2, dead2, rem3, moved, finished


def tick_ema(ops: ArrayOps, rate_est, delivered, delivered_at_tick, period):
    """Batched ``fabric.reference.tick_rate_update`` over chunk slots."""
    xp = ops.xp
    inst = (delivered - delivered_at_tick) / period
    return xp.where(rate_est == 0.0, inst, 0.5 * rate_est + 0.5 * inst)


def compact_channels(ops: ArrayOps, trig, chunk_of, busy, dead, rem, cap):
    """Left-pack the channel axis: open channels shift to the lowest
    columns *preserving their relative order*, freed columns collect at
    the tail (empty state). Applied after every close so that column
    order stays the event simulator's channel-*list* order — the scalar
    loop removes closed channels from its list and appends opens at the
    end, and both the feed ranking and idle-victim selection key on that
    order. ``trig`` (...,) gates rows (others pass through untouched).

    Returns ``(chunk_of, busy, dead, rem, cap)``.
    """
    xp = ops.xp
    C = chunk_of.shape[-1]
    is_open = chunk_of != NO_CHUNK
    # destination of each open column = its rank among open columns
    dest = xp.cumsum(is_open, axis=-1) - 1
    # (..., C_src, C_dst) one-hot routing: source c lands at dest[c]
    route = (
        is_open[..., :, None]
        & (dest[..., :, None] == xp.arange(C))
        & xp.expand_dims(trig, -1)[..., :, None]
    )

    def pack(arr, empty):
        if arr.dtype == bool:
            out = xp.any(route & arr[..., :, None], axis=-2)
        else:
            packed = xp.sum(
                xp.where(route, arr[..., :, None], arr.dtype.type(0)),
                axis=-2,
            )
            filled = xp.any(route, axis=-2)
            out = xp.where(filled, packed, arr.dtype.type(empty))
        return xp.where(xp.expand_dims(trig, -1), out, arr)

    return (
        pack(chunk_of, NO_CHUNK),
        pack(busy, False),
        pack(dead, 0.0),
        pack(rem, 0.0),
        pack(cap, 0.0),
    )


def row_total(ops: ArrayOps, x):
    """Left-to-right sum over the last axis. ``xp.sum`` is pairwise in
    NumPy and, past a width, tree-shaped in XLA, so the two can differ
    in the last ulp; the timeline's rate total takes this one fixed
    order so both drivers record bit-identically."""
    if x.shape[-1] == 0:
        return ops.xp.zeros(x.shape[:-1], dtype=x.dtype)
    total = x[..., 0]
    for c in range(1, x.shape[-1]):
        total = total + x[..., c]
    return total


def timeline_push(
    ops: ArrayOps, rec, t, rate, buf_t, buf_r, length, stride, seen,
    last_t, last_r,
):
    """Streaming append into the on-device timeline ring buffer with
    uniform-stride decimation.

    The buffer holds every candidate sample whose index is a multiple of
    ``stride`` (so it is always a uniform-stride decimation of the full
    timeline, first sample included). When a store would overflow the
    fixed budget ``T = buf_t.shape[-1]``, the buffer is compacted in
    place — keep every other stored sample — and the stride doubles, so
    the budget amortizes over arbitrarily long runs. ``rec`` (...,)
    gates which rows record this sweep; ``seen`` counts candidates so
    far; ``last_t``/``last_r`` always track the newest candidate (the
    host finalize, :func:`timeline_samples`, re-attaches the final
    sample when decimation dropped it).

    Pure selects and integer bookkeeping — no float arithmetic — so the
    NumPy and JAX instantiations record bit-identically given the same
    sample stream. Returns the seven updated arrays in argument order.
    """
    xp = ops.xp
    T = buf_t.shape[-1]
    stride_safe = xp.maximum(stride, 1)  # padded device rows carry 0
    want = rec & (seen % stride_safe == 0)
    full = want & (length >= T)
    # stride-2 compaction: storage position j keeps old position 2j
    half = (T + 1) // 2
    comp_t = xp.concatenate(
        [buf_t[..., 0::2], xp.zeros_like(buf_t[..., : T - half])], axis=-1
    )
    comp_r = xp.concatenate(
        [buf_r[..., 0::2], xp.zeros_like(buf_r[..., : T - half])], axis=-1
    )
    full_e = xp.expand_dims(full, -1)
    buf_t = xp.where(full_e, comp_t, buf_t)
    buf_r = xp.where(full_e, comp_r, buf_r)
    length = xp.where(full, (length + 1) // 2, length)
    stride = xp.where(full, stride_safe * 2, stride)
    # re-check under the (possibly doubled) stride
    store = rec & (seen % xp.maximum(stride, 1) == 0) & (length < T)
    at = xp.arange(T) == xp.expand_dims(length, -1)
    store_e = xp.expand_dims(store, -1)
    buf_t = xp.where(store_e & at, xp.expand_dims(t, -1), buf_t)
    buf_r = xp.where(store_e & at, xp.expand_dims(rate, -1), buf_r)
    length = length + xp.where(store, 1, 0)
    seen = seen + xp.where(rec, 1, 0)
    last_t = xp.where(rec, t, last_t)
    last_r = xp.where(rec, rate, last_r)
    return buf_t, buf_r, length, stride, seen, last_t, last_r


def timeline_samples(buf_t, buf_r, length, stride, seen, last_t, last_r):
    """Finalize one scenario's recorded timeline (host side, 1-D rows).

    Returns the stored ``(t, rate)`` samples plus the *last* candidate
    sample when decimation dropped it (appended while the budget allows,
    else replacing the final stored slot) — so first and last samples
    are always preserved and the result never exceeds the budget.
    """
    n, s, seen = int(length), max(int(stride), 1), int(seen)
    out = [(float(buf_t[j]), float(buf_r[j])) for j in range(n)]
    if seen > 0 and (seen - 1) % s != 0:
        final = (float(last_t), float(last_r))
        if n < buf_t.shape[-1]:
            out.append(final)
        else:
            out[-1] = final
    return out


def feed_queues(
    ops: ArrayOps, enabled, chunk_of, busy, dead, rem, qsizes, qoff, qlen,
    qptr, queue_bytes, fsdt, prepend_sizes=None, prepend_n=None,
):
    """Idle open channels pull the next file of their chunk: resume files
    off the LIFO prepend stack first, then the FIFO queue.

    Channels of one chunk are interchangeable (same params). Ranking the
    chunk's idle channels in column order, the channel of rank ``r`` takes
    the resume file at stack depth ``prepend_n - 1 - r`` while ``r <
    prepend_n`` (deque.appendleft/popleft order), and the queued file at
    ``qptr + r - prepend_n`` afterwards — byte-for-byte the assignment the
    scalar feed loop produces. ``enabled`` (...,) gates whole scenarios.
    ``prepend_sizes`` (..., K, P) / ``prepend_n`` (..., K) may be omitted
    when no resume files can exist (pure-FIFO callers/tests).

    Returns ``(busy, dead, rem, qptr, queue_bytes, prepend_n)``.
    """
    xp = ops.xp
    K = qptr.shape[-1]
    if prepend_n is None:
        prepend_n = xp.zeros(qptr.shape, dtype=qptr.dtype)
    if qsizes.shape[0] == 0 and prepend_sizes is None:
        # no files anywhere: nothing can feed
        return busy, dead, rem, qptr, queue_bytes, prepend_n
    open_oh = chunk_of[..., :, None] == xp.arange(K)  # NO_CHUNK matches none
    idle = (chunk_of >= 0) & ~busy & xp.expand_dims(enabled, -1)
    incl = open_oh & idle[..., :, None]
    # rank of each idle channel within its (scenario, chunk) group, in
    # channel order: inclusive cumsum down the channel axis, gathered at
    # the channel's own chunk column
    cum = xp.cumsum(incl, axis=-2)
    rank = xp.sum(xp.where(incl, cum, 0), axis=-1) - 1  # -1 when not idle
    # chunk-indexed gathers; junk values on unassigned channels are
    # harmless because ``valid`` requires ``idle`` (=> assigned)
    ch_clip = xp.clip(chunk_of, 0, K - 1)
    qptr_c = ops.table_lookup(qptr, ch_clip)
    qlen_c = ops.table_lookup(qlen, ch_clip)
    qoff_c = ops.table_lookup(qoff, ch_clip)
    fsdt_c = ops.table_lookup(fsdt, ch_clip)

    if prepend_sizes is not None:
        pn_c = ops.table_lookup(prepend_n, ch_clip)
        use_pre = idle & (rank >= 0) & (rank < pn_c)
        P = prepend_sizes.shape[-1]
        ps_flat = xp.reshape(
            prepend_sizes, prepend_sizes.shape[:-2] + (K * P,)
        )
        pidx = ch_clip * P + xp.clip(pn_c - 1 - rank, 0, P - 1)
        # a real gather, not the one-hot table_lookup: the stack axis is
        # pre-sized from the worst-case bound (P up to ~2x the channel
        # axis), so a one-hot here would cost O(C*K*P) on every
        # stack-path sweep; C scalar loads are cheaper on both backends
        pre_sz = xp.take_along_axis(ps_flat, pidx, axis=-1)
    else:
        # pure-FIFO fast path: callers pass None exactly when no resume
        # files exist anywhere, so skip the stack bookkeeping entirely
        pn_c = xp.zeros(rank.shape, dtype=qptr.dtype)
        use_pre = xp.zeros(rank.shape, dtype=bool)
        pre_sz = xp.zeros(rank.shape, dtype=xp.float64)

    fidx = qptr_c + rank - pn_c
    valid_fifo = idle & (rank >= pn_c) & (fidx < qlen_c)
    if qsizes.shape[0] == 0:
        valid_fifo = valid_fifo & False
        fifo_sz = xp.zeros(rank.shape, dtype=xp.float64)
    else:
        flat = xp.clip(qoff_c + fidx, 0, qsizes.shape[0] - 1)
        fifo_sz = xp.take(qsizes, flat)
    valid = use_pre | valid_fifo
    sizes = xp.where(use_pre, pre_sz, xp.where(valid_fifo, fifo_sz, 0.0))
    busy2 = busy | valid
    rem2 = xp.where(valid, sizes, rem)
    dead2 = dead + xp.where(valid, fsdt_c, 0.0)
    # per-chunk counts/sums reuse the one-hot built for ranking; sizes are
    # integer-valued doubles, so the summation order is exact either way
    qptr2 = qptr + xp.sum(open_oh & valid_fifo[..., :, None], axis=-2)
    pn2 = prepend_n - xp.sum(open_oh & use_pre[..., :, None], axis=-2)
    qb2 = queue_bytes - xp.sum(
        xp.where(open_oh & valid[..., :, None], sizes[..., :, None], 0.0),
        axis=-2,
    )
    return busy2, dead2, rem2, qptr2, qb2, pn2
