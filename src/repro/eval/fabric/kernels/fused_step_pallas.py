"""Fused Pallas sweep step: water-fill + horizon + advance + FIFO feed.

One ``pallas_call`` per sweep instead of four kernel launches plus the
intermediate arrays between them: each grid program owns one scenario
row and carries rates -> dt -> byte movement -> queue feed through
registers/VMEM. The water level comes from the same bisection as
:mod:`repro.eval.fabric.kernels.waterfill_pallas` (no in-kernel sort);
everything else mirrors the backend-neutral kernels in
:mod:`repro.eval.fabric.kernels` — ``disk_pool``, ``event_horizon``,
``advance_channels``, and the pure-FIFO branch of ``feed_queues`` —
which remain the semantic reference (``tests/test_fabric_kernels.py``
pins the equivalence).

Scope: the *pure-FIFO* common case. The driver only routes a sweep here
while no resume file exists anywhere in the batch
(``REPRO_FABRIC_FUSED_STEP=pallas`` or ``FabricSimulation(...,
fused_step="pallas")``); sweeps with a live LIFO stack take the
classic split path. Compiled on TPU/GPU, interpreted on CPU, exactly
like the standalone water-fill kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..bucketing import qsizes_pad
from .waterfill_pallas import _BISECT_ITERS, supports_compiled_pallas

_EPS = 1e-12
_INF = float("inf")


def _fused_kernel(
    act_ref, busy_ref, dead_ref, rem_ref, cap_ref, chunk_ref,
    tick_dt_ref, bw_ref, disk_ref, sat_ref, cont_ref,
    qoff_ref, qlen_ref, qptr_ref, qb_ref, fsdt_ref, qsizes_ref,
    dt_ref, rsum_ref, fin_ref, busy_out, dead_out, rem_out, moved_out,
    qptr_out, qb_out,
):
    row = lambda ref: jnp.reshape(ref[...], (-1,))  # (1, W) block -> (W,)
    enabled = act_ref[0]
    busy = row(busy_ref)
    dead = row(dead_ref)
    rem = row(rem_ref)
    chunk_of = row(chunk_ref)
    K = qptr_ref.shape[-1]

    # ---- disk_pool ----
    transferring = busy & (dead <= _EPS)
    n_t = jnp.sum(transferring)
    over = jnp.maximum(0, n_t - sat_ref[0])
    agg_disk = disk_ref[0] / (1.0 + cont_ref[0] * over)
    pool = jnp.where(n_t > 0, jnp.minimum(bw_ref[0], agg_disk), 0.0)

    # ---- water-fill (bisected level, as waterfill_pallas) ----
    caps = jnp.where(transferring, row(cap_ref), 0.0)
    total = jnp.sum(caps)
    pool_eff = jnp.clip(jnp.minimum(pool, total), 0.0, None)
    hi = jnp.max(caps)
    lo = jnp.zeros_like(hi)

    def bisect(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        low = jnp.sum(jnp.minimum(caps, mid)) < pool_eff
        return jnp.where(low, mid, lo), jnp.where(low, hi, mid)

    lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, bisect, (lo, hi))
    rates = jnp.where(enabled, jnp.minimum(caps, hi), 0.0)

    # ---- event_horizon ----
    dead_evt = jnp.where(busy & (dead > _EPS), dead, _INF)
    xcond = transferring & (rates > _EPS)
    xfer_evt = jnp.where(xcond, rem, _INF) / jnp.where(xcond, rates, 1.0)
    dt = jnp.minimum(
        tick_dt_ref[0], jnp.minimum(jnp.min(dead_evt), jnp.min(xfer_evt))
    )
    dt = jnp.where(enabled, jnp.maximum(dt, 0.0), 0.0)

    # ---- advance_channels ----
    in_dead = busy & (dead > _EPS) & enabled
    dead2 = jnp.where(in_dead, jnp.maximum(0.0, dead - dt), dead)
    moving = transferring & (rates > _EPS) & enabled
    moved = jnp.where(moving, jnp.minimum(rem, rates * dt), 0.0)
    rem2 = rem - moved
    finished = transferring & enabled & (rem2 <= _EPS)
    busy2 = busy & ~finished
    rem2 = jnp.where(finished, 0.0, rem2)

    # ---- feed_queues, pure-FIFO branch ----
    open_oh = chunk_of[:, None] == jnp.arange(K)
    idle = (chunk_of >= 0) & ~busy2 & enabled
    incl = open_oh & idle[:, None]
    cum = jnp.cumsum(incl, axis=0)
    rank = jnp.sum(jnp.where(incl, cum, 0), axis=1) - 1
    ch = jnp.clip(chunk_of, 0, K - 1)
    qptr = row(qptr_ref)
    fidx = qptr[ch] + rank
    valid = idle & (rank >= 0) & (fidx < row(qlen_ref)[ch])
    flat = jnp.clip(row(qoff_ref)[ch] + fidx, 0, qsizes_ref.shape[-1] - 1)
    sz = jnp.where(valid, qsizes_ref[...][flat], 0.0)
    busy3 = busy2 | valid
    rem3 = jnp.where(valid, sz, rem2)
    dead3 = dead2 + jnp.where(valid, row(fsdt_ref)[ch], 0.0)
    fed = open_oh & valid[:, None]
    qptr2 = qptr + jnp.sum(fed, axis=0)
    qb2 = row(qb_ref) - jnp.sum(jnp.where(fed, sz[:, None], 0.0), axis=0)

    dt_ref[0] = dt
    rsum_ref[0] = jnp.sum(rates)
    fin_ref[0] = jnp.any(finished)
    busy_out[...] = busy3[None, :]
    dead_out[...] = dead3[None, :]
    rem_out[...] = rem3[None, :]
    moved_out[...] = moved[None, :]
    qptr_out[...] = qptr2[None, :]
    qb_out[...] = qb2[None, :]


@functools.lru_cache(maxsize=64)
def _build_call(S: int, C: int, K: int, Q: int, interpret: bool):
    """One fused ``pallas_call`` per bucketed (S, C, K, Q) signature —
    the canonical pad ladder keeps this a handful of entries."""
    f8, i8 = jnp.float64, jnp.int64
    row = lambda width: pl.BlockSpec((1, width), lambda s: (s, 0))
    scalar = pl.BlockSpec((1,), lambda s: (s,))
    shared = pl.BlockSpec((Q,), lambda s: (0,))
    return pl.pallas_call(
        _fused_kernel,
        grid=(S,),
        in_specs=[
            scalar,                                    # act
            row(C), row(C), row(C), row(C), row(C),    # busy dead rem cap chunk
            scalar, scalar, scalar, scalar, scalar,    # tick_dt bw disk sat cont
            row(K), row(K), row(K), row(K), row(K),    # qoff qlen qptr qb fsdt
            shared,                                    # qsizes
        ],
        out_specs=[
            scalar, scalar, scalar,                    # dt rate_sum fin_any
            row(C), row(C), row(C), row(C),            # busy dead rem moved
            row(K), row(K),                            # qptr queue_bytes
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S,), f8),
            jax.ShapeDtypeStruct((S,), f8),
            jax.ShapeDtypeStruct((S,), jnp.bool_),
            jax.ShapeDtypeStruct((S, C), jnp.bool_),
            jax.ShapeDtypeStruct((S, C), f8),
            jax.ShapeDtypeStruct((S, C), f8),
            jax.ShapeDtypeStruct((S, C), f8),
            jax.ShapeDtypeStruct((S, K), i8),
            jax.ShapeDtypeStruct((S, K), f8),
        ],
        interpret=interpret,
    )


def fused_advance_feed_f64(
    act, busy, dead, rem, cap, chunk_of, tick_dt, bw, disk_rate, sat_cc,
    contention, qoff, qlen, qptr, queue_bytes, fsdt, qsizes,
    interpret=None,
):
    """Run one fused sweep step for the NumPy driver (f64 in, NumPy out).

    Returns ``(dt, rate_sum, fin_any, busy, dead, rem, moved, qptr,
    queue_bytes)``; inactive rows pass through with ``dt = 0``.
    """
    from ..jaxenv import x64

    if interpret is None:
        interpret = not supports_compiled_pallas()
    S, C = busy.shape
    K = qptr.shape[1]
    # Q rides the canonical quarter-step ladder like the jax driver's
    # upload: the feed only reads below qoff+qlen, so zero pad is inert
    qsizes = np.asarray(qsizes, dtype=np.float64)
    q_pad = qsizes_pad(qsizes.shape[0])
    if q_pad > qsizes.shape[0]:
        qsizes = np.concatenate(
            [qsizes, np.zeros(q_pad - qsizes.shape[0])]
        )
    with x64():
        call = _build_call(S, C, K, int(qsizes.shape[0]), bool(interpret))
        out = call(
            jnp.asarray(np.asarray(act, dtype=bool)),
            jnp.asarray(np.asarray(busy, dtype=bool)),
            jnp.asarray(np.asarray(dead, dtype=np.float64)),
            jnp.asarray(np.asarray(rem, dtype=np.float64)),
            jnp.asarray(np.asarray(cap, dtype=np.float64)),
            jnp.asarray(np.asarray(chunk_of, dtype=np.int64)),
            jnp.asarray(np.asarray(tick_dt, dtype=np.float64)),
            jnp.asarray(np.asarray(bw, dtype=np.float64)),
            jnp.asarray(np.asarray(disk_rate, dtype=np.float64)),
            jnp.asarray(np.asarray(sat_cc, dtype=np.int64)),
            jnp.asarray(np.asarray(contention, dtype=np.float64)),
            jnp.asarray(np.asarray(qoff, dtype=np.int64)),
            jnp.asarray(np.asarray(qlen, dtype=np.int64)),
            jnp.asarray(np.asarray(qptr, dtype=np.int64)),
            jnp.asarray(np.asarray(queue_bytes, dtype=np.float64)),
            jnp.asarray(np.asarray(fsdt, dtype=np.float64)),
            jnp.asarray(np.asarray(qsizes, dtype=np.float64)),
        )
        # np.array (not asarray): device buffers come back as read-only
        # zero-copy views, and the driver mutates these in place
        return tuple(np.array(o) for o in out)
