"""JAX instantiation of the fabric kernels: jit + vmap at matrix scale.

The advance loop *and the controller decision layer* run on-device: a
per-scenario sweep function — the same :mod:`repro.eval.fabric.kernels`
fluid kernels plus the :mod:`repro.eval.fabric.controllers` decision
kernels (ProMC streak machine, laggard-ETA grants, SC cursor walk,
masked channel Open/Close/Move transitions, LIFO resume stack) — is
``vmap``-mapped over the scenario axis and iterated inside a
``jit``-compiled ``lax.while_loop``. Steady-state SC / MC / ProMC and
baseline scenarios therefore never leave the device: the per-scenario
host-sync count is O(1) instead of O(ticks).

For every *built-in* scheduler the loop is zero-host-round: timeline
recording streams into an on-device ring buffer (the
``kernels.timeline_push`` uniform-stride decimator, bit-identical to the
NumPy driver's), simultaneous multi-chunk completions drain through an
unrolled on-device handler loop, and the channel / resume-stack axes are
pre-sized from the driver's closed-form worst-case bound
(:meth:`FabricSimulation.capacity_need`), so the old capacity-guard park
classes cannot fire. A scenario *parks* (``stall``) only when its next
transition genuinely needs Python:

  * custom Scheduler subclasses (anything that is not exactly one of the
    three paper controllers or a no-op baseline) park at their callback
    events, exactly like the pre-fusion design;
  * the capacity guards (an SC open wave exceeding the pre-sized channel
    axis, a resume push into a full prepend stack) remain compiled in as
    an assertion-guarded fallback — unreachable for built-in schedulers,
    but a custom subclass that defeats the closed-form bound degrades to
    a one-sweep host replay instead of corrupting device state.

The host then replays exactly the NumPy driver's transition half
(:meth:`FabricSimulation._post`) for the parked rows and re-enters the
device loop. Scenarios are independent — their clocks may drift
arbitrarily — so this interleaving produces the same per-scenario event
sequence as the synchronized NumPy sweeps; ``eval.difftest`` holds all
backends to the event simulator within the 2% bar, and ``SYNC_STATS``
proves the zero-replay property on every run.

Numerics run in float64 via the scoped :func:`jaxenv.x64` context
(``jax.enable_x64``, never the global flag: the rest of the repo traces
in f32).

Execution-loop structure (the overlap-pipelined executor rides on it):

  * the jit boundary takes *(mutable, const, qsizes)* instead of one
    merged state dict, and only the mutable half is carried through the
    ``while_loop`` — the read-only tables are closed over as loop
    invariants, so the carry's double buffer covers state that actually
    changes, not the decision tables;
  * the mutable half is **donated** (``donate_argnums=0``) whenever
    :func:`donation_enabled` says so (default: on under the async
    executor, forced via ``REPRO_FABRIC_DONATE``), so steady-state sweeps
    update device buffers in place instead of allocating a second copy.
    Donated buffers are dead after the call — the driver re-uploads from
    host NumPy each round and never touches a donated array again;
  * each batch can be pinned to a device (``device=``) — the executor
    round-robins chunks across ``jax.devices()``;
  * :func:`warm_signature` AOT-compiles (``jit(...).lower().compile()``)
    the loop for a canonical :func:`bucketing.canonical_signature` before
    the first chunk needs it, taking the ~1 s/signature Python retrace
    off the critical path. ``SYNC_STATS`` merges are per-run atomic so
    interleaved chunks report the same totals as serial execution.
"""
from __future__ import annotations

import os
import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.simulator import SimResult, Simulation

from . import controllers, kernels
from .bucketing import COMPACT_FLOOR, MIN_ROW_PAD, bucket, qsizes_pad
from .driver import (
    _EPS,
    _NO_CHUNK,
    KIND_MC,
    KIND_PROMC,
    KIND_SC,
    FabricSimulation,
)
from .jaxenv import cache_dir, x64
from .shim import jax_ops

_ERR_NONE, _ERR_MAXTIME, _ERR_STRANDED = 0, 1, 2
_STALL_NONE, _STALL_POST = 0, 1

#: cap on device sweeps per while_loop entry: parked scenarios wait for
#: the loop to exit before their Python decision runs, so unbounded entries
#: let one long trivial stretch starve every parked controller. With the
#: controller layer fused, parking is a rare edge — the quarter-cohort
#: early exit (compactable shapes only) still bounds any parked row's
#: wait there, and the cap bounds it everywhere else.
_ROUND_CAP = 2048

#: floor on the padded device row count. Straggler tails run thousands of
#: narrow sweeps whose cost is linear in the pad width, so a low floor is
#: what makes the endgame cheap; each extra power-of-two bucket costs one
#: more XLA trace at compile time. Once a round starts at the floor,
#: draining below half the cohort cannot shrink the device shape, so the
#: half-cohort early exit is skipped there (see ``_device_rounds``).
#: Aliased from :mod:`repro.eval.fabric.bucketing` — the canonical pad
#: ladder shared by the runner's chunk spans and the tuner's planes.
_MIN_PAD = MIN_ROW_PAD

#: host-sync + wall-clock telemetry. The accumulator lives in the
#: jax-free :mod:`repro.eval.fabric.stats` (the executor records build/
#: compute walls from NumPy runs too); these are the *same* objects, so
#: ``jax_backend.SYNC_STATS`` / ``reset_sync_stats`` keep their
#: historical spelling and reset both views in place.
from .stats import (  # noqa: E402,F401  (re-exported API)
    SYNC_STATS,
    _SYNC_LOCK,
    _merge_sync_stats,
    reset_sync_stats,
)


def donation_enabled(default: Optional[bool] = None) -> bool:
    """Resolve the buffer-donation toggle: ``REPRO_FABRIC_DONATE`` wins,
    else ``default`` (a driver kwarg), else on exactly when the async
    executor is active — ``REPRO_FABRIC_EXECUTOR=serial`` preserves the
    undonated pre-executor execution path byte for byte. Donated programs
    go through the persistent compilation cache like any other."""
    env = os.environ.get("REPRO_FABRIC_DONATE")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "off", "no", "")
    if default is not None:
        return bool(default)
    from .executor import executor_mode

    return executor_mode() == "async"


#: state arrays the device sweep may mutate (host <-> device sync set)
_MUTABLE = (
    "t", "done", "next_tick", "n_events", "dead", "rem", "busy",
    "chunk_done", "completed_at", "delivered", "delivered_at_tick",
    "rate_est", "queue_bytes", "qptr", "finish_t", "fin_any", "stall",
    "err", "chunk_of", "cap", "prepend_n", "prepend_sizes", "streak",
    "pair_fast", "pair_slow", "sc_cursor", "n_moves",
    "tl_t", "tl_rate", "tl_len", "tl_stride", "tl_seen", "tl_last_t",
    "tl_last_rate",
)
#: read-only inputs fixed for a batch's lifetime — device-cached, rebuilt
#: only when compaction changes the row set
_CONST_STATIC = (
    "max_time", "tick_period", "bw", "disk_rate", "sat_cc", "contention",
    "trivial_tick", "trivial_complete", "qoff", "qlen", "fsdt", "kind",
    "sc_order", "conc", "par", "cap_k", "avg_fs_k", "nfiles",
    "setup_cost", "promc_ratio", "promc_patience", "prof_t", "prof_mult",
    "n_chunks", "record_timeline", "cap_need",
)


def _views_row(ops, xp, row, chunk_of, busy, rem, queue_bytes, rate_est, K):
    """Per-row ChunkView arrays: (K,) channel counts, ETA inputs."""
    open_mask = chunk_of != _NO_CHUNK
    n_ch = ops.count_by_chunk(chunk_of, open_mask, K)
    n_open = xp.sum(open_mask)
    inflight = ops.chunk_scatter_add(
        xp.zeros_like(queue_bytes), chunk_of, rem, open_mask & busy
    )
    bytes_rem = queue_bytes + inflight
    pred = controllers.predicted_chunk_rate(
        ops, row["avg_fs_k"], row["cap_k"], row["fsdt"], n_ch, n_open,
        row["bw"], row["disk_rate"], row["sat_cc"], row["contention"],
    )
    eta = controllers.chunk_eta(ops, bytes_rem, rate_est, pred, row["chunk_done"])
    return bytes_rem, n_ch, eta


#: per-sweep scratch passed between the phases of one device sweep
#: (zero-initialized on upload so the while_loop carry keeps its shape)
_SCRATCH = ("_completed", "_handler", "_tick", "_moving", "_msrc", "_mdst")

#: the on-device timeline ring-buffer state threaded through phase A
_TIMELINE = (
    "tl_t", "tl_rate", "tl_len", "tl_stride", "tl_seen", "tl_last_t",
    "tl_last_rate",
)


def _phase_advance(
    row: dict, qsizes, with_stack: bool = True, coupled: bool = False
):
    """Phase A of one sweep (always runs): physics advance, park
    detection, queue feed, completion marking, tick EMA bookkeeping, and
    scenario-done detection — everything except the (rarer) controller
    handlers, which the batch-level driver gates behind ``lax.cond``.

    ``with_stack=False`` is the pure-FIFO feed variant the driver picks
    (batch-level ``lax.cond``) on sweeps where no resume file exists
    anywhere — the common case — skipping the resume-stack gathers whose
    cost scales with the pre-sized stack depth P.

    ``coupled=True`` is the shared-fabric variant: the coupled device
    loop pre-computes each row's granted pool (``row["_pool_ovr"]``, the
    cross-row ``waterfill_coupled`` output — the uncoupled pool verbatim
    for rows outside every fabric group) and the group lockstep horizon
    cap (``row["_dt_ovr"]``, +inf for uncoupled rows), and this phase
    substitutes them for its own pool / caps its own dt. Everything
    downstream of the two substitutions is the uncoupled sweep
    unchanged.
    """
    ops = jax_ops()
    xp = ops.xp
    K = row["chunk_done"].shape[-1]
    P = row["prepend_sizes"].shape[-1]

    runnable = (
        ~row["done"]
        & (row["stall"] == _STALL_NONE)
        & (row["err"] == _ERR_NONE)
    )
    err = xp.where(
        row["t"] > row["max_time"], _ERR_MAXTIME, _ERR_NONE
    )

    # ---- advance: rates, horizon, fluid byte movement ----
    transferring = row["busy"] & (row["dead"] <= _EPS)
    oh = row["chunk_of"][..., :, None] == xp.arange(K)
    n_ch_open = xp.sum(oh, axis=-2)
    stranded = (~xp.any(row["busy"])) & xp.any(
        ~row["chunk_done"] & (n_ch_open == 0)
    )
    err = xp.where((err == _ERR_NONE) & stranded, _ERR_STRANDED, err)
    # rows that are parked/done, or errored *this* sweep, freeze at their
    # pre-sweep state: zeroing dt and gating every transition mask below
    # makes the whole sweep a natural no-op for them — no commit masking
    alive = runnable & (err == _ERR_NONE)
    if row["prof_t"].shape[-1] == 1:  # static path: the common case
        eff_bw, next_prof = row["bw"], xp.inf
    else:
        prof_at = xp.sum(row["prof_t"] <= row["t"]) - 1
        mult = row["prof_mult"][xp.maximum(prof_at, 0)]
        eff_bw = row["bw"] * xp.where(prof_at >= 0, mult, 1.0)
        next_prof = xp.min(
            xp.where(row["prof_t"] > row["t"], row["prof_t"], xp.inf)
        )
    if coupled:
        pool = row["_pool_ovr"]
    else:
        pool = kernels.disk_pool(
            ops, xp.sum(transferring), eff_bw, row["disk_rate"],
            row["sat_cc"], row["contention"],
        )
    rates = kernels.waterfill(
        ops, xp.where(transferring, row["cap"], 0.0), pool
    )
    # ---- timeline ring buffer (pre-advance t, this sweep's rates) ----
    tl = {k: row[k] for k in _TIMELINE}
    if row["tl_t"].shape[-1] > 1:  # width-1 buffers mean "no row records"
        (
            tl["tl_t"], tl["tl_rate"], tl["tl_len"], tl["tl_stride"],
            tl["tl_seen"], tl["tl_last_t"], tl["tl_last_rate"],
        ) = kernels.timeline_push(
            ops, alive & row["record_timeline"], row["t"],
            kernels.row_total(ops, rates),
            row["tl_t"], row["tl_rate"], row["tl_len"], row["tl_stride"],
            row["tl_seen"], row["tl_last_t"], row["tl_last_rate"],
        )
    dt = kernels.event_horizon(
        ops,
        xp.minimum(row["next_tick"] - row["t"], next_prof - row["t"]),
        row["busy"], row["dead"], transferring, row["rem"], rates,
    )
    if coupled:
        # lockstep: a fabric group shares one clock; members take the
        # group-minimum horizon (a partial advance crosses no threshold,
        # so the sweep is a natural no-op beyond the moved bytes)
        dt = xp.minimum(dt, row["_dt_ovr"])
    dt = xp.where(alive, dt, 0.0)
    t2 = row["t"] + dt
    busy2, dead2, rem2, moved, finished = kernels.advance_channels(
        ops, alive, dt, row["busy"], row["dead"], transferring,
        row["rem"], rates,
    )
    delivered2 = row["delivered"] + xp.sum(
        xp.where(oh & (moved != 0.0)[..., :, None], moved[..., :, None], 0.0),
        axis=-2,
    )
    fin_any = xp.where(alive, xp.any(finished), row["fin_any"])

    # ---- decision-point detection (pre-feed completion == post-feed:
    # feeding swaps queue files for busy channels, never zeroes both) ----
    files_left = row["qlen"] - row["qptr"] + row["prepend_n"]
    busy_pc = xp.sum(oh & busy2[..., :, None], axis=-2)
    comp_pre = ~row["chunk_done"] & (files_left == 0) & (busy_pc == 0)
    comp_any_pre = xp.any(comp_pre)
    tick_hit = t2 >= row["next_tick"] - _EPS
    kind = row["kind"]
    # SC / MC / ProMC route through the fused controller phases below;
    # KIND_STATIC (the autotuner's fixed-parameter candidate rows) sits
    # deliberately below KIND_SC — like the trivial baselines it acts
    # only at t=0, so it needs neither the handlers nor a host replay
    known = kind >= KIND_SC

    # Only *custom* scheduler subclasses still need Python: their
    # callbacks run through the scalar protocol on the host. Built-in
    # rows never park — multi-chunk same-sweep completions drain through
    # the on-device phase-B loop, and the channel / resume-stack axes
    # are pre-sized from the closed-form worst-case bound, so the
    # capacity guards below can never fire for them (``SYNC_STATS``/CI
    # gate on exactly that). The guards stay as defense in depth should
    # the bound ever be wrong: ``sc_short`` checks the *actual* free
    # columns against the next SC wave (single-wave conservative — the
    # static ``cap_need`` term covers the multi-wave drain) and
    # ``pp_full`` the *actual* stack depth, each degrading to a
    # one-sweep host replay (with growth) instead of corrupting device
    # state.
    C = row["chunk_of"].shape[-1]
    n_free = xp.sum(row["chunk_of"] == _NO_CHUNK)
    freed_cols = xp.sum(xp.where(comp_pre, n_ch_open, 0))
    sc_short = (
        (kind == KIND_SC)
        & comp_any_pre
        & (
            (row["cap_need"] > C)
            | (n_free + freed_cols < xp.max(row["conc"]))
        )
    )
    pp_full = (
        (kind == KIND_PROMC) & tick_hit & xp.any(row["prepend_n"] >= P)
    )
    needs_py = alive & (
        (comp_any_pre & ~row["trivial_complete"] & ~known)
        | (tick_hit & ~row["trivial_tick"] & (kind != KIND_PROMC))
        | sc_short
        | pp_full
    )
    ok = alive & ~needs_py

    # ---- feed (LIFO resume stack first, then FIFO queue) ----
    busy3, dead3, rem3, qptr3, qb3, pn3 = kernels.feed_queues(
        ops, ok, row["chunk_of"], busy2, dead2, rem2, qsizes,
        row["qoff"], row["qlen"], row["qptr"], row["queue_bytes"],
        row["fsdt"], row["prepend_sizes"] if with_stack else None,
        row["prepend_n"],
    )

    # ---- chunk completions: mark (handlers run in phase B) ----
    # post-feed busy count derives from the feed deltas (a fed channel is
    # exactly a queue/stack pop): no second per-chunk count needed
    busy_pc3 = busy_pc + (qptr3 - row["qptr"]) + (row["prepend_n"] - pn3)
    completed = (
        ~row["chunk_done"]
        & ((row["qlen"] - qptr3 + pn3) == 0)
        & (busy_pc3 == 0)
        & ok
    )
    chunk_done2 = row["chunk_done"] | completed
    qb4 = xp.where(completed, 0.0, qb3)
    completed_at2 = xp.where(completed, t2, row["completed_at"])
    comp_any = xp.any(completed)

    is_promc = kind == KIND_PROMC
    streak2 = xp.where(comp_any & is_promc, 0, row["streak"])
    pf2 = xp.where(comp_any & is_promc, -1, row["pair_fast"])
    ps2 = xp.where(comp_any & is_promc, -1, row["pair_slow"])

    # ---- tick EMA bookkeeping (the ProMC decision is phase C) ----
    do_tick = tick_hit & ok
    ema = kernels.tick_ema(
        ops, row["rate_est"], delivered2, row["delivered_at_tick"],
        row["tick_period"],
    )
    rate_est2 = xp.where(do_tick, ema, row["rate_est"])
    dat2 = xp.where(do_tick, delivered2, row["delivered_at_tick"])
    next_tick2 = row["next_tick"] + xp.where(
        do_tick, row["tick_period"], 0.0
    )

    # ---- scenario completion ----
    done2 = ok & xp.all(chunk_done2) & (fin_any | comp_any)
    finish_t2 = xp.where(done2, t2, row["finish_t"])

    # ---- commit ----
    # frozen rows (parked/done/errored) took dt=0 with every transition
    # mask gated on ``alive``/``ok``, so their arrays pass through
    # unchanged by construction — no per-array commit masking needed
    out = dict(row)
    out["err"] = xp.where(runnable, err, row["err"])
    out["t"] = t2
    out["n_events"] = row["n_events"] + xp.where(alive, 1, 0)
    out["busy"] = busy3
    out["dead"] = dead3
    out["rem"] = rem3
    out["delivered"] = delivered2
    out["fin_any"] = fin_any
    out["qptr"] = qptr3
    out["queue_bytes"] = qb4
    out["prepend_n"] = pn3
    out["chunk_done"] = chunk_done2
    out["completed_at"] = completed_at2
    out["rate_est"] = rate_est2
    out["delivered_at_tick"] = dat2
    out["next_tick"] = next_tick2
    out["streak"] = streak2
    out["pair_fast"] = pf2
    out["pair_slow"] = ps2
    out["finish_t"] = finish_t2
    out["done"] = row["done"] | done2
    out["stall"] = xp.where(needs_py, _STALL_POST, row["stall"])
    out.update(tl)
    # scratch for phases B-D (zeroed wherever this sweep didn't act)
    out["_completed"] = completed
    out["_handler"] = comp_any & known
    out["_tick"] = do_tick & is_promc
    out["_moving"] = xp.zeros_like(alive)
    return out


def _phase_complete(row: dict, qsizes):
    """Phase B, one drain step (runs only on sweeps where some row
    completed a chunk on a fused controller): the completion handler —
    SC close/cursor/open or MC/ProMC laggard grants — plus the
    post-action feed, for the *lowest-index* unhandled completed chunk
    of each row (``argmax`` of the remaining mask), which the handler
    then clears. The batch driver iterates this step in a ``lax.
    while_loop`` until every row's completions drain, mirroring the host
    ``_complete_ctrl``'s ascending ``for k in range(K)`` per row — so
    simultaneous multi-chunk completions (empty size classes at t=0,
    exact ties) no longer need a host replay, the common
    single-completion sweep pays one drain iteration, and only one
    handler body is ever compiled."""
    ops = jax_ops()
    xp = ops.xp
    K = row["chunk_done"].shape[-1]
    C = row["chunk_of"].shape[-1]
    kind = row["kind"]
    remaining = row["_completed"] & xp.expand_dims(row["_handler"], -1)
    trig = xp.any(remaining, axis=-1)
    k = xp.argmax(remaining, axis=-1)

    chunk_of_c, busy_c, dead_c, rem_c, cap_c = (
        row["chunk_of"], row["busy"], row["dead"], row["rem"], row["cap"],
    )
    qb_c, qptr_c, pn_c = (
        row["queue_bytes"], row["qptr"], row["prepend_n"],
    )
    nmoves_c = row["n_moves"]

    # SC: close the finished chunk, cursor past empties, open the next
    sc_t = trig & (kind == KIND_SC)
    chunk_of_c, busy_c, dead_c, rem_c, cap_c = controllers.close_chunk(
        ops, sc_t, k, chunk_of_c, busy_c, dead_c, rem_c, cap_c
    )
    cursor_c = controllers.sc_advance_cursor(
        ops, sc_t, row["sc_cursor"], row["sc_order"], row["nfiles"],
        row["n_chunks"],
    )
    open_ok = sc_t & (cursor_c < row["n_chunks"])
    nxt = row["sc_order"][xp.clip(cursor_c, 0, K - 1)]
    n_open = xp.where(open_ok, row["conc"][nxt], 0)
    chunk_of_c, dead_c, cap_c = controllers.open_ranked(
        ops, n_open, nxt, chunk_of_c, dead_c, cap_c,
        row["setup_cost"], row["cap_k"],
    )
    # MC / ProMC: freed channels to the largest-ETA laggards
    mc_t = trig & ((kind == KIND_MC) | (kind == KIND_PROMC))
    bytes_rem, n_ch, eta = _views_row(
        ops, xp, row, chunk_of_c, busy_c, rem_c, qb_c,
        row["rate_est"], K,
    )
    live = ~row["chunk_done"] & (xp.arange(K) != k) & (bytes_rem > 0)
    freed = xp.where(mc_t, n_ch[..., k], 0)
    grants, first = controllers.laggard_grants(
        ops, eta, n_ch, live, freed, C
    )
    acted = mc_t & (xp.sum(grants) > 0)
    (
        chunk_of_c, busy_c, dead_c, rem_c, cap_c, nmoves_c,
    ) = controllers.apply_grants(
        ops, acted, k, grants, first, chunk_of_c, busy_c, dead_c,
        rem_c, cap_c, nmoves_c, row["par"], row["cap_k"],
        row["setup_cost"],
    )
    busy_c, dead_c, rem_c, qptr_c, qb_c, pn_c = kernels.feed_queues(
        ops, sc_t | acted, chunk_of_c, busy_c, dead_c, rem_c, qsizes,
        row["qoff"], row["qlen"], qptr_c, qb_c, row["fsdt"],
        row["prepend_sizes"], pn_c,
    )
    # the handled chunk leaves the remaining-completions mask, so the
    # batch drain loop terminates after the deepest row's count
    cleared = row["_completed"] & ~(
        (xp.arange(K) == xp.expand_dims(k, -1)) & xp.expand_dims(trig, -1)
    )
    return dict(
        row, chunk_of=chunk_of_c, busy=busy_c, dead=dead_c, rem=rem_c,
        cap=cap_c, queue_bytes=qb_c, qptr=qptr_c, prepend_n=pn_c,
        sc_cursor=cursor_c, n_moves=nmoves_c, _completed=cleared,
    )


def _phase_tick(row: dict):
    """Phase C (runs only on sweeps where some ProMC row ticked): the
    streak state machine over the post-handler views; a firing row sets
    ``_moving`` for phase D."""
    ops = jax_ops()
    xp = ops.xp
    K = row["chunk_done"].shape[-1]
    pt = row["_tick"]
    bytes_rem, n_ch, eta = _views_row(
        ops, xp, row, row["chunk_of"], row["busy"], row["rem"],
        row["queue_bytes"], row["rate_est"], K,
    )
    live = ~row["chunk_done"] & (bytes_rem > 0)
    streak3, pf3, ps3, move, msrc, mdst = controllers.promc_tick(
        ops, eta, row["rate_est"], n_ch, live, row["streak"],
        row["pair_fast"], row["pair_slow"], row["promc_ratio"],
        row["promc_patience"],
    )
    return dict(
        row,
        streak=xp.where(pt, streak3, row["streak"]),
        pair_fast=xp.where(pt, pf3, row["pair_fast"]),
        pair_slow=xp.where(pt, ps3, row["pair_slow"]),
        _moving=pt & move,
        _msrc=xp.where(pt, msrc, 0),
        _mdst=xp.where(pt, mdst, 0),
    )


def _phase_move(row: dict, qsizes):
    """Phase D (runs only on sweeps where some ProMC row fired a move):
    one fast->slow channel move with the LIFO resume push, then feed."""
    ops = jax_ops()
    xp = ops.xp
    moving = row["_moving"]
    (
        chunk_of_c, busy_c, dead_c, rem_c, cap_c, qb_c, ps_sizes_c, pn_c,
        nmoves_c,
    ) = controllers.move_channel(
        ops, moving, row["_msrc"], row["_mdst"], row["chunk_of"],
        row["busy"], row["dead"], row["rem"], row["cap"],
        row["queue_bytes"], row["prepend_sizes"], row["prepend_n"],
        row["n_moves"], row["par"], row["cap_k"], row["setup_cost"],
    )
    busy_c, dead_c, rem_c, qptr_c, qb_c, pn_c = kernels.feed_queues(
        ops, moving, chunk_of_c, busy_c, dead_c, rem_c, qsizes,
        row["qoff"], row["qlen"], row["qptr"], qb_c, row["fsdt"],
        ps_sizes_c, pn_c,
    )
    return dict(
        row, chunk_of=chunk_of_c, busy=busy_c, dead=dead_c, rem=rem_c,
        cap=cap_c, queue_bytes=qb_c, qptr=qptr_c, prepend_n=pn_c,
        prepend_sizes=ps_sizes_c, n_moves=nmoves_c,
    )


#: the while_loop carry: everything the device may write. The read-only
#: tables (``_CONST_STATIC``) are *not* carried — they're closed over as
#: loop invariants — so the carry's double buffer, and the donation
#: aliasing below, cover exactly the state that changes.
_CARRY = _MUTABLE + _SCRATCH


def _device_rounds_fn(mut: dict, const: dict, qsizes, compact_floor: int):
    """Advance every runnable scenario to its own next Python decision
    point (or completion): vmapped sweeps inside lax.while_loop. Each
    sweep is phase A (always) plus controller phases B/C/D gated by
    batch-level ``lax.cond`` — completions, ProMC ticks, and fired moves
    are sparse across sweeps, so most iterations pay phase A alone.

    ``mut`` is the carried (and donatable) half; ``const`` the per-batch
    read-only tables, merged into the phase row-dicts each iteration and
    stripped before the carry closes. ``compact_floor`` is the *static*
    per-batch compaction floor (part of the program identity): it decides
    at trace time whether the early exit can ever lead anywhere.
    """
    import functools

    phase_a = jax.vmap(_phase_advance, in_axes=(0, None))
    phase_a_fifo = jax.vmap(
        functools.partial(_phase_advance, with_stack=False),
        in_axes=(0, None),
    )
    phase_b = jax.vmap(_phase_complete, in_axes=(0, None))
    phase_c = jax.vmap(_phase_tick)
    phase_d = jax.vmap(_phase_move, in_axes=(0, None))

    def runnable(st):
        return (
            ~st["done"]
            & (st["stall"] == _STALL_NONE)
            & (st["err"] == _ERR_NONE)
        )

    start_count = jnp.sum(runnable(mut))
    # the row axis is a static jit shape: whether an early exit can ever
    # lead anywhere is decided at trace time. Rows at (or below) this
    # batch's compaction floor can't shrink their device shape, so
    # exiting early would buy a full state download/re-upload for
    # nothing — those programs run to completion (or the sweep cap).
    # Above the floor the exit fraction follows the floor itself:
    # heterogeneous grid batches (deep ladder, floor 64) exit once half
    # the starting cohort has drained — straggler tails get compacted
    # down the rungs promptly — while all-static plane batches (shallow
    # ladder, floor 256) ride to a quarter cohort before syncing, since
    # their rows drain nearly together and each exit is a full state
    # download/re-upload.
    can_shrink = mut["done"].shape[0] > compact_floor
    exit_div = 2 if compact_floor < 256 else 4

    def cond(carry):
        st, it = carry
        n = jnp.sum(runnable(st))
        keep = (n > 0) & (it < _ROUND_CAP)
        if can_shrink:
            keep &= (exit_div * n > start_count) | (start_count <= _MIN_PAD)
        return keep

    def body(carry):
        st, it = carry
        st = {**st, **const}
        # resume files are rare: feed through the pure-FIFO phase-A
        # variant unless some row's stack holds one
        st = lax.cond(
            jnp.any(st["prepend_n"] > 0),
            lambda s: phase_a(s, qsizes),
            lambda s: phase_a_fifo(s, qsizes),
            st,
        )
        # drain completed chunks: each iteration handles every row's
        # lowest-index remaining completion (ascending k per row, the
        # host _complete_ctrl order) and clears it, so the loop runs
        # exactly as deep as the worst row's completion count — zero
        # iterations on the common no-completion sweep
        st = lax.while_loop(
            lambda s: jnp.any(s["_completed"] & s["_handler"][:, None]),
            lambda s: phase_b(s, qsizes),
            st,
        )
        st = lax.cond(
            jnp.any(st["_tick"]), phase_c, lambda s: s, st
        )
        st = lax.cond(
            jnp.any(st["_moving"]), lambda s: phase_d(s, qsizes),
            lambda s: s, st,
        )
        return {k: st[k] for k in _CARRY}, it + 1

    state, iters = lax.while_loop(cond, body, (dict(mut), 0))
    return state, iters


#: the undonated loop (exact pre-executor semantics: inputs stay live).
#: ``compact_floor`` is static: two batches with identical shapes but
#: different floors are different programs (the early-exit clause folds
#: at trace time)
_device_rounds = jax.jit(_device_rounds_fn, static_argnums=3)
#: the donated twin: the mutable carry updates in place, halving the
#: loop's peak device footprint. The driver re-uploads from host NumPy
#: every round, so donated inputs are never read again.
_device_rounds_donated = jax.jit(
    _device_rounds_fn, donate_argnums=0, static_argnums=3
)


def _row_demand(row: dict):
    """Per-row inputs to the cross-row coupling step: the uncoupled
    disk/bandwidth pool and the coupled *demand* — that pool clipped to
    the row's transferring channel caps, totalled with ``caps_total``
    (waterfill's own cumsum-of-sorted reduction, so an unsaturated grant
    reproduces the uncoupled water-fill bit for bit). Mirrors the phase-A
    prologue's physics read-only; the sweep itself recomputes nothing
    from these."""
    ops = jax_ops()
    xp = ops.xp
    runnable = (
        ~row["done"]
        & (row["stall"] == _STALL_NONE)
        & (row["err"] == _ERR_NONE)
    )
    transferring = row["busy"] & (row["dead"] <= _EPS)
    if row["prof_t"].shape[-1] == 1:
        eff_bw = row["bw"]
    else:
        prof_at = xp.sum(row["prof_t"] <= row["t"]) - 1
        mult = row["prof_mult"][xp.maximum(prof_at, 0)]
        eff_bw = row["bw"] * xp.where(prof_at >= 0, mult, 1.0)
    pool = kernels.disk_pool(
        ops, xp.sum(transferring), eff_bw, row["disk_rate"],
        row["sat_cc"], row["contention"],
    )
    caps_eff = xp.where(transferring, row["cap"], 0.0)
    demand = xp.minimum(pool, kernels.caps_total(ops, caps_eff))
    return runnable, pool, demand


def _row_horizon(row: dict, pool):
    """One row's own event horizon under an externally granted ``pool``
    — the per-member input to the group's lockstep minimum. Phase A then
    recomputes the identical dt and caps it with the group minimum."""
    ops = jax_ops()
    xp = ops.xp
    transferring = row["busy"] & (row["dead"] <= _EPS)
    if row["prof_t"].shape[-1] == 1:
        next_prof = xp.inf
    else:
        next_prof = xp.min(
            xp.where(row["prof_t"] > row["t"], row["prof_t"], xp.inf)
        )
    rates = kernels.waterfill(
        ops, xp.where(transferring, row["cap"], 0.0), pool
    )
    return kernels.event_horizon(
        ops,
        xp.minimum(row["next_tick"] - row["t"], next_prof - row["t"]),
        row["busy"], row["dead"], transferring, row["rem"], rates,
    )


def _device_rounds_coupled_fn(
    mut: dict, const: dict, qsizes, fab: dict, compact_floor: int
):
    """The shared-fabric twin of :func:`_device_rounds_fn`: identical
    vmapped phases, but every sweep starts with one cross-row coupling
    step — per-row demands (vmapped), one batch ``waterfill_coupled``
    over the (links x rows) membership table, per-row horizons under the
    grants (vmapped), and a segment-min over group ids for the lockstep
    dt — all inside the fused ``while_loop``, so coupled sweeps stay
    zero-host-round.

    ``fab`` carries ``gid`` (rows,) int64 (-1 == uncoupled, pad rows
    included), ``member`` (L, rows) bool, ``link_cap`` (L,) f64 (pad
    links hold cap 0 and no members — their water level is +inf, which
    the member-min ignores), and ``gslot`` (G,) f64 zeros whose only job
    is giving the group axis a static shape for the segment-min.

    No early-exit clause: coupled batches never compact (a done tenant
    already releases its link share via zero demand, and a frozen row
    set keeps the membership table and one compiled program for the
    whole run), so exiting early buys a host sync for nothing.
    """
    import functools

    ops = jax_ops()
    phase_a = jax.vmap(
        functools.partial(_phase_advance, coupled=True), in_axes=(0, None)
    )
    phase_a_fifo = jax.vmap(
        functools.partial(_phase_advance, with_stack=False, coupled=True),
        in_axes=(0, None),
    )
    phase_b = jax.vmap(_phase_complete, in_axes=(0, None))
    phase_c = jax.vmap(_phase_tick)
    phase_d = jax.vmap(_phase_move, in_axes=(0, None))
    demand_v = jax.vmap(_row_demand)
    horizon_v = jax.vmap(_row_horizon)

    gid = fab["gid"]
    member = fab["member"]
    link_cap = fab["link_cap"]
    G = fab["gslot"].shape[0]
    gclip = jnp.clip(gid, 0, G - 1)
    in_group = gid >= 0

    def runnable(st):
        return (
            ~st["done"]
            & (st["stall"] == _STALL_NONE)
            & (st["err"] == _ERR_NONE)
        )

    def cond(carry):
        st, it = carry
        return (jnp.sum(runnable(st)) > 0) & (it < _ROUND_CAP)

    def body(carry):
        st, it = carry
        st = {**st, **const}
        live, pool, demand = demand_v(st)
        grant, _ = kernels.waterfill_coupled(
            ops, jnp.where(live & in_group, demand, 0.0), member, link_cap
        )
        pool_ovr = jnp.where(in_group, grant, pool)
        dt_own = horizon_v(st, pool_ovr)
        g_dt = (
            jnp.full((G,), jnp.inf)
            .at[gclip]
            .min(jnp.where(live & in_group, dt_own, jnp.inf))
        )
        st["_pool_ovr"] = pool_ovr
        st["_dt_ovr"] = jnp.where(in_group, g_dt[gclip], jnp.inf)
        st = lax.cond(
            jnp.any(st["prepend_n"] > 0),
            lambda s: phase_a(s, qsizes),
            lambda s: phase_a_fifo(s, qsizes),
            st,
        )
        st = lax.while_loop(
            lambda s: jnp.any(s["_completed"] & s["_handler"][:, None]),
            lambda s: phase_b(s, qsizes),
            st,
        )
        st = lax.cond(jnp.any(st["_tick"]), phase_c, lambda s: s, st)
        st = lax.cond(
            jnp.any(st["_moving"]), lambda s: phase_d(s, qsizes),
            lambda s: s, st,
        )
        return {k: st[k] for k in _CARRY}, it + 1

    state, iters = lax.while_loop(cond, body, (dict(mut), 0))
    return state, iters


#: the coupled loop and its donated twin. ``fab`` rides the jit
#: signature through its array shapes (L, G, rows) — bucketed to the
#: pow2 ladder by ``_upload_fabric`` so the program count stays bounded.
_device_rounds_coupled = jax.jit(
    _device_rounds_coupled_fn, static_argnums=4
)
_device_rounds_coupled_donated = jax.jit(
    _device_rounds_coupled_fn, donate_argnums=0, static_argnums=4
)


# ------------------------------------------------------------------ #
# AOT warm-start: pre-compile the canonical-signature ladder
# ------------------------------------------------------------------ #

#: per-key shape templates over the canonical signature axes
#: (rows, C, K, P, B, T, Q); ``signature_shapes`` instantiates them.
#: Kept explicit — and honest via the test that diffs it against a real
#: ``_upload`` — because AOT avals must match runtime uploads exactly.
_F64, _I64, _BOOL = np.float64, np.int64, np.bool_
_SHAPE_TABLE = {
    # mutable scalars (rows,)
    "t": ("S", _F64), "next_tick": ("S", _F64), "finish_t": ("S", _F64),
    "tl_last_t": ("S", _F64), "tl_last_rate": ("S", _F64),
    "done": ("S", _BOOL), "fin_any": ("S", _BOOL),
    "n_events": ("S", _I64), "stall": ("S", _I64), "err": ("S", _I64),
    "streak": ("S", _I64), "pair_fast": ("S", _I64),
    "pair_slow": ("S", _I64), "sc_cursor": ("S", _I64),
    "n_moves": ("S", _I64), "tl_len": ("S", _I64),
    "tl_stride": ("S", _I64), "tl_seen": ("S", _I64),
    # channel axis (rows, C)
    "dead": ("SC", _F64), "rem": ("SC", _F64), "cap": ("SC", _F64),
    "busy": ("SC", _BOOL), "chunk_of": ("SC", _I64),
    # chunk axis (rows, K)
    "chunk_done": ("SK", _BOOL), "completed_at": ("SK", _F64),
    "delivered": ("SK", _F64), "delivered_at_tick": ("SK", _F64),
    "rate_est": ("SK", _F64), "queue_bytes": ("SK", _F64),
    "qptr": ("SK", _I64), "prepend_n": ("SK", _I64),
    # resume stack + timeline ring
    "prepend_sizes": ("SKP", _F64),
    "tl_t": ("ST", _F64), "tl_rate": ("ST", _F64),
    # per-sweep scratch
    "_completed": ("SK", _BOOL), "_handler": ("S", _BOOL),
    "_tick": ("S", _BOOL), "_moving": ("S", _BOOL),
    "_msrc": ("S", _I64), "_mdst": ("S", _I64),
    # read-only tables
    "max_time": ("S", _F64), "tick_period": ("S", _F64),
    "bw": ("S", _F64), "disk_rate": ("S", _F64),
    "contention": ("S", _F64), "setup_cost": ("S", _F64),
    "promc_ratio": ("S", _F64),
    "trivial_tick": ("S", _BOOL), "trivial_complete": ("S", _BOOL),
    "record_timeline": ("S", _BOOL),
    "sat_cc": ("S", _I64), "kind": ("S", _I64),
    "promc_patience": ("S", _I64), "n_chunks": ("S", _I64),
    "cap_need": ("S", _I64),
    "qoff": ("SK", _I64), "qlen": ("SK", _I64), "sc_order": ("SK", _I64),
    "conc": ("SK", _I64), "par": ("SK", _I64), "nfiles": ("SK", _I64),
    "fsdt": ("SK", _F64), "cap_k": ("SK", _F64), "avg_fs_k": ("SK", _F64),
    "prof_t": ("SB", _F64), "prof_mult": ("SB", _F64),
}


def signature_shapes(
    sig: Tuple[int, ...], device=None
) -> Tuple[dict, dict, jax.ShapeDtypeStruct]:
    """``(mut, const, qsizes)`` aval pytrees for one canonical signature
    ``(rows, C, K, P, B, T, Q)`` — exactly what :meth:`JaxFabricSimulation.
    _upload` produces for a batch occupying that signature, so
    ``jit(...).lower(*signature_shapes(sig)).compile()`` pre-builds the
    very executable the runtime call will look up."""
    rows, C, K, P, B, T, Q = sig
    dims = {
        "S": (rows,), "SC": (rows, C), "SK": (rows, K),
        "SKP": (rows, K, P), "ST": (rows, T), "SB": (rows, B),
    }
    sharding = None
    if device is not None:
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(device)

    def aval(shape, dt):
        if sharding is not None:
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
        return jax.ShapeDtypeStruct(shape, dt)

    mut = {k: aval(dims[_SHAPE_TABLE[k][0]], _SHAPE_TABLE[k][1])
           for k in _CARRY}
    const = {k: aval(dims[_SHAPE_TABLE[k][0]], _SHAPE_TABLE[k][1])
             for k in _CONST_STATIC}
    return mut, const, aval((Q,), _F64)


_AOT_LOCK = threading.Lock()
#: ``(sig, device, donate, floor) -> jax.stages.Compiled`` (``None``
#: records a warm that raised; the error reaches the warm's caller)
_AOT_CACHE: dict = {}
#: in-flight warms: waiters block on the event instead of re-compiling
_AOT_PENDING: dict = {}


# ------------------------------------------------------------------ #
# Trace cache: serialized jax.export blobs alongside the XLA cache
# ------------------------------------------------------------------ #
#
# The persistent XLA cache only skips the *backend compile*; every fresh
# process still pays ~1 s of Python trace + StableHLO lowering per
# program before the cache can even be consulted. ``jax.export``
# captures exactly that lowered artifact, so warm processes deserialize
# the StableHLO from disk (~15 ms) and hand it straight to XLA — whose
# persistent cache then returns the executable — instead of re-tracing.
# Cold and warm paths both compile the *exported* call so they share one
# HLO identity (and one XLA cache entry) per program.
#
# Blobs are keyed on the backend platform, the signature and a digest of
# the jax version and every source file under this package (controllers/
# and kernels/ included) — any edit to the traced code (or the constants
# it closes over) invalidates the whole trace cache. A blob exported for
# another platform is a miss. Donated programs are excluded (donation
# metadata does not survive the export round trip); they compile from
# the jit twin, which the persistent XLA cache serves directly.

_EXPORT_DIGEST: Optional[str] = None


def _export_digest() -> str:
    """Digest of everything the device-loop trace can depend on: the jax
    version plus the bytes of every ``.py`` file under this package."""
    global _EXPORT_DIGEST
    if _EXPORT_DIGEST is None:
        import hashlib

        h = hashlib.sha256(jax.__version__.encode())
        pkg = os.path.dirname(os.path.abspath(__file__))
        for root, dirs, files in os.walk(pkg):
            dirs.sort()
            for name in sorted(files):
                if name.endswith(".py"):
                    path = os.path.join(root, name)
                    with open(path, "rb") as f:
                        h.update(os.path.relpath(path, pkg).encode())
                        h.update(f.read())
        _EXPORT_DIGEST = h.hexdigest()[:32]
    return _EXPORT_DIGEST


def _export_path(sig, floor: int) -> Optional[str]:
    """Blob path for one signature on the current backend, or None when
    no persistent cache directory is configured (no point caching traces
    the process can't amortize across runs)."""
    base = cache_dir()
    if not base:
        return None
    name = "rounds-{}-{}-f{}-{}.stablehlo".format(
        jax.default_backend(), "x".join(str(int(x)) for x in sig),
        int(floor), _export_digest(),
    )
    return os.path.join(base, "exports", name)


def _exported_rounds(sig, shapes, floor: int):
    """The exported device loop for ``sig``: deserialized from the blob
    cache when present, else traced now and written back (best effort).
    ``shapes`` must be the device-free avals — sharding is applied later
    at compile time, keeping one blob valid for every device."""
    from jax import export as jax_export

    path = _export_path(sig, floor)
    if path is not None and os.path.exists(path):
        with open(path, "rb") as f:
            exp = jax_export.deserialize(f.read())
        if jax.default_backend() in exp.platforms:
            return exp
    exp = jax_export.export(_device_rounds)(*shapes, int(floor))
    if path is not None:
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = "{}.tmp.{}".format(path, os.getpid())
            with open(tmp, "wb") as f:
                f.write(exp.serialize())
            os.replace(tmp, path)
        except OSError:
            pass  # a read-only cache dir only costs the next process a trace
    return exp


def _aot_key(sig, device, donate, floor):
    return (tuple(int(x) for x in sig), device, bool(donate), int(floor))


def warm_signature(
    sig, device=None, donate: Optional[bool] = None,
    floor: int = COMPACT_FLOOR,
) -> bool:
    """AOT-compile the device loop for one canonical signature (exactly
    once per ``(sig, device, donate)`` process-wide; concurrent callers
    wait). Returns True if this call did the compile. The executor warms
    each chunk's signature — and its compaction rungs — from a background
    thread while earlier chunks compute, so by the time a chunk reaches
    the device its executable already exists and the ~1 s/signature
    Python retrace never lands on the critical path."""
    donate = donation_enabled(donate)
    key = _aot_key(sig, device, donate, floor)
    with _AOT_LOCK:
        if key in _AOT_CACHE:
            return False
        ev = _AOT_PENDING.get(key)
        if ev is not None:
            owner = False
        else:
            ev = threading.Event()
            _AOT_PENDING[key] = ev
            owner = True
    if not owner:
        ev.wait()
        return False
    compiled = None
    try:
        # x64 is thread-local: the warm thread needs its own context so
        # the traced avals match the runtime's f64 uploads
        with x64():
            if not donate and cache_dir():
                exp = _exported_rounds(
                    sig, signature_shapes(sig, None), floor
                )
                compiled = (
                    jax.jit(exp.call)
                    .lower(*signature_shapes(sig, device))
                    .compile()
                )
            else:
                fn = _device_rounds_donated if donate else _device_rounds
                compiled = fn.lower(
                    *signature_shapes(sig, device), int(floor)
                ).compile()
    finally:
        with _AOT_LOCK:
            _AOT_CACHE[key] = compiled
            _AOT_PENDING.pop(key, None)
        ev.set()
    return compiled is not None


def _aot_lookup(sig, device, donate, floor):
    """The compiled executable for a signature, waiting out an in-flight
    warm (the warm thread is already doing the same compile the jit
    fallback would pay); None if never warmed or the warm failed."""
    key = _aot_key(sig, device, donate, floor)
    with _AOT_LOCK:
        exe = _AOT_CACHE.get(key)
        ev = _AOT_PENDING.get(key)
    if exe is not None:
        return exe
    if ev is not None:
        ev.wait()
        with _AOT_LOCK:
            return _AOT_CACHE.get(key)
    return None


def reset_aot_cache() -> None:
    with _AOT_LOCK:
        _AOT_CACHE.clear()


def compiled_program_count() -> int:
    """Compiled executables for the device loop across all entry points:
    the undonated jit, the donated twin, and the AOT warm cache — the
    bench's compile-tax telemetry and the bucketing tests count this."""
    with _AOT_LOCK:
        aot = sum(1 for v in _AOT_CACHE.values() if v is not None)
    return (
        aot
        + _device_rounds._cache_size()
        + _device_rounds_donated._cache_size()
        + _device_rounds_coupled._cache_size()
        + _device_rounds_coupled_donated._cache_size()
    )


class JaxFabricSimulation(FabricSimulation):
    """FabricSimulation driven by the jit/vmap device loop.

    Host state (the parent's NumPy arrays) stays canonical; each round
    uploads it, lets the device run every scenario to its next decision
    point (usually: completion), downloads, and replays the parent's
    Python half for parked rows. Custom-scheduler bookkeeping (callback
    objects, views) is inherited unchanged.

    ``device`` pins every upload (and the AOT executable) to one
    ``jax.Device`` — the executor round-robins chunks across
    ``jax.devices()`` this way; None uses the default placement.
    ``donate`` overrides :func:`donation_enabled` for this batch.
    """

    #: the executor passes ``device=`` only to drivers that advertise it
    supports_device_placement = True

    def __init__(
        self,
        sims: Sequence[Simulation],
        names: Optional[Sequence[str]] = None,
        *,
        device=None,
        donate: Optional[bool] = None,
        **kwargs,
    ):
        super().__init__(sims, names=names, **kwargs)
        self.device = device
        self.donate = donation_enabled(donate)

    # -------------------------------------------------------------- #

    def _row_arrays(self) -> tuple:
        return super()._row_arrays() + ("_stall",)

    def _pad_rows(self) -> int:
        """Row count uploaded to the device: next power of two >= live rows
        (min ``_MIN_PAD``). Padded rows are born ``done`` and never sweep;
        ``_pad_floor`` (set by the one-rung compaction policy below) keeps
        the post-compaction shape on a deterministic ladder rung instead
        of wherever the live count happened to land."""
        return bucket(max(self.S, getattr(self, "_pad_floor", 0)), _MIN_PAD)

    def _padded(self, key: str, arr: np.ndarray, pad: int):
        if pad:
            fill = np.ones if key == "done" else np.zeros
            arr = np.concatenate(
                [arr, fill((pad,) + arr.shape[1:], dtype=arr.dtype)]
            )
        if self.device is not None:
            return jax.device_put(arr, self.device)
        return jnp.asarray(arr)

    def _to_device(self, arr):
        if self.device is not None:
            return jax.device_put(arr, self.device)
        return jnp.asarray(arr)

    def _upload(self) -> Tuple[dict, dict]:
        """Fresh device buffers for one round: ``(mut, const)``. ``mut``
        is rebuilt from host NumPy every round — which is what makes
        donating it safe — while the read-only ``const`` tables are
        device-cached until compaction/growth reshapes the rows."""
        pad = self._pad_rows() - self.S
        rows = self.S + pad
        mut = {}
        for key in _MUTABLE:
            if key == "stall":
                arr = self._stall
            elif key == "err":
                arr = np.zeros(self.S, dtype=np.int64)
            else:
                arr = getattr(self, key)
            mut[key] = self._padded(key, arr, pad)
        # per-sweep scratch threaded between the device phases
        mut["_completed"] = self._to_device(
            np.zeros((rows, self.K), dtype=bool)
        )
        for key in ("_handler", "_tick", "_moving"):
            mut[key] = self._to_device(np.zeros(rows, dtype=bool))
        for key in ("_msrc", "_mdst"):
            mut[key] = self._to_device(np.zeros(rows, dtype=np.int64))
        # statics are immutable for a given row set: cache on device and
        # rebuild only when compaction (or channel growth) reshapes rows
        cache_key = (self.S, self.C, self.P, pad)
        if getattr(self, "_static_cache_key", None) != cache_key:
            self._static_cache = {
                key: self._padded(key, getattr(self, key), pad)
                for key in _CONST_STATIC
            }
            self._static_cache_key = cache_key
        return mut, self._static_cache

    def _upload_fabric(self) -> dict:
        """Device form of the batch's coupling arrays, padded row-wise to
        the device row bucket (pad rows: gid -1, no memberships) and with
        the link / group axes bucketed to the pow2 ladder (pad links hold
        cap 0 and no members — water level +inf, invisible to the
        member-min; pad group slots only ever hold the +inf identity).
        Built once per run: coupled batches never compact or grow, so the
        shapes — and the one compiled coupled program — stay fixed."""
        rows = self._pad_rows()
        S = self.S
        L = int(self.link_cap.shape[0])
        Lp = bucket(max(L, 1), 2)
        Gp = bucket(max(self._n_groups, 1), 2)
        gid = np.full(rows, -1, dtype=np.int64)
        gid[:S] = self.group_id
        member = np.zeros((Lp, rows), dtype=bool)
        member[:L, :S] = self.link_member
        caps = np.zeros(Lp, dtype=np.float64)
        caps[:L] = self.link_cap
        return {
            "gid": self._to_device(gid),
            "member": self._to_device(member),
            "link_cap": self._to_device(caps),
            "gslot": self._to_device(np.zeros(Gp, dtype=np.float64)),
        }

    def _rounds_signature(self) -> Tuple[int, ...]:
        """The canonical signature of the *current* device shape (it
        walks down the rows ladder as compaction fires) — the AOT-cache
        key the next ``_device_call`` will look up."""
        return (
            self._pad_rows(), self.C, self.K, self.P,
            self.prof_t.shape[1], self.tl_t.shape[1], self._q_pad,
        )

    def _device_call(self, mut: dict, const: dict, qsizes):
        """One device round through the best available executable: the
        AOT-warmed one when the executor pre-built it, else the jit twin
        matching this batch's donation mode.

        Coupled batches never consult the AOT cache — the executor warms
        *uncoupled* signatures, and a shape-compatible uncoupled
        executable would silently run the wrong physics — they go
        straight to the coupled jit twins.
        """
        floor = self.compact_floor()
        if self.coupled:
            fn = (
                _device_rounds_coupled_donated if self.donate
                else _device_rounds_coupled
            )
            return fn(mut, const, qsizes, self._fab_dev, floor)
        exe = _aot_lookup(
            self._rounds_signature(), self.device, self.donate, floor
        )
        if exe is not None:
            return exe(mut, const, qsizes)
        fn = _device_rounds_donated if self.donate else _device_rounds
        return fn(mut, const, qsizes, floor)

    def _download(self, state: dict) -> None:
        for key in _MUTABLE:
            if key == "err":
                continue
            # np.array (not asarray): device buffers are zero-copy
            # read-only views, and the host half mutates these in place.
            # Pad rows are dropped on the host: slicing the device array
            # would compile a slice program per (shape, row count, device)
            arr = np.array(state[key])[: self.S]
            setattr(self, "_stall" if key == "stall" else key, arr)
        err = np.asarray(state["err"])[: self.S]
        if err.any():
            s = int(np.flatnonzero(err)[0])
            if err[s] == _ERR_MAXTIME:
                raise RuntimeError(
                    f"batch scenario {self.rt[s].name!r} exceeded max_time="
                    f"{self.max_time[s]}s (t={self.t[s]:.1f})"
                )
            r = self.rt[s]
            live = np.flatnonzero(~self.chunk_done[s])
            raise RuntimeError(
                f"scheduler {r.scheduler.name} stranded chunks "
                f"{[r.chunks[int(k)].name for k in live]} in {r.name!r}"
            )

    # -------------------------------------------------------------- #

    def run(self) -> List[SimResult]:
        all_rt = list(self.rt)
        self.start()
        # pre-size the channel and resume-stack axes from the closed-form
        # worst-case bound: every built-in scheduler then fits the device
        # shape for its whole run, so the capacity-guard park classes
        # (SC open waves, resume-stack overflow) can never fire
        need_c, need_p = self.capacity_need()
        while self.C < need_c:
            self._grow()
        while self.P < need_p:
            self._grow_prepend()
        with x64():
            self._drive()
        return [self._result(r) for r in all_rt]

    def _maybe_compact(self) -> None:
        """Compaction policy for the device loop: one deterministic
        quarter-step rung, then stop.

        The parent compacts whenever half the batch is done — right for
        NumPy, where a rebuild is free and sweep cost tracks live rows.
        Here every rebuild that shrinks the padded row bucket is a fresh
        jit signature, and each signature costs seconds of *retrace* per
        process even when the persistent cache supplies the compiled
        executable (tracing is Python, the cache only skips XLA).
        Walking every pow2 rung (1024 -> 512 -> ... -> 16) spent more
        wall time tracing than the narrower sweeps saved. So: when the
        live rows fit a 4x smaller pad, compact to exactly ``pad // 4``
        (pinned via ``_pad_floor`` even if far fewer rows survive) and
        stop at this batch's :meth:`compact_floor` device shape — a
        1024-row grid chunk occupies exactly {1024, 256, 64}, never a
        stray 512/128 rung from wherever the live count happened to
        land, and an all-static candidate-plane chunk stops at 256
        (its rows drain together; the narrow tail rungs only buy extra
        host syncs there).
        """
        if self.coupled:
            # frozen row set: membership table, group ids, and the one
            # compiled coupled program stay valid for the whole run
            return
        floor = self.compact_floor()
        live = self.S - int(self.done.sum())
        pad = self._pad_rows()
        if pad > floor and bucket(live, _MIN_PAD) * 4 <= pad:
            self._pad_floor = max(pad // 4, floor)
            self._compact()

    def _drive(self) -> None:
        self._stall = np.zeros(self.S, dtype=np.int64)
        # accumulate host-sync telemetry privately and merge once at the
        # end: under the pipelined executor several batches drive
        # concurrently, and per-increment writes to the module-global
        # counters would interleave (same totals, but torn reads for any
        # observer); one locked merge per run keeps SYNC_STATS exactly
        # serial-equivalent
        stats = {k: 0 for k in SYNC_STATS}
        stats["runs"] = 1
        stats["scenarios"] = self.S
        # the flat file-size buffer is a jit-signature axis too — its raw
        # length is the batch's total file count, different for every
        # chunk, which made every chunk a fresh XLA compile. Zero-pad to
        # the quarter-step ladder; the feed kernel only reads qoff+qptr <
        # qoff+qlen, so the pad slots are dead weight (8 B each), not
        # semantics
        self._q_pad = qsizes_pad(self.qsizes.shape[0])
        qsizes_dev = self._to_device(
            np.concatenate(
                [self.qsizes, np.zeros(self._q_pad - self.qsizes.shape[0])]
            )
        )
        if self.coupled:
            self._fab_dev = self._upload_fabric()
        try:
            while not self.done.all():
                progressed = False
                runnable = ~self.done & (self._stall == _STALL_NONE)
                if runnable.any():
                    mut, const = self._upload()
                    state, iters = self._device_call(mut, const, qsizes_dev)
                    # donated inputs are dead past this point; the next
                    # round re-uploads from the host arrays _download
                    # refreshes, so nothing reads them again
                    del mut
                    t0 = time.perf_counter()
                    self._download(state)
                    stats["download_wall_s"] += time.perf_counter() - t0
                    stats["rounds"] += 1
                    progressed = int(iters) > 0
                post_rows = ~self.done & (self._stall == _STALL_POST)
                if post_rows.any():
                    # custom-scheduler callbacks (or a capacity guard a
                    # custom subclass defeated): replay the NumPy
                    # transition half
                    stats["replay_rounds"] += 1
                    stats["post_row_replays"] += int(post_rows.sum())
                    self._post(post_rows)
                    self._stall[post_rows] = _STALL_NONE
                    progressed = True
                if not progressed:
                    raise RuntimeError(
                        "jax fabric backend made no progress; device loop "
                        f"exited with {int(runnable.sum())} runnable rows"
                    )
                self._maybe_compact()
        finally:
            _merge_sync_stats(stats)
