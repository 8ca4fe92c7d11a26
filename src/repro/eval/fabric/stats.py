"""Shared pipeline telemetry: host-sync counters and wall-clock spans.

Historically :data:`SYNC_STATS` lived in :mod:`repro.eval.fabric.
jax_backend`; the executor's prep/compute wall instrumentation needs the
same accumulator from NumPy-only runs, which must not import jax. The
dict (and its lock) moved here; ``jax_backend`` re-imports the *same*
objects, so ``jax_backend.SYNC_STATS`` keeps working and
:func:`reset_sync_stats` (in-place) resets both views at once.

Counter keys keep their zero-host-round contract (see the jax backend
docstring); ``iterations`` sums the device ``while_loop``'s iterations
over rounds, and ``state_syncs`` counts whole-state copies back to the
host. The ``*_wall_s`` keys are filled by :func:`span`, one named span
each:

  ======================  ===================  ===========================
  span                    wall key             what it times
  ======================  ===================  ===========================
  ``fabric.build``        ``build_wall_s``     host chunk construction
  ``fabric.run``          ``compute_wall_s``   one driver ``run()``
  ``fabric.upload``       ``upload_wall_s``    a round's host buffers built
                                               and enqueued to the device
  ``fabric.device``       ``device_wall_s``    a round's device call until
                                               its outputs are ready
  ``fabric.download``     ``download_wall_s``  a round's ready flags
                                               read, and each whole-state
                                               sync, copied to the host
  ======================  ===================  ===========================

The last three are jax-backend rounds, nested inside ``fabric.run``.
Between rounds the loop state stays on the device: ``upload_wall_s``
holds only the rounds that start from the host arrays (a run's first,
and the first after a host replay or a compaction), and
``download_wall_s`` transfer and host copy only, the device's own time
being in ``device_wall_s``.

Counters other than the walls:

  ======================  =============================================
  counter                 what it counts
  ======================  =============================================
  ``rounds``              device ``while_loop`` entries
  ``iterations``          loop iterations over those rounds
  ``state_syncs``         whole-state copies to the host (a replay, a
                          compaction, an error, a run's end);
                          ``rounds - state_syncs`` rounds resumed from
                          the device-resident state
  ``replay_rounds``       rounds ended by a host ``_post`` replay
  ``post_row_replays``    parked rows replayed
  ``scenarios``/``runs``  rows and driver runs
  ``profile_steps``       bandwidth-profile steps after t = 0 and no
                          later than the row's finish time, over real
                          rows; read from the whole-state syncs a run
                          makes anyway (before a compaction, at its end);
                          0 for static paths
  ``channel_slots``       real rows times the padded channel axis C,
                          counted once per driver run as the batch is
                          built
  ``channel_slots_live``  the most channels each of those rows can hold
                          (its closed-form worst case, ``cap_need``),
                          summed: ``1 - live / slots`` is the share of
                          the channel axis no row can use
  ======================  =============================================

Wall keys are float seconds and overlap freely (several prep/compute
threads accumulate concurrently), so they measure aggregate thread-time
per phase, not elapsed wall clock; their ratio is what the breakdown under ``runner --verbose`` reports.
"""
from __future__ import annotations

import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Optional

#: wall-clock accumulator keys: float seconds, thread-time semantics.
#: Everything else in SYNC_STATS is an exact integer counter — tests that
#: pin counter equality across execution modes must exclude these.
WALL_KEYS = frozenset(
    {
        "build_wall_s", "compute_wall_s", "upload_wall_s",
        "device_wall_s", "download_wall_s",
    }
)

#: host-sync telemetry, accumulated across runs (reset with
#: :func:`reset_sync_stats`); the eval-matrix bench derives its
#: device-syncs-per-scenario figure from this. ``rounds`` counts device
#: while_loop entries (compaction/straggler re-entries included) and
#: ``iterations`` the loop iterations they ran, ``state_syncs`` the
#: whole-state copies back to the host; ``replay_rounds`` counts
#: only rounds that ended with the host replaying ``_post`` for parked
#: rows, and ``post_row_replays`` the parked rows themselves — both
#: exactly 0 for built-in schedulers, the zero-host-round invariant CI
#: gates on.
SYNC_STATS = {
    "rounds": 0,
    "iterations": 0,
    "state_syncs": 0,
    "replay_rounds": 0,
    "post_row_replays": 0,
    "scenarios": 0,
    "runs": 0,
    "profile_steps": 0,
    "channel_slots": 0,
    "channel_slots_live": 0,
    "build_wall_s": 0.0,
    "compute_wall_s": 0.0,
    "upload_wall_s": 0.0,
    "device_wall_s": 0.0,
    "download_wall_s": 0.0,
}

#: guards SYNC_STATS: under the pipelined executor several driver
#: instances finish concurrently, and each merges its private per-run
#: counters in one locked step — interleaved chunks therefore report
#: exactly the totals serial execution would
_SYNC_LOCK = threading.Lock()


def reset_sync_stats() -> None:
    with _SYNC_LOCK:
        for k in SYNC_STATS:
            SYNC_STATS[k] = 0.0 if k in WALL_KEYS else 0


def _merge_sync_stats(local: dict) -> None:
    with _SYNC_LOCK:
        for k, v in local.items():
            SYNC_STATS[k] += v


@contextmanager
def span(name: str, key: str, into: Optional[dict] = None):
    """Time the enclosed block into wall key ``key`` and, once jax is
    imported, open a ``jax.profiler.TraceAnnotation`` named ``name``
    around it: inside a profiler trace the span lands on the host plane,
    on the line of the thread that opened it, on the device planes'
    clock. ``into`` is a private per-run accumulator merged later with
    :func:`_merge_sync_stats`; without it the time goes straight into
    :data:`SYNC_STATS` (thread-safe). NumPy-only runs never import jax,
    so they time without annotating."""
    jax = sys.modules.get("jax")
    ann = jax.profiler.TraceAnnotation(name) if jax else nullcontext()
    with ann:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if into is not None:
                into[key] += dt
            else:
                with _SYNC_LOCK:
                    SYNC_STATS[key] += dt
