"""Batched fluid fast-path driver over the backend-neutral fabric kernels.

The event-driven :class:`repro.core.simulator.Simulation` spends its time in
per-event Python: water-filling over channels, horizon search, per-channel
advancement, queue feeding. :class:`FabricSimulation` runs the *same* event
semantics for S scenarios at once — channel state lives in (S, C) arrays,
per-chunk queue state in (S, K) arrays over one flat file-size buffer, and
all array math goes through :mod:`repro.eval.fabric.kernels` against an
:class:`repro.eval.fabric.shim.ArrayOps` namespace. Each outer sweep
advances every live scenario to its own next event simultaneously;
scenarios are independent, so their clocks drift apart freely.

The controller decision layer is batched too: SC / MC / ProMC rows run
their tick and chunk-completion logic through the array kernels of
:mod:`repro.eval.fabric.controllers` — the ProMC streak state machine,
laggard-ETA discounting, and channel Open/Close/Move transitions are
masked (S,)-row updates, with resume files held on a device-friendly
LIFO stack ``(S, K, P)`` instead of host lists. Python remains only for
*custom* scheduler subclasses (anything that is not exactly one of the
three paper controllers or a no-op baseline), which still go through the
scalar callback protocol.

A sweep is split into :meth:`FabricSimulation._advance` (rates, horizon,
fluid byte movement) and :meth:`FabricSimulation._post` (feed, completions,
tick, scenario-done detection); the JAX backend fuses both halves into its
on-device loop — timeline recording included, via the shared
``kernels.timeline_push`` ring buffer — and reuses ``_post`` only for
rows it parks (custom controllers; capacity-guard edges survive as an
assertion-guarded fallback that the pre-sized axes from
:meth:`capacity_need` make unreachable for built-in schedulers).

The fidelity contract against ``Simulation.step`` lives in the package
docstring (:mod:`repro.eval.fabric`); ``eval.difftest`` enforces it on
every matrix scenario.
"""
from __future__ import annotations

import math
import os
from typing import List, Optional, Sequence

import numpy as np

from repro.core import netmodel
from repro.core.schedulers import (
    Close,
    ChunkView,
    Move,
    MultiChunkScheduler,
    Open,
    ProActiveMultiChunkScheduler,
    Scheduler,
    SingleChunkScheduler,
)
from repro.core.simulator import SimResult, Simulation
from repro.core.types import TransferParams

from . import controllers, kernels
from .bucketing import COMPACT_FLOOR, PROFILE_PAD_FLOOR, bucket
from .reference import resume_file
from .shared import resolve_fabric
from .shim import NO_CHUNK, ArrayOps, numpy_ops

_EPS = 1e-12
_NO_CHUNK = NO_CHUNK

#: controller kinds the vectorized decision layer understands; anything
#: else (KIND_CUSTOM) drives the scalar callback protocol on the host.
#: KIND_STATIC (the autotuner's fixed-parameter candidate rows) behaves
#: exactly like a trivial baseline at runtime — initial Opens, then no
#: actions — but is kept distinct so capacity pre-sizing and telemetry
#: can see the candidate axis; it deliberately sits *below* KIND_SC so
#: every ``kind >= KIND_SC`` controller-dispatch guard excludes it.
(
    KIND_CUSTOM, KIND_TRIVIAL, KIND_STATIC, KIND_SC, KIND_MC, KIND_PROMC,
) = -1, 0, 1, 2, 3, 4


def _scheduler_kind(scheduler: Scheduler) -> int:
    from repro.core.baselines import StaticParamsScheduler

    cls = type(scheduler)
    if cls is SingleChunkScheduler:
        return KIND_SC
    if cls is MultiChunkScheduler:
        return KIND_MC
    if cls is ProActiveMultiChunkScheduler:
        return KIND_PROMC
    if cls is StaticParamsScheduler:
        return KIND_STATIC
    if (
        cls.on_tick is Scheduler.on_tick
        and cls.on_chunk_complete is Scheduler.on_chunk_complete
    ):
        return KIND_TRIVIAL
    return KIND_CUSTOM


class _ScenarioRuntime:
    """Python-side (non-vectorizable) per-scenario state: the controller
    object (for custom schedulers) and chunk metadata."""

    __slots__ = (
        "index", "name", "network", "scheduler", "chunks", "params",
        "trivial_tick", "trivial_complete", "tick_period",
        "total_bytes", "avg_fs", "predict_cache", "archive",
    )

    def __init__(self, index: int, name: str, sim: Simulation):
        self.index = index
        #: final metrics snapshot taken when the scenario's row is retired
        #: by compaction: (finish_t, n_events, completed_at, delivered,
        #: n_moves)
        self.archive = None
        self.name = name
        self.network = sim.network
        self.scheduler = sim.scheduler
        self.chunks = [st.chunk for st in sim.states]
        self.params: List[TransferParams] = [c.params for c in self.chunks]
        cls = type(sim.scheduler)
        self.trivial_tick = cls.on_tick is Scheduler.on_tick
        self.trivial_complete = (
            cls.on_chunk_complete is Scheduler.on_chunk_complete
        )
        self.tick_period = sim.tick_period
        self.total_bytes = float(sum(st.queue_bytes for st in sim.states))
        self.avg_fs = [max(c.avg_file_size, 1.0) for c in self.chunks]
        #: (chunk, n_channels, total_channels) -> predicted rate; the model
        #: is pure, and allocations revisit the same few tuples constantly
        self.predict_cache: dict = {}


#: default scenario wall-clock guard, mirroring ``Simulation(max_time=)``
_DEFAULT_MAX_TIME = 48 * 3600.0


class _PlanRuntime:
    """Columnar-ingest twin of :class:`_ScenarioRuntime`: plan rows are
    always built-in controllers, so the runtime carries only what result
    assembly, compaction and error paths read (names + byte totals) —
    scheduler/chunks are shared name-only refs, never Python objects."""

    __slots__ = (
        "index", "name", "network", "scheduler", "chunks", "total_bytes",
        "archive",
    )

    def __init__(self, index, name, network, scheduler, chunks, total_bytes):
        self.index = index
        self.name = name
        self.network = network
        self.scheduler = scheduler
        self.chunks = chunks
        self.total_bytes = total_bytes
        self.archive = None


#: every per-scenario row array of the driver state, for compaction and
#: device upload; (S,) scalars and (S, C)/(S, K)/(S, K, P) tables alike
_ROW_ARRAYS = (
    "t", "done", "next_tick", "tick_period", "n_events", "finish_t",
    "fin_any", "max_time", "record_timeline",
    "trivial_tick", "trivial_complete", "bw", "disk_rate", "sat_cc",
    "contention", "n_chunks", "chunk_of", "dead", "rem", "busy", "cap",
    "chunk_done", "completed_at", "delivered", "delivered_at_tick",
    "rate_est", "queue_bytes", "fsdt", "qoff", "qlen", "qptr", "prepend_n",
    "prepend_sizes", "kind", "streak", "pair_fast", "pair_slow",
    "promc_ratio", "promc_patience", "sc_cursor", "sc_order", "conc",
    "par", "cap_k", "avg_fs_k", "nfiles", "setup_cost", "n_moves",
    "prof_t", "prof_mult", "cap_need",
    "tl_t", "tl_rate", "tl_len", "tl_stride", "tl_seen", "tl_last_t",
    "tl_last_rate",
)

#: default on-device timeline sample budget per scenario (override with
#: ``timeline_budget=`` or ``REPRO_FABRIC_TIMELINE_BUDGET``). Recording
#: rows decimate by uniform stride past this, so memory stays fixed no
#: matter how many events a scenario runs.
DEFAULT_TIMELINE_BUDGET = 512


class FabricSimulation:
    """Run many scenarios through the fluid transfer model simultaneously.

    Construction takes ready ``Simulation`` objects (one per scenario, fresh
    schedulers) so scenario assembly stays in one place (eval.scenarios);
    only their initial state is consumed, never their event loop.

    ``ops`` selects the array backend for the batched sweeps (NumPy by
    default; the JAX subclass drives the same state on-device).
    ``waterfill_impl`` may name an alternative water-fill kernel
    (``"closed"`` — the sort-based closed form — or ``"pallas"`` for the
    optional Pallas kernel; also via ``REPRO_FABRIC_WATERFILL``).
    ``fused_step`` (``"none"`` / ``"pallas"``, also via
    ``REPRO_FABRIC_FUSED_STEP``) routes resume-free sweeps through the
    fused Pallas advance+feed kernel
    (:mod:`repro.eval.fabric.kernels.fused_step_pallas`) instead of the
    split ``_advance`` / feed path; the JAX subclass ignores it (its
    device loop is already fused).
    """

    #: whether the driver accepts a ``device=`` kwarg and benefits from
    #: the executor's round-robin device sharding (the JAX subclass flips
    #: this; the eager NumPy driver has no device axis, so the pipelined
    #: executor still overlaps its prep and compute but never pins it)
    supports_device_placement = False

    def __init__(
        self,
        sims: Optional[Sequence[Simulation]],
        names: Optional[Sequence[str]] = None,
        *,
        ops: Optional[ArrayOps] = None,
        waterfill_impl: Optional[str] = None,
        fused_step: Optional[str] = None,
        timeline_budget: Optional[int] = None,
        fabric: Optional[Sequence] = None,
        plan=None,
    ):
        self.ops = ops or numpy_ops()
        self.timeline_budget = int(
            timeline_budget
            if timeline_budget is not None
            else os.environ.get(
                "REPRO_FABRIC_TIMELINE_BUDGET", DEFAULT_TIMELINE_BUDGET
            )
        )
        if self.timeline_budget < 2:
            raise ValueError("timeline_budget must be >= 2")
        impl = waterfill_impl or os.environ.get(
            "REPRO_FABRIC_WATERFILL", "closed"
        )
        if impl not in ("closed", "pallas"):
            raise ValueError(
                f"unknown waterfill_impl {impl!r}; options: closed, pallas"
            )
        self.waterfill_impl = impl
        fused = fused_step or os.environ.get(
            "REPRO_FABRIC_FUSED_STEP", "none"
        )
        if fused not in ("none", "pallas"):
            raise ValueError(
                f"unknown fused_step {fused!r}; options: none, pallas"
            )
        self.fused_step = fused
        for option, value in (
            ("REPRO_FABRIC_WATERFILL", impl),
            ("REPRO_FABRIC_FUSED_STEP", fused),
        ):
            if value == "pallas":
                from .kernels.waterfill_pallas import require_f64_kernels

                require_f64_kernels(option)
        #: set by the columnar path only: (open_n, visit_rank) initial
        #: channel layout, consumed once by :meth:`_start_plan`
        self._plan_open = None
        if plan is not None:
            if sims:
                raise ValueError("pass either sims or plan=, not both")
            if fabric is not None:
                raise ValueError(
                    "plan= batches carry their fabric column on the plan "
                    "itself (ScenarioPlan.fabrics); fabric= is sims-only"
                )
            self._init_from_plan(plan)
            return
        if names is None:
            names = [f"scenario{i}" for i in range(len(sims))]
        self.rt = [
            _ScenarioRuntime(i, n, sim)
            for i, (n, sim) in enumerate(zip(names, sims))
        ]
        S = len(self.rt)
        self.S = S
        if fabric is not None and len(fabric) != S:
            raise ValueError(
                f"fabric column length {len(fabric)} != batch size {S}"
            )
        self._set_fabric(fabric)
        self.C = 4  # channel capacity; grows on demand
        self.P = 4  # resume-stack capacity; grows on demand
        # chunk axis bucketed to the canonical pow2 ladder: padding chunks
        # are born done/empty (see chunk_done below), so a 3-chunk batch
        # shares the K=4 compiled program with a 4-chunk one
        K = bucket(max((len(r.chunks) for r in self.rt), default=1))
        self.K = K

        # scenario scalars
        self.t = np.zeros(S)
        self.done = np.zeros(S, dtype=bool)
        self.next_tick = np.array([r.tick_period for r in self.rt])
        self.tick_period = np.array([r.tick_period for r in self.rt])
        self.n_events = np.zeros(S, dtype=np.int64)
        self.finish_t = np.zeros(S)
        #: per-sweep flag: some channel finished a file (consumed by _post)
        self.fin_any = np.zeros(S, dtype=bool)
        # per-scenario settings carried over from the event Simulations
        self.max_time = np.array([sim.max_time for sim in sims])
        self.record_timeline = np.array(
            [sim.record_timeline for sim in sims], dtype=bool
        )
        self.trivial_tick = np.array([r.trivial_tick for r in self.rt])
        self.trivial_complete = np.array(
            [r.trivial_complete for r in self.rt]
        )
        # network constants
        self.bw = np.array([r.network.bandwidth for r in self.rt])
        self.disk_rate = np.array(
            [r.network.disk.streaming_rate for r in self.rt]
        )
        self.sat_cc = np.array(
            [r.network.disk.saturation_cc for r in self.rt], dtype=np.int64
        )
        self.contention = np.array(
            [r.network.disk.contention for r in self.rt]
        )
        # time-varying bandwidth profiles: piecewise-constant multiplier
        # steps, padded to the widest profile in the batch ((0, 1.0) rows
        # for static paths — the common case costs one gather per sweep)
        profiles = [
            getattr(r.network, "bandwidth_profile", None) or ((0.0, 1.0),)
            for r in self.rt
        ]
        # profile width rides the same ladder (all-static batches keep the
        # width-1 fast path; mixed batches bucket so step counts don't leak
        # into the jit signature): pad steps hold t=inf / the last
        # multiplier, which the gather below never selects
        B = max((len(p) for p in profiles), default=1)
        if B > 1:
            B = bucket(B, PROFILE_PAD_FLOOR)
        self.prof_t = np.full((S, B), np.inf)
        self.prof_mult = np.ones((S, B))
        for r, prof in zip(self.rt, profiles):
            for j, (pt, pm) in enumerate(prof):
                self.prof_t[r.index, j] = pt
                self.prof_mult[r.index, j] = pm
            self.prof_mult[r.index, len(prof):] = prof[-1][1]

        # channel state, padded to capacity C
        self.chunk_of = np.full((S, self.C), _NO_CHUNK, dtype=np.int64)
        self.dead = np.zeros((S, self.C))
        self.rem = np.zeros((S, self.C))
        self.busy = np.zeros((S, self.C), dtype=bool)
        self.cap = np.zeros((S, self.C))

        # per-chunk state, padded to K (padding slots are born done/empty)
        self.n_chunks = np.array(
            [len(r.chunks) for r in self.rt], dtype=np.int64
        )
        self.chunk_done = np.zeros((S, K), dtype=bool)
        self.chunk_done[np.arange(K)[None, :] >= self.n_chunks[:, None]] = True
        self.completed_at = np.full((S, K), math.nan)
        self.delivered = np.zeros((S, K))
        self.delivered_at_tick = np.zeros((S, K))
        self.rate_est = np.zeros((S, K))
        self.queue_bytes = np.zeros((S, K))
        #: serial per-file dead time per chunk (params are fixed per chunk)
        self.fsdt = np.zeros((S, K))

        # controller state: kind dispatch, ProMC streak machine, SC cursor,
        # per-chunk decision tables (caps, parallelism, concurrency, sizes)
        self.kind = np.array(
            [_scheduler_kind(r.scheduler) for r in self.rt], dtype=np.int64
        )
        self.streak = np.zeros(S, dtype=np.int64)
        self.pair_fast = np.full(S, -1, dtype=np.int64)
        self.pair_slow = np.full(S, -1, dtype=np.int64)
        self.promc_ratio = np.array(
            [getattr(r.scheduler, "ratio", 2.0) for r in self.rt]
        )
        self.promc_patience = np.array(
            [getattr(r.scheduler, "patience", 3) for r in self.rt],
            dtype=np.int64,
        )
        self.sc_cursor = np.zeros(S, dtype=np.int64)
        self.sc_order = np.zeros((S, K), dtype=np.int64)
        self.conc = np.zeros((S, K), dtype=np.int64)
        self.par = np.ones((S, K), dtype=np.int64)
        self.cap_k = np.zeros((S, K))
        self.avg_fs_k = np.ones((S, K))
        self.nfiles = np.zeros((S, K), dtype=np.int64)
        self.setup_cost = np.array(
            [r.network.channel_setup_cost for r in self.rt]
        )
        self.n_moves = np.zeros(S, dtype=np.int64)

        # FIFO queues: one flat size buffer + (offset, length, cursor) per
        # (scenario, chunk). Resume files go to the (S, K, P) LIFO stack,
        # consumed before the cursor moves — exactly deque.appendleft/
        # popleft order.
        sizes: List[float] = []
        self.qoff = np.zeros((S, K), dtype=np.int64)
        self.qlen = np.zeros((S, K), dtype=np.int64)
        self.qptr = np.zeros((S, K), dtype=np.int64)
        #: count of re-queued resume files per (scenario, chunk)
        self.prepend_n = np.zeros((S, K), dtype=np.int64)
        self.prepend_sizes = np.zeros((S, K, self.P))
        for r in self.rt:
            if isinstance(r.scheduler, SingleChunkScheduler):
                order = list(r.scheduler._order)
                self.sc_order[r.index, : len(order)] = order
            for k, chunk in enumerate(r.chunks):
                self.qoff[r.index, k] = len(sizes)
                self.qlen[r.index, k] = len(chunk.files)
                self.queue_bytes[r.index, k] = chunk.total_bytes
                sizes.extend(float(f.size) for f in chunk.files)
                self.fsdt[r.index, k] = netmodel.file_start_dead_time(
                    r.network, r.params[k]
                )
                self.conc[r.index, k] = r.params[k].concurrency
                self.par[r.index, k] = r.params[k].parallelism
                self.cap_k[r.index, k] = netmodel.channel_rate_cap(
                    r.network, r.params[k].parallelism
                )
                self.avg_fs_k[r.index, k] = r.avg_fs[k]
                self.nfiles[r.index, k] = len(chunk.files)
        self.qsizes = np.asarray(sizes, dtype=np.float64)

        # on-device timeline ring buffer (uniform-stride decimation past
        # the budget); all-static width 1 when no row records, so batches
        # without timelines pay one no-op column at most
        T = self.timeline_budget if self.record_timeline.any() else 1
        self.tl_t = np.zeros((S, T))
        self.tl_rate = np.zeros((S, T))
        self.tl_len = np.zeros(S, dtype=np.int64)
        self.tl_stride = np.ones(S, dtype=np.int64)
        self.tl_seen = np.zeros(S, dtype=np.int64)
        self.tl_last_t = np.zeros(S)
        self.tl_last_rate = np.zeros(S)

        #: closed-form per-scenario worst case of simultaneously open
        #: channels (see :meth:`capacity_need`); the JAX backend pre-sizes
        #: its channel/resume axes from it so capacity-guard parks never
        #: fire for built-in schedulers
        self.cap_need = np.array(
            [
                self._worst_case_channels(r, bool(self.group_id[r.index] >= 0))
                for r in self.rt
            ],
            dtype=np.int64,
        )
        self._need_c_floor = 1
        self._started = False

    def _init_from_plan(self, plan) -> None:
        """Materialize the resident arrays straight from a
        :class:`repro.eval.fabric.plan.ScenarioPlan` — no ``Simulation``
        objects, no per-row packing loop. Column-for-column this mirrors
        the legacy constructor above (same dtypes, same pad values:
        ``tests/test_plan_ingest.py`` pins bit-identity), but every fill
        is a gather over the plan's context/network tables."""
        S = self.S = plan.n_rows
        self.C = 4
        self.P = 4
        nets = plan.networks
        ni = plan.net_idx
        # chunk axis re-buckets to this batch's widest row (a sliced
        # sub-plan of one-chunk rows keeps K=1 even if the parent plan
        # carried four-chunk contexts)
        n_chunks = plan.n_chunks.astype(np.int64)
        K = bucket(int(n_chunks.max(initial=1)))
        self.K = K
        self.rt = [
            _PlanRuntime(
                i,
                plan.names[i],
                nets[ni[i]],
                plan.sched_refs[i],
                plan.chunk_refs[i],
                float(plan.total_bytes[i]),
            )
            for i in range(S)
        ]

        # scenario scalars
        self.t = np.zeros(S)
        self.done = np.zeros(S, dtype=bool)
        self.next_tick = plan.tick_period.copy()
        self.tick_period = plan.tick_period.copy()
        self.n_events = np.zeros(S, dtype=np.int64)
        self.finish_t = np.zeros(S)
        self.fin_any = np.zeros(S, dtype=bool)
        self.max_time = np.full(S, _DEFAULT_MAX_TIME)
        self.record_timeline = plan.record_timeline.copy()
        self.trivial_tick = plan.trivial_tick.copy()
        self.trivial_complete = plan.trivial_complete.copy()
        # network constants: one small per-network table each, gathered
        net_f = lambda f: np.array(  # noqa: E731
            [f(n) for n in nets], dtype=np.float64
        )[ni]
        self.bw = net_f(lambda n: n.bandwidth)
        self.disk_rate = net_f(lambda n: n.disk.streaming_rate)
        self.sat_cc = np.array(
            [n.disk.saturation_cc for n in nets], dtype=np.int64
        )[ni]
        self.contention = net_f(lambda n: n.disk.contention)
        profiles = [
            getattr(n, "bandwidth_profile", None) or ((0.0, 1.0),)
            for n in nets
        ]
        B = max((len(profiles[j]) for j in ni), default=1)
        if B > 1:
            B = bucket(B, PROFILE_PAD_FLOOR)
        pt = np.full((len(nets), B), np.inf)
        pm = np.ones((len(nets), B))
        for j, prof in enumerate(profiles):
            for b, (t0, m0) in enumerate(prof[:B]):
                pt[j, b] = t0
                pm[j, b] = m0
            pm[j, len(prof):] = prof[-1][1]
        self.prof_t = pt[ni]
        self.prof_mult = pm[ni]

        # channel state
        self.chunk_of = np.full((S, self.C), _NO_CHUNK, dtype=np.int64)
        self.dead = np.zeros((S, self.C))
        self.rem = np.zeros((S, self.C))
        self.busy = np.zeros((S, self.C), dtype=bool)
        self.cap = np.zeros((S, self.C))

        # per-chunk state (plan columns are padded to plan.K >= K)
        self.n_chunks = n_chunks
        self.chunk_done = np.zeros((S, K), dtype=bool)
        self.chunk_done[np.arange(K)[None, :] >= n_chunks[:, None]] = True
        self.completed_at = np.full((S, K), math.nan)
        self.delivered = np.zeros((S, K))
        self.delivered_at_tick = np.zeros((S, K))
        self.rate_est = np.zeros((S, K))
        self.queue_bytes = plan.queue_bytes[:, :K].copy()
        self.fsdt = plan.fsdt[:, :K].copy()

        # controller state (plan rows are never KIND_CUSTOM; ProMC rows
        # carry the schedulers' default streak machine)
        self.kind = plan.kind.copy()
        self.streak = np.zeros(S, dtype=np.int64)
        self.pair_fast = np.full(S, -1, dtype=np.int64)
        self.pair_slow = np.full(S, -1, dtype=np.int64)
        self.promc_ratio = np.full(S, 2.0)
        self.promc_patience = np.full(S, 3, dtype=np.int64)
        self.sc_cursor = np.zeros(S, dtype=np.int64)
        self.sc_order = plan.sc_order[:, :K].copy()
        self.conc = plan.conc[:, :K].copy()
        self.par = plan.par[:, :K].copy()
        self.cap_k = plan.cap_k[:, :K].copy()
        self.avg_fs_k = plan.avg_fs_k[:, :K].copy()
        self.nfiles = plan.qlen[:, :K].copy()
        self.setup_cost = net_f(lambda n: n.channel_setup_cost)
        self.n_moves = np.zeros(S, dtype=np.int64)

        # FIFO queues: rows address the plan's shared flat buffer through
        # their offsets — sub-plans of the same parent share one buffer
        # (read-only in every kernel), collapsing the jax queue-pad
        # signature axis to a single rung per plan
        self.qoff = plan.qoff[:, :K].copy()
        self.qlen = plan.qlen[:, :K].copy()
        self.qptr = np.zeros((S, K), dtype=np.int64)
        self.prepend_n = np.zeros((S, K), dtype=np.int64)
        self.prepend_sizes = np.zeros((S, K, self.P))
        self.qsizes = plan.qsizes

        T = self.timeline_budget if self.record_timeline.any() else 1
        self.tl_t = np.zeros((S, T))
        self.tl_rate = np.zeros((S, T))
        self.tl_len = np.zeros(S, dtype=np.int64)
        self.tl_stride = np.ones(S, dtype=np.int64)
        self.tl_seen = np.zeros(S, dtype=np.int64)
        self.tl_last_t = np.zeros(S)
        self.tl_last_rate = np.zeros(S)

        self.cap_need = plan.cap_need.copy()
        # plan chunks pad the channel axis to the shape-hint floor: a few
        # dead columns buy every cc<=PLAN_C_FLOOR chunk the SAME compiled
        # C, and batches holding profiled rows share one (C, B=16)
        # program family (see ScenarioPlan.shape_hints)
        from .plan import (
            PLAN_C_FLOOR,
            PLAN_COMPACT_FLOOR,
            PLAN_PROFILED_C_FLOOR,
        )

        self._need_c_floor = (
            PLAN_C_FLOOR if B == 1 else PLAN_PROFILED_C_FLOOR
        )
        # all-static batches (baseline + candidate rows, no timelines)
        # drain chunk-at-a-time, so compaction stops at the plane floor:
        # the grid's narrow straggler rungs would only add device
        # re-entries and download syncs here (see plan.PLAN_COMPACT_FLOOR)
        if not self.record_timeline.any() and bool(
            (self.kind <= KIND_STATIC).all()
        ):
            self._compact_floor = PLAN_COMPACT_FLOOR
        self._plan_open = (
            plan.open_n[:, :K].copy(),
            plan.visit_rank[:, :K].copy(),
        )
        # plan.cap_need already carries the coupled SC widening (see
        # plan.build_plan); only the membership arrays resolve here
        self._set_fabric(getattr(plan, "fabrics", None))
        self._started = False

    def _set_fabric(self, fabrics) -> None:
        """Lower the per-row fabric column into the coupling arrays the
        sweep reads (``group_id`` (S,), ``link_member`` (L, S),
        ``link_cap`` (L,)); all-``None`` columns collapse to the
        uncoupled fast path (``self.coupled`` False, L == 0)."""
        if fabrics is None or all(f is None for f in fabrics):
            self.group_id = np.full(self.S, -1, dtype=np.int64)
            self.link_member = np.zeros((0, self.S), dtype=bool)
            self.link_cap = np.zeros(0, dtype=np.float64)
            self._n_groups = 0
            self.coupled = False
            return
        fab = resolve_fabric(fabrics)
        self.group_id = fab.group_id
        self.link_member = fab.member
        self.link_cap = fab.link_cap
        self._n_groups = fab.n_groups
        self.coupled = fab.coupled

    @staticmethod
    def _worst_case_channels(
        r: _ScenarioRuntime, coupled: bool = False
    ) -> int:
        """Closed-form bound on channels a scenario can hold at once.

        * SC holds one chunk's wave at a time, except when empty-chunk (or
          exactly tied) completions advance the cursor while earlier waves
          still run — each such completion co-schedules at most one more
          chunk, so the bound is the sum of the ``1 + n_empty`` largest
          per-chunk concurrencies. Coupled rows advance on the *group*
          horizon, so completion ties the uncoupled physics could never
          produce become ordinary (two chunks starved to identical rates
          finish on the same sweep); the only safe static bound is every
          wave live at once — the full concurrency sum.
        * MC / ProMC open ``max(maxCC, n_nonempty)`` channels up front
          (every non-empty chunk gets at least one) and every later
          transition (laggard grants, ProMC moves) conserves the count —
          coupling changes rates, never that invariant.
        * Trivial baselines and static-params candidate rows only act at
          t=0 (bounded by the per-chunk concurrency sum — exactly the
          candidate's ``cc`` for a one-chunk static row); custom
          schedulers keep the host-growth path.
        """
        kind = _scheduler_kind(r.scheduler)
        conc = sorted(
            (int(c.params.concurrency) for c in r.chunks if len(c.files)),
            reverse=True,
        )
        n_empty = len(r.chunks) - len(conc)
        max_cc = int(getattr(r.scheduler, "max_cc", 1))
        if kind == KIND_SC and not coupled:
            return max(1, sum(conc[: 1 + n_empty]))
        if kind in (KIND_MC, KIND_PROMC):
            return max(1, max_cc, len(conc))
        return max(1, sum(conc))

    def capacity_need(self) -> tuple:
        """Batch-wide worst-case ``(channels, resume-stack)`` capacities.

        Valid once :meth:`start` ran (initial actions may already hold
        the per-row bound's worth of channels; custom schedulers can
        exceed the closed form, so the observed open count joins the
        max). A chunk's resume-stack depth never exceeds its channel
        count — a push closes a busy channel and a regained channel pops
        the stack before the queue — so the stack bound is the channel
        bound plus one slot of headroom for the device loop's
        prospective-overflow guard.
        """
        open_now = (self.chunk_of != _NO_CHUNK).sum(axis=1)
        need_c = int(np.maximum(self.cap_need, open_now).max(initial=1))
        need_c = max(need_c, self._need_c_floor)
        return need_c, need_c + 1

    def compact_floor(self) -> int:
        """The smallest padded device shape compaction will descend to
        for this batch: :data:`bucketing.COMPACT_FLOOR` for the
        heterogeneous grid, ``plan.PLAN_COMPACT_FLOOR`` for all-static
        candidate-plane batches (set by :meth:`_init_from_plan`). The
        jax backend passes it as a *static* jit argument, so the two
        policies occupy disjoint compiled programs."""
        return int(getattr(self, "_compact_floor", COMPACT_FLOOR))

    # ------------------------------------------------------------------ #
    # water-fill dispatch
    # ------------------------------------------------------------------ #

    def _waterfill(self, caps, pool):
        if self.waterfill_impl == "pallas":
            from .kernels.waterfill_pallas import waterfill_pallas_f64

            return waterfill_pallas_f64(caps, pool)
        return kernels.waterfill(self.ops, caps, pool)

    # ------------------------------------------------------------------ #
    # channel bookkeeping (mirrors Simulation._open_channel/_close_channels)
    # ------------------------------------------------------------------ #

    def _grow(self) -> None:
        pad = self.C
        self.C *= 2

        def z(a, fill):
            return np.concatenate(
                [a, np.full((self.S, pad), fill, dtype=a.dtype)], axis=1
            )

        self.chunk_of = z(self.chunk_of, _NO_CHUNK)
        self.dead = z(self.dead, 0.0)
        self.rem = z(self.rem, 0.0)
        self.busy = z(self.busy, False)
        self.cap = z(self.cap, 0.0)

    def _grow_prepend(self) -> None:
        pad = self.P
        self.P *= 2
        self.prepend_sizes = np.concatenate(
            [self.prepend_sizes, np.zeros((self.S, self.K, pad))], axis=2
        )

    def _push_resume(self, s: int, chunk: int, size: float) -> None:
        if self.prepend_n[s, chunk] >= self.P:
            self._grow_prepend()
        self.prepend_sizes[s, chunk, self.prepend_n[s, chunk]] = size
        self.prepend_n[s, chunk] += 1
        self.queue_bytes[s, chunk] += size

    def _open_channel(
        self, r: _ScenarioRuntime, chunk: int, prev: Optional[TransferParams]
    ) -> None:
        s = r.index
        free = np.flatnonzero(self.chunk_of[s] == _NO_CHUNK)
        if free.size == 0:
            self._grow()
            free = np.flatnonzero(self.chunk_of[s] == _NO_CHUNK)
        c = free[0]
        params = r.params[chunk]
        self.chunk_of[s, c] = chunk
        self.dead[s, c] = netmodel.channel_open_cost(r.network, params, prev)
        self.rem[s, c] = 0.0
        self.busy[s, c] = False
        self.cap[s, c] = netmodel.channel_rate_cap(r.network, params.parallelism)

    def _close_channels(
        self, r: _ScenarioRuntime, chunk: int, n: int
    ) -> List[TransferParams]:
        s = r.index
        cols = np.flatnonzero(self.chunk_of[s] == chunk)
        # idle first, matching the event simulator's preference
        cols = sorted(cols, key=lambda c: bool(self.busy[s, c]))
        closed: List[TransferParams] = []
        for c in cols[:n]:
            if self.busy[s, c] and self.rem[s, c] > 0:
                f = resume_file(self.rem[s, c])
                self._push_resume(s, chunk, float(f.size))
            self.chunk_of[s, c] = _NO_CHUNK
            self.busy[s, c] = False
            self.dead[s, c] = 0.0
            self.rem[s, c] = 0.0
            self.cap[s, c] = 0.0
            closed.append(r.params[chunk])
        if closed:
            self._pack_row(s)
        return closed

    def _pack_row(self, s: int) -> None:
        """Left-pack row ``s``'s channel axis after a close, keeping column
        order equal to the event simulator's channel-list order (closes
        remove, opens append) — see ``kernels.compact_channels``."""
        order = np.argsort(self.chunk_of[s] == _NO_CHUNK, kind="stable")
        for arr in (self.chunk_of, self.busy, self.dead, self.rem, self.cap):
            arr[s] = arr[s][order]

    def _apply(self, r: _ScenarioRuntime, actions) -> None:
        for act in actions:
            if isinstance(act, Open):
                for _ in range(act.n):
                    self._open_channel(r, act.chunk, prev=None)
            elif isinstance(act, Close):
                self._close_channels(r, act.chunk, act.n)
            elif isinstance(act, Move):
                moved = self._close_channels(r, act.src, act.n)
                for prev in moved:
                    self._open_channel(r, act.dst, prev=prev)
                self.n_moves[r.index] += len(moved)

    # ------------------------------------------------------------------ #
    # queue feeding
    # ------------------------------------------------------------------ #

    def _files_left(self, s: int, k: int) -> int:
        return int(
            self.qlen[s, k] - self.qptr[s, k] + self.prepend_n[s, k]
        )

    def _feed_py(self, r: _ScenarioRuntime) -> None:
        """Scalar feed for one scenario (after custom-scheduler actions).
        Mirrors Simulation._feed_channels."""
        s = r.index
        idle = np.flatnonzero((self.chunk_of[s] != _NO_CHUNK) & ~self.busy[s])
        for c in idle:
            k = int(self.chunk_of[s, c])
            if self.prepend_n[s, k] > 0:
                self.prepend_n[s, k] -= 1
                size = self.prepend_sizes[s, k, self.prepend_n[s, k]]
            elif self.qptr[s, k] < self.qlen[s, k]:
                size = self.qsizes[self.qoff[s, k] + self.qptr[s, k]]
                self.qptr[s, k] += 1
            else:
                continue
            self.queue_bytes[s, k] -= size
            self.busy[s, c] = True
            self.rem[s, c] = size
            self.dead[s, c] += self.fsdt[s, k]

    def _feed_vec(self, rows: np.ndarray) -> None:
        """Batched feed (the ``kernels.feed_queues`` fabric kernel, LIFO
        resume stack included — skipped while no resume files exist)."""
        ps = self.prepend_sizes if self.prepend_n.any() else None
        (
            self.busy, self.dead, self.rem, self.qptr, self.queue_bytes,
            self.prepend_n,
        ) = kernels.feed_queues(
            self.ops, rows, self.chunk_of, self.busy, self.dead,
            self.rem, self.qsizes, self.qoff, self.qlen, self.qptr,
            self.queue_bytes, self.fsdt, ps, self.prepend_n,
        )

    # ------------------------------------------------------------------ #
    # controller plumbing (mirrors Simulation._view)
    # ------------------------------------------------------------------ #

    def _bytes_remaining(self, r: _ScenarioRuntime, k: int) -> float:
        s = r.index
        mask = (self.chunk_of[s] == k) & self.busy[s]
        return float(self.queue_bytes[s, k]) + float(self.rem[s][mask].sum())

    def _view(self, r: _ScenarioRuntime) -> List[ChunkView]:
        s = r.index
        ko = self.chunk_of[s]
        open_mask = ko != _NO_CHUNK
        n_open_total = int(open_mask.sum())
        nK = len(r.chunks)
        n_ch = np.bincount(ko[open_mask], minlength=nK)
        busy_ch = np.bincount(ko[open_mask & self.busy[s]], minlength=nK)
        inflight = np.zeros(nK)
        np.add.at(
            inflight, ko[open_mask & self.busy[s]],
            self.rem[s][open_mask & self.busy[s]],
        )
        views = []
        for k, chunk in enumerate(r.chunks):
            key = (k, int(n_ch[k]), n_open_total)
            predicted = r.predict_cache.get(key)
            if predicted is None:
                predicted = netmodel.predict_chunk_rate(
                    r.network,
                    r.avg_fs[k],
                    chunk.params,
                    max(int(n_ch[k]), 1),
                    total_active_channels=max(1, n_open_total),
                )
                r.predict_cache[key] = predicted
            views.append(
                ChunkView(
                    index=k,
                    ctype=chunk.ctype,
                    bytes_remaining=float(self.queue_bytes[s, k])
                    + float(inflight[k]),
                    files_remaining=self._files_left(s, k) + int(busy_ch[k]),
                    throughput=float(self.rate_est[s, k]),
                    n_channels=int(n_ch[k]),
                    done=bool(self.chunk_done[s, k]),
                    predicted_rate=predicted,
                )
            )
        return views

    def _view_arrays(self):
        """Batched ChunkViews: the (S, K) decision inputs (ETA, measured
        and predicted rates, channel counts) for the controller kernels."""
        open_mask = self.chunk_of != _NO_CHUNK
        n_ch = self.ops.count_by_chunk(self.chunk_of, open_mask, self.K)
        n_open = open_mask.sum(axis=-1)
        inflight = self.ops.chunk_scatter_add(
            np.zeros_like(self.queue_bytes), self.chunk_of, self.rem,
            open_mask & self.busy,
        )
        bytes_rem = self.queue_bytes + inflight
        pred = controllers.predicted_chunk_rate(
            self.ops, self.avg_fs_k, self.cap_k, self.fsdt,
            n_ch, n_open, self.bw, self.disk_rate, self.sat_cc,
            self.contention,
        )
        eta = controllers.chunk_eta(
            self.ops, bytes_rem, self.rate_est, pred, self.chunk_done
        )
        return bytes_rem, n_ch, eta

    def _check_completions_py(self, r: _ScenarioRuntime) -> List[int]:
        s = r.index
        completed = []
        for k in range(len(r.chunks)):
            if self.chunk_done[s, k]:
                continue
            busy = bool(((self.chunk_of[s] == k) & self.busy[s]).any())
            if self._files_left(s, k) == 0 and not busy:
                self._mark_complete(s, k)
                completed.append(k)
        return completed

    def _mark_complete(self, s: int, k: int) -> None:
        self.chunk_done[s, k] = True
        self.queue_bytes[s, k] = 0.0
        self.completed_at[s, k] = self.t[s]

    # ------------------------------------------------------------------ #
    # the vectorized event loop
    # ------------------------------------------------------------------ #

    def start(self) -> None:
        # idempotent: run() calls start() unconditionally, and re-applying
        # initial actions (stateful schedulers, advanced queue cursors)
        # would corrupt a batch a caller already started explicitly
        if self._started:
            return
        self._started = True
        if self._plan_open is not None:
            self._start_plan()
            return
        for r in self.rt:
            self._apply(r, r.scheduler.initial_actions(self._view(r)))
            self._feed_py(r)
            # mirror post-initial controller state into the row arrays
            if isinstance(r.scheduler, SingleChunkScheduler):
                self.sc_cursor[r.index] = r.scheduler._cursor
            if isinstance(r.scheduler, ProActiveMultiChunkScheduler):
                self.streak[r.index] = r.scheduler._streak
                pair = r.scheduler._streak_pair or (-1, -1)
                self.pair_fast[r.index], self.pair_slow[r.index] = pair

    def _start_plan(self) -> None:
        """Vectorized t=0 initial actions for plan-ingested batches.

        The plan pre-computed, per row, how many channels each chunk
        opens (``open_n``) and in which service order the controller
        would have issued the Opens (``visit_rank``). Applying them in
        that order against the legacy ``_open_channel`` (lowest free
        column) lays chunk ``k``'s channels out contiguously starting at
        the sum of the counts of chunks served before it — so the column
        layout, setup dead time and per-channel caps are reproduced here
        as pure array work, followed by one batched feed. SC cursors and
        the ProMC streak machine keep their start-of-run defaults, which
        is exactly what the scalar facades hold after ``start``."""
        open_n, vrank = self._plan_open
        total_open = open_n.sum(axis=1)
        max_open = int(total_open.max(initial=0))
        while self.C < max_open:
            self._grow()
        S, K, C = self.S, self.K, self.C
        # column offset of each chunk's block: channels opened by chunks
        # earlier in the service order
        ahead = vrank[:, :, None] > vrank[:, None, :]
        off = np.sum(np.where(ahead, open_n[:, None, :], 0), axis=2)
        cols = np.arange(C)[None, None, :]
        lo = off[:, :, None]
        hi = (off + open_n)[:, :, None]
        occupies = (cols >= lo) & (cols < hi)  # (S, K, C), disjoint in K
        chunk_idx = occupies.argmax(axis=1)
        is_open = occupies.any(axis=1)
        self.chunk_of = np.where(is_open, chunk_idx, _NO_CHUNK).astype(
            np.int64
        )
        # every t=0 Open pays the full setup cost (no prior channel on
        # the row: _open_channel's warm-reopen discount can't apply)
        self.dead = np.where(is_open, self.setup_cost[:, None], 0.0)
        self.cap = np.where(
            is_open, np.take_along_axis(self.cap_k, chunk_idx, axis=1), 0.0
        )
        self.rem = np.zeros((S, C))
        self.busy = np.zeros((S, C), dtype=bool)
        self._feed_vec(np.ones(S, dtype=bool))

    def step(self, rows: Optional[np.ndarray] = None) -> None:
        """One synchronized sweep over ``rows`` (default: all scenarios):
        every live selected scenario advances to its own next event.
        Mirrors Simulation.step; keep the orders in lockstep."""
        act = ~self.done if rows is None else (~self.done & rows)
        if not act.any():
            return
        if (
            self.fused_step == "pallas"
            and not self.coupled
            and not self.prepend_n.any()
        ):
            # resume-free sweeps (the overwhelmingly common case) run
            # water-fill + horizon + advance + FIFO feed as one fused
            # Pallas launch; _post then skips its own feed
            self._advance_fused(act)
            self._post(act, skip_feed=True)
        else:
            self._advance(act)
            self._post(act)

    def _bandwidth_now(self):
        """Effective per-row bandwidth under the profile at time ``t`` and
        the time of each row's next profile step (inf when static)."""
        if self.prof_t.shape[1] == 1:  # all-static batch: one (0, 1.0) step
            return self.bw, np.full(self.S, np.inf)
        at = np.sum(self.prof_t <= self.t[:, None], axis=1) - 1
        mult = np.take_along_axis(
            self.prof_mult, np.maximum(at, 0)[:, None], axis=1
        )[:, 0]
        eff_bw = self.bw * np.where(at >= 0, mult, 1.0)
        nxt = np.min(
            np.where(self.prof_t > self.t[:, None], self.prof_t, np.inf),
            axis=1,
        )
        return eff_bw, nxt

    def _advance(self, act: np.ndarray) -> None:
        """Physics half of a sweep: rates, horizon, fluid byte movement.

        Leaves ``self.fin_any[act]`` holding whether a channel finished a
        file, which :meth:`_post` consumes for scenario-done detection.
        """
        over = act & (self.t > self.max_time)
        if over.any():
            s = int(np.flatnonzero(over)[0])
            raise RuntimeError(
                f"batch scenario {self.rt[s].name!r} exceeded max_time="
                f"{self.max_time[s]}s (t={self.t[s]:.1f})"
            )
        self.n_events[act] += 1

        transferring = self.busy & (self.dead <= _EPS)
        n_t = transferring.sum(axis=1)
        eff_bw, next_prof = self._bandwidth_now()
        pool = kernels.disk_pool(
            self.ops, n_t, eff_bw, self.disk_rate, self.sat_cc,
            self.contention,
        )
        if self.coupled:
            # shared-fabric override: each coupled row's demand is what it
            # could actually move uncoupled — pool clipped to its channel
            # caps, totalled with waterfill's own cumsum-of-sorted
            # reduction so an unsaturated grant reproduces the uncoupled
            # water-fill bit for bit — and the cross-row kernel shrinks
            # the pools of rows on saturated links to the max-min share
            caps_eff = np.where(transferring, self.cap, 0.0)
            demand = np.where(
                act & (self.group_id >= 0),
                np.minimum(pool, kernels.caps_total(self.ops, caps_eff)),
                0.0,
            )
            grant, _ = kernels.waterfill_coupled(
                self.ops, demand, self.link_member, self.link_cap
            )
            pool = np.where(self.group_id >= 0, grant, pool)
        # water-fill only live rows: the sort inside is the costliest
        # per-iteration op and finished scenarios would pay it for nothing
        rates = np.zeros_like(self.rem)
        act_rows = np.flatnonzero(act)
        rates[act_rows] = self._waterfill(
            np.where(transferring[act_rows], self.cap[act_rows], 0.0),
            pool[act_rows],
        )
        rec = act & self.record_timeline
        if rec.any():
            # on-device-shaped ring buffer: the same kernel the JAX loop
            # runs, so numpy and jax record bit-identically
            (
                self.tl_t, self.tl_rate, self.tl_len, self.tl_stride,
                self.tl_seen, self.tl_last_t, self.tl_last_rate,
            ) = kernels.timeline_push(
                self.ops, rec, self.t, kernels.row_total(self.ops, rates),
                self.tl_t, self.tl_rate, self.tl_len, self.tl_stride,
                self.tl_seen, self.tl_last_t, self.tl_last_rate,
            )

        dt = kernels.event_horizon(
            self.ops,
            np.minimum(self.next_tick - self.t, next_prof - self.t),
            self.busy, self.dead, transferring, self.rem, rates,
        )
        dt = np.where(act, dt, 0.0)
        if self.coupled:
            # lockstep dt: a fabric group shares one clock, so every live
            # member advances by the group's minimum horizon. A member
            # whose own next event lies further out takes a partial
            # advance — no completion/feed/tick threshold is crossed, so
            # _post is a natural no-op for it beyond the moved bytes.
            live = act & (self.group_id >= 0)
            if live.any():
                g_dt = np.full(self._n_groups, np.inf)
                np.minimum.at(g_dt, self.group_id[live], dt[live])
                dt = np.where(
                    live, g_dt[np.maximum(self.group_id, 0)], dt
                )

        # stranded-chunk detection (scheduler bug), as in the event sim
        no_busy = act & ~self.busy.any(axis=1)
        for s in np.flatnonzero(no_busy):
            r = self.rt[s]
            live = np.flatnonzero(~self.chunk_done[s])
            held = set(self.chunk_of[s][self.chunk_of[s] != _NO_CHUNK].tolist())
            if any(int(k) not in held for k in live):
                raise RuntimeError(
                    f"scheduler {r.scheduler.name} stranded chunks "
                    f"{[r.chunks[int(k)].name for k in live]} in {r.name!r}"
                )

        # advance every live scenario by its own dt
        self.t += dt
        self.busy, self.dead, self.rem, moved, finished = (
            kernels.advance_channels(
                self.ops, act, dt, self.busy, self.dead, transferring,
                self.rem, rates,
            )
        )
        self.delivered = self.ops.chunk_scatter_add(
            self.delivered, self.chunk_of, moved, moved != 0.0
        )
        self.fin_any = np.where(act, finished.any(axis=1), self.fin_any)

    def _advance_fused(self, act: np.ndarray) -> None:
        """Physics half + FIFO feed as one fused Pallas launch.

        Semantics match :meth:`_advance` followed by :meth:`_post`'s feed
        on resume-free sweeps (the caller guarantees ``prepend_n`` is all
        zero); host-side error checks, timeline recording, and the
        delivered scatter stay here, fed by the kernel's returns. The
        bisected water level agrees with the closed form to ~1e-12, so
        results sit far inside the difftest's 2% bar but are not
        bit-identical to the default path.
        """
        from .kernels.fused_step_pallas import fused_advance_feed_f64

        over = act & (self.t > self.max_time)
        if over.any():
            s = int(np.flatnonzero(over)[0])
            raise RuntimeError(
                f"batch scenario {self.rt[s].name!r} exceeded max_time="
                f"{self.max_time[s]}s (t={self.t[s]:.1f})"
            )
        self.n_events[act] += 1
        # stranded-chunk detection on pre-advance state, as in _advance
        no_busy = act & ~self.busy.any(axis=1)
        for s in np.flatnonzero(no_busy):
            r = self.rt[s]
            live = np.flatnonzero(~self.chunk_done[s])
            held = set(self.chunk_of[s][self.chunk_of[s] != _NO_CHUNK].tolist())
            if any(int(k) not in held for k in live):
                raise RuntimeError(
                    f"scheduler {r.scheduler.name} stranded chunks "
                    f"{[r.chunks[int(k)].name for k in live]} in {r.name!r}"
                )
        eff_bw, next_prof = self._bandwidth_now()
        (
            dt, rate_sum, fin, busy, dead, rem, moved, qptr, qb,
        ) = fused_advance_feed_f64(
            act, self.busy, self.dead, self.rem, self.cap, self.chunk_of,
            np.minimum(self.next_tick - self.t, next_prof - self.t),
            eff_bw, self.disk_rate, self.sat_cc, self.contention,
            self.qoff, self.qlen, self.qptr, self.queue_bytes, self.fsdt,
            self.qsizes,
        )
        rec = act & self.record_timeline
        if rec.any():
            (
                self.tl_t, self.tl_rate, self.tl_len, self.tl_stride,
                self.tl_seen, self.tl_last_t, self.tl_last_rate,
            ) = kernels.timeline_push(
                self.ops, rec, self.t, rate_sum, self.tl_t, self.tl_rate,
                self.tl_len, self.tl_stride, self.tl_seen, self.tl_last_t,
                self.tl_last_rate,
            )
        self.t += dt  # kernel zeroes dt on inactive rows
        self.busy, self.dead, self.rem = busy, dead, rem
        self.qptr, self.queue_bytes = qptr, qb
        self.delivered = self.ops.chunk_scatter_add(
            self.delivered, self.chunk_of, moved, moved != 0.0
        )
        self.fin_any = np.where(act, fin, self.fin_any)

    def _post(self, act: np.ndarray, skip_feed: bool = False) -> None:
        """Transition half of a sweep: feed -> completions -> tick -> done.

        The order is the fidelity contract's feed/complete/tick ordering;
        the JAX backend fuses the same sequence on-device and calls this
        only for rows it parked (timeline / custom-controller / guard
        edges — their ``_advance`` ran on-device). ``skip_feed`` is the
        fused-step path, whose kernel already fed the queues.
        """
        # ---- feed (batched, resume-stack aware) ----
        if not skip_feed:
            self._feed_vec(act)

        # ---- chunk completions ----
        # a chunk can only complete in an iteration where one of its
        # channels finished a file (or lost its channels to an action,
        # which the controller branches below handle)
        busy_per_chunk = self.ops.count_by_chunk(
            self.chunk_of, self.busy, self.K
        )
        files_left = self.qlen - self.qptr + self.prepend_n
        completed = (
            act[:, None]
            & ~self.chunk_done
            & (files_left == 0)
            & (busy_per_chunk == 0)
        )
        comp_rows = completed.any(axis=1)
        # trivial controllers (baselines): pure vector bookkeeping
        vec_rows = comp_rows & self.trivial_complete
        if vec_rows.any():
            m = completed & vec_rows[:, None]
            self.chunk_done |= m
            self.queue_bytes[m] = 0.0
            rs, ks = np.nonzero(m)
            self.completed_at[rs, ks] = self.t[rs]
        # SC / MC / ProMC: batched controller kernels
        ctrl_rows = comp_rows & (self.kind >= KIND_SC)
        if ctrl_rows.any():
            self._complete_ctrl(completed & ctrl_rows[:, None])
        # custom controllers: event-ordered python
        py_rows = comp_rows & ~self.trivial_complete & (self.kind == KIND_CUSTOM)
        for s in np.flatnonzero(py_rows):
            r = self.rt[s]
            for k in self._check_completions_py(r):
                actions = r.scheduler.on_chunk_complete(self._view(r), k)
                if actions:
                    self._apply(r, actions)
                    self._feed_py(r)

        # ---- controller tick ----
        tick_hit = act & (self.t >= self.next_tick - _EPS)
        if tick_hit.any():
            ema = kernels.tick_ema(
                self.ops, self.rate_est, self.delivered,
                self.delivered_at_tick, self.tick_period[:, None],
            )
            rows = tick_hit[:, None]
            np.copyto(self.rate_est, ema, where=rows)
            np.copyto(self.delivered_at_tick, self.delivered, where=rows)
            promc_rows = tick_hit & (self.kind == KIND_PROMC)
            if promc_rows.any():
                self._tick_ctrl(promc_rows)
            for s in np.flatnonzero(
                tick_hit & ~self.trivial_tick & (self.kind == KIND_CUSTOM)
            ):
                r = self.rt[s]
                actions = r.scheduler.on_tick(self._view(r))
                if actions:
                    self._apply(r, actions)
                    self._feed_py(r)
            self.next_tick += np.where(tick_hit, self.tick_period, 0.0)

        # ---- scenario completion ----
        newly = act & self.chunk_done.all(axis=1) & (self.fin_any | comp_rows)
        self.finish_t = np.where(newly, self.t, self.finish_t)
        self.done |= newly

    # ------------------------------------------------------------------ #
    # batched controller dispatch (SC / MC / ProMC rows)
    # ------------------------------------------------------------------ #

    def _complete_ctrl(self, m: np.ndarray) -> None:
        """Chunk completions on controller rows: mark all completed chunks
        first (as the scalar ``_check_completions`` does), then run each
        chunk's completion handler in index order with a re-feed after
        every one — the exact event order of the scalar callback loop."""
        rows = m.any(axis=1)
        self.chunk_done |= m
        self.queue_bytes[m] = 0.0
        rs, ks = np.nonzero(m)
        self.completed_at[rs, ks] = self.t[rs]
        # ProMC drops accumulated streak evidence on any completion
        pr = rows & (self.kind == KIND_PROMC)
        self.streak[pr] = 0
        self.pair_fast[pr] = -1
        self.pair_slow[pr] = -1
        for k in range(self.K):
            trig = m[:, k]
            if not trig.any():
                continue
            fed = np.zeros(self.S, dtype=bool)
            sc_t = trig & (self.kind == KIND_SC)
            if sc_t.any():
                self._sc_complete(sc_t, k)
                fed |= sc_t  # SC always emits a Close => always re-feeds
            mc_t = trig & (
                (self.kind == KIND_MC) | (self.kind == KIND_PROMC)
            )
            if mc_t.any():
                fed |= self._laggard_complete(mc_t, k)
            if fed.any():
                self._feed_vec(fed)

    def _sc_complete(self, trig: np.ndarray, k: int) -> None:
        """SC's on_chunk_complete: close the finished chunk's channels,
        advance the cursor past empty size classes, open the next chunk at
        its own concurrency."""
        (
            self.chunk_of, self.busy, self.dead, self.rem, self.cap,
        ) = controllers.close_chunk(
            self.ops, trig, k, self.chunk_of, self.busy, self.dead,
            self.rem, self.cap,
        )
        self.sc_cursor = controllers.sc_advance_cursor(
            self.ops, trig, self.sc_cursor, self.sc_order, self.nfiles,
            self.n_chunks,
        )
        open_ok = trig & (self.sc_cursor < self.n_chunks)
        nxt = np.take_along_axis(
            self.sc_order, np.clip(self.sc_cursor, 0, self.K - 1)[:, None],
            axis=1,
        )[:, 0]
        n_open = np.where(
            open_ok,
            np.take_along_axis(self.conc, nxt[:, None], axis=1)[:, 0],
            0,
        )
        # host-side capacity: grow the channel axis until every row fits
        while True:
            free = (self.chunk_of == _NO_CHUNK).sum(axis=1)
            if (free >= n_open).all():
                break
            self._grow()
        self.chunk_of, self.dead, self.cap = controllers.open_ranked(
            self.ops, n_open, nxt, self.chunk_of, self.dead, self.cap,
            self.setup_cost, self.cap_k,
        )

    def _laggard_complete(self, trig: np.ndarray, k: int) -> np.ndarray:
        """MC/ProMC's on_chunk_complete: re-target the freed channels to
        the largest-ETA chunks (with per-grant discounting). Returns the
        rows that received actions (and therefore re-feed)."""
        bytes_rem, n_ch, eta = self._view_arrays()
        idx = np.arange(self.K)[None, :]
        live = (
            ~self.chunk_done & (idx != k) & (bytes_rem > 0)
        )
        freed = np.where(trig, n_ch[:, k], 0)
        max_iters = int(freed.max())
        if max_iters == 0:
            return np.zeros(self.S, dtype=bool)
        grants, first = controllers.laggard_grants(
            self.ops, eta, n_ch, live, freed, max_iters
        )
        # no grants (no live receivers) => the scalar reference emits no
        # actions at all, leaving the source's idle channels open
        acted = trig & (grants.sum(axis=1) > 0)
        (
            self.chunk_of, self.busy, self.dead, self.rem, self.cap,
            self.n_moves,
        ) = controllers.apply_grants(
            self.ops, acted, k, grants, first, self.chunk_of, self.busy,
            self.dead, self.rem, self.cap, self.n_moves, self.par,
            self.cap_k, self.setup_cost,
        )
        return acted

    def _tick_ctrl(self, rows: np.ndarray) -> None:
        """ProMC periodic re-allocation check on ``rows``: streak update
        plus (on patience expiry) one fast->slow channel move, with the
        LIFO resume push when the move victims a busy channel."""
        bytes_rem, n_ch, eta = self._view_arrays()
        live = ~self.chunk_done & (bytes_rem > 0)
        streak, pf, ps, move, src, dst = controllers.promc_tick(
            self.ops, eta, self.rate_est, n_ch, live, self.streak,
            self.pair_fast, self.pair_slow, self.promc_ratio,
            self.promc_patience,
        )
        self.streak = np.where(rows, streak, self.streak)
        self.pair_fast = np.where(rows, pf, self.pair_fast)
        self.pair_slow = np.where(rows, ps, self.pair_slow)
        # grow the resume stack whenever it is full, even on no-move ticks:
        # the JAX backend parks a row on prospective overflow, and replaying
        # its tick must leave headroom or the row would re-park every tick
        while (self.prepend_n >= self.P).any():
            self._grow_prepend()
        moving = rows & move
        if not moving.any():
            return
        (
            self.chunk_of, self.busy, self.dead, self.rem, self.cap,
            self.queue_bytes, self.prepend_sizes, self.prepend_n,
            self.n_moves,
        ) = controllers.move_channel(
            self.ops, moving, src, dst, self.chunk_of, self.busy,
            self.dead, self.rem, self.cap, self.queue_bytes,
            self.prepend_sizes, self.prepend_n, self.n_moves, self.par,
            self.cap_k, self.setup_cost,
        )
        self._feed_vec(moving)

    # ------------------------------------------------------------------ #
    # live-row compaction
    # ------------------------------------------------------------------ #

    def _compact(self) -> bool:
        """Retire finished scenarios from the batch arrays.

        Synchronized sweeps pay O(live rows) per iteration; without
        compaction a heterogeneous matrix pays full width until its very
        last straggler finishes. Final metrics of retired rows are archived
        on their runtime objects; surviving rows are re-indexed in place.
        Scenarios are independent, so dropping finished rows cannot change
        any survivor's event sequence.
        """
        alive = np.flatnonzero(~self.done)
        if alive.size == self.S:
            return False
        for r in self.rt:
            if r.archive is None and self.done[r.index]:
                s = r.index
                r.archive = (
                    float(self.finish_t[s]),
                    int(self.n_events[s]),
                    self.completed_at[s].copy(),
                    self.delivered[s].copy(),
                    int(self.n_moves[s]),
                    self._timeline(s),
                )
        for name in self._row_arrays():
            setattr(self, name, getattr(self, name)[alive])
        self.group_id = self.group_id[alive]
        if self.link_member.shape[0]:
            self.link_member = self.link_member[:, alive]
        survivors = []
        for new_row, s in enumerate(alive):
            r = self.rt[int(s)]
            r.index = new_row
            survivors.append(r)
        self.rt = survivors
        self.S = alive.size
        return True

    def _row_arrays(self) -> tuple:
        return _ROW_ARRAYS

    def _maybe_compact(self) -> None:
        # amortized: only rebuild once half the batch has finished.
        # Coupled batches never compact: a done tenant already releases
        # its link share (zero demand), and keeping row indices stable
        # keeps the (L, S) membership table and group ids frozen for the
        # whole run (the jax backend additionally keeps one compiled
        # program that way).
        if self.coupled:
            return
        if self.S > 16 and int(self.done.sum()) * 2 >= self.S:
            self._compact()

    # ------------------------------------------------------------------ #

    def run(self) -> List[SimResult]:
        all_rt = list(self.rt)
        self.start()
        while not self.done.all():
            self.step()
            self._maybe_compact()
        return [self._result(r) for r in all_rt]

    def _timeline(self, s: int) -> List[tuple]:
        """Finalized (t, rate) samples of row ``s`` (empty when the
        scenario does not record)."""
        return kernels.timeline_samples(
            self.tl_t[s], self.tl_rate[s], self.tl_len[s],
            self.tl_stride[s], self.tl_seen[s], self.tl_last_t[s],
            self.tl_last_rate[s],
        )

    def _result(self, r: _ScenarioRuntime) -> SimResult:
        if r.archive is not None:
            (
                finish_t, n_events, completed_at, delivered, n_moves,
                timeline,
            ) = r.archive
        else:
            s = r.index
            finish_t = float(self.finish_t[s])
            n_events = int(self.n_events[s])
            completed_at = self.completed_at[s]
            delivered = self.delivered[s]
            n_moves = int(self.n_moves[s])
            timeline = self._timeline(s)
        total_time = max(finish_t, _EPS)
        return SimResult(
            network=r.network.name,
            scheduler=r.scheduler.name,
            total_bytes=r.total_bytes,
            total_time=total_time,
            throughput=r.total_bytes / total_time,
            per_chunk_time={
                c.name: float(completed_at[k])
                for k, c in enumerate(r.chunks)
            },
            per_chunk_bytes={
                c.name: float(delivered[k])
                for k, c in enumerate(r.chunks)
            },
            timeline=timeline,
            n_events=n_events,
            n_moves=n_moves,
        )
