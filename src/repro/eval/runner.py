"""Matrix runner: scenarios -> results on any backend + golden snapshots.

Backends (the ``--backend`` axis shared with ``eval.difftest``):

  - ``event``  the per-scenario discrete-event reference
                (``core.simulator.Simulation``);
  - ``numpy``  the batched fabric driver (alias ``batch``, its historical
                name);
  - ``jax``    the jit/vmap fabric driver (``fabric.jax_backend``).

Batched backends execute in *chunks* of ``chunk_size`` scenarios, ordered
by a cheap per-scenario cost proxy: memory stays bounded at matrix scale
(the 1000+-scenario grid holds every queue of every scenario otherwise)
and each chunk is cost-homogeneous, so one long-running straggler doesn't
pin the whole matrix's sweep width. Results always come back in input
order, and per-scenario outputs are independent of chunk composition —
scenarios never interact.

Golden snapshots are small JSON files mapping scenario name to the metrics
both tests and benchmarks care about (throughput, completion time, event
and move counts). They pin the simulator's behaviour across refactors: a
diff in a golden file is a *reviewable semantic change*, not a test flake.
Refresh with::

    PYTHONPATH=src python -m repro.eval.runner --refresh-golden \
        --out tests/golden/eval_matrix.json

which is also this module's __main__.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence

from repro.core.simulator import SimResult, Simulation

from .fabric.bucketing import bucket, chunk_spans
from .fabric.executor import EXECUTOR_MODES, execute_chunks
from .scenarios import (
    Scenario,
    build_files,
    build_simulation,
    default_matrix,
    full_matrix,
    smoke_matrix,
    tenant_matrix,
)

#: default scenarios per batched execution chunk (bounds peak memory).
#: NumPy sweeps pay per-row Python dispatch, so narrower chunks win; the
#: JAX device loop amortizes fixed per-sweep overhead over width and skips
#: parked rows cheaply, so it prefers wide chunks.
BACKEND_CHUNK_SIZE = {"numpy": 256, "jax": 1024}
DEFAULT_CHUNK_SIZE: Optional[int] = None  # per-backend default above

#: metrics captured per scenario; keep additive — removing/renaming a field
#: invalidates every golden file.
SNAPSHOT_FIELDS = (
    "throughput_gbps",
    "total_time",
    "total_bytes",
    "n_moves",
)

BACKENDS = ("event", "numpy", "jax")

#: scenario ingest paths for the batched backends: the columnar
#: ``ScenarioPlan`` fast path (default) or the legacy per-row
#: ``build_simulation`` object chain (the difftest reference; also the
#: only path for custom scheduler subclasses, which have no Scenario
#: spelling). Select with ``REPRO_FABRIC_INGEST`` or the ``ingest=``
#: kwarg of :func:`run_matrix`.
INGEST_MODES = ("plan", "legacy")

#: prep threads for plan-sliced chunk construction: plan slicing is
#: pure array work (thread-safe, no shared caches), so a few workers
#: keep the device queues fed during multi-device sweeps
PLAN_PREP_WORKERS = 4


def ingest_mode(override: Optional[str] = None) -> str:
    """Resolve the scenario ingest path: explicit ``override`` wins,
    then ``REPRO_FABRIC_INGEST``, then the columnar default."""
    mode = override or os.environ.get("REPRO_FABRIC_INGEST") or "plan"
    if mode not in INGEST_MODES:
        raise ValueError(
            f"unknown ingest mode {mode!r}; options: {INGEST_MODES}"
        )
    return mode


def _resolve_backend(backend: str) -> str:
    if backend == "batch":  # historical alias for the NumPy fast path
        return "numpy"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; options: {BACKENDS} (+ 'batch')"
        )
    return backend


def _driver_cls(backend: str):
    from .fabric.registry import get_backend

    return get_backend(backend)


def cost_estimate(network, files, concurrency: int, tick_period: float) -> float:
    """Cheap *event-count* estimate for cost-homogeneous chunking.

    Batched sweep cost scales with the straggler's event count (file
    completions + controller ticks), so the proxy estimates the transfer
    duration at the *achievable* rate — window-limited streams on lossy
    paths run far below line rate — and converts it to ticks. Shared by
    the scenario cost proxy below and the autotuner's explicit-fileset
    rows (successive halving's sketch rungs).
    """
    from repro.core.netmodel import channel_rate_cap

    total = sum(f.size for f in files)
    est_rate = min(
        network.bandwidth,
        network.disk.streaming_rate,
        max(1, concurrency) * channel_rate_cap(network, 4),
    )
    duration = total / max(est_rate, 1.0)
    return duration / max(tick_period, 1e-9) + len(files)


def _effective_cc(scenario: Scenario) -> int:
    # static candidate rows run at their own fixed concurrency, not the
    # heuristics' maxCC budget
    return (
        scenario.static_params[2]
        if scenario.static_params is not None
        else scenario.max_cc
    )


def _cost_proxy(scenario: Scenario) -> float:
    from repro.core import testbeds

    net = testbeds.TESTBEDS[scenario.network]
    return cost_estimate(
        net, build_files(scenario), _effective_cc(scenario),
        scenario.tick_period,
    )


def shape_hint(concurrency: int) -> int:
    """Chunk-grouping key for shape-homogeneous batches: the pow2 bucket
    the row's worst-case channel axis lands in (the jax driver pre-sizes
    C/P from ``capacity_need`` by doubling from 4). Grouping rows by this
    hint *before* cost-sorting keeps a cc=32 candidate from dragging every
    cc<=8 row in its chunk up to the C=32 compiled program."""
    return bucket(concurrency, 4)


def run_scenario(scenario: Scenario, backend: str = "event") -> SimResult:
    backend = _resolve_backend(backend)
    if backend == "event":
        if scenario.shared_fabric is not None:
            from .fabric.coupled_event import run_event_coupled

            return run_event_coupled([scenario])[0]
        return build_simulation(scenario).run()
    return run_matrix([scenario], backend=backend)[0]


def _group_atomic_parts(
    order: Sequence[int], fabrics: Sequence, size: int
) -> tuple:
    """Split a cost-sorted row order into ``(uncoupled_order,
    coupled_parts)``.

    A shared-fabric group is only coupled when its members share a batch,
    so chunking must never split one: coupled rows leave the ordinary
    cost-sorted span stream and are packed whole-group (greedily, in
    first-appearance order) into their own execution parts of at most
    ``size`` rows — a group larger than ``size`` still stays whole in an
    oversized part. Uncoupled rows keep the untouched span path, so
    matrices without fabrics chunk exactly as before.
    """
    uncoupled = [i for i in order if fabrics[i] is None]
    groups: Dict[str, List[int]] = {}
    for i in order:
        if fabrics[i] is not None:
            groups.setdefault(fabrics[i].group, []).append(i)
    parts: List[List[int]] = []
    cur: List[int] = []
    for rows in groups.values():
        if cur and len(cur) + len(rows) > size:
            parts.append(cur)
            cur = []
        cur.extend(rows)
    if cur:
        parts.append(cur)
    return uncoupled, parts


def run_built(
    builders: Sequence,
    names: Sequence[str],
    costs: Optional[Sequence[float]] = None,
    backend: str = "numpy",
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
    hints: Optional[Sequence[int]] = None,
    executor: Optional[str] = None,
    fabrics: Optional[Sequence] = None,
) -> List[SimResult]:
    """Chunked batched execution of *lazily built* Simulations.

    ``fabrics`` is the optional per-row ``SharedFabric`` column: coupled
    rows are chunked group-atomically (see :func:`_group_atomic_parts`)
    and the column is threaded to the driver so shared-link contention
    actually couples them; an all-``None`` (or absent) column keeps the
    historical chunking byte for byte.

    ``builders[i]`` is a zero-argument callable returning a fresh
    ``Simulation`` (schedulers are stateful, so every run needs its own);
    construction happens per chunk, so peak memory holds one chunk's
    queues, not the whole sweep's. ``costs`` orders rows into
    cost-homogeneous chunks exactly like :func:`run_matrix`'s scenario
    proxy. This is the execution primitive shared by the scenario-matrix
    runner and the autotuner (:mod:`repro.eval.tune`), whose
    successive-halving rungs sweep candidate rows that are not matrix
    scenarios (subsampled filesets).

    On the jax backend two more shape-canonicalization steps apply (see
    :mod:`repro.eval.fabric.bucketing`): rows are grouped by ``hints``
    (the :func:`shape_hint` capacity bucket) before cost-sorting, and
    chunk spans are cut power-of-two-aligned so live rows fill the padded
    device shape instead of sweeping dead pad width.

    ``executor`` picks the chunk execution strategy (see
    :mod:`repro.eval.fabric.executor`): the default async pipeline
    overlaps next-chunk host prep and AOT warm-compiles with in-flight
    device compute and round-robins chunks across devices;
    ``"serial"`` (or ``REPRO_FABRIC_EXECUTOR=serial``) restores the
    historical strictly-serial loop. Results are in input order and
    per-row outputs are identical under either mode — scenarios never
    interact.
    """
    backend = _resolve_backend(backend)
    if chunk_size is not None and chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    if backend == "event":
        return [b().run() for b in builders]
    cls = _driver_cls(backend)
    order = list(range(len(builders)))
    aligned = backend == "jax"
    if costs is not None:
        if aligned and hints is not None:
            order.sort(key=lambda i: (hints[i], costs[i]))
        else:
            order.sort(key=lambda i: costs[i])
    size = chunk_size or BACKEND_CHUNK_SIZE[backend]
    results: List[Optional[SimResult]] = [None] * len(builders)
    make_chunk = None
    if fabrics is not None and any(f is not None for f in fabrics):
        uncoupled, coupled_parts = _group_atomic_parts(order, fabrics, size)
        parts = [
            uncoupled[lo:hi]
            for lo, hi in chunk_spans(
                len(uncoupled), size, pad_aligned=aligned
            )
        ] + coupled_parts
        placed = getattr(cls, "supports_device_placement", False)

        def make_chunk(part, dev):
            kwargs = {"device": dev} if placed else {}
            return cls(
                [builders[i]() for i in part],
                names=[names[i] for i in part],
                fabric=[fabrics[i] for i in part],
                **kwargs,
            )

    else:
        parts = [
            order[lo:hi]
            for lo, hi in chunk_spans(len(order), size, pad_aligned=aligned)
        ]
    execute_chunks(
        cls, parts, builders, names, results, mode=executor,
        make_chunk=make_chunk,
    )
    return results  # type: ignore[return-value]


def run_plan(
    plan,
    backend: str = "numpy",
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
    executor: Optional[str] = None,
) -> List[SimResult]:
    """Chunked batched execution of a columnar :class:`ScenarioPlan`.

    The plan-path twin of :func:`run_built`: same cost-homogeneous
    ordering (the plan's vectorized proxy computes the identical
    doubles), same shape-hint grouping and pow2-aligned spans on jax —
    but each chunk is ``plan.take(part)`` (thread-safe array slicing)
    handed straight to the driver's batch constructor, so the executor
    fans chunk prep over several workers instead of one ordered Python
    build thread.
    """
    backend = _resolve_backend(backend)
    if backend == "event":
        raise ValueError("the event backend has no columnar ingest path")
    if chunk_size is not None and chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    cls = _driver_cls(backend)
    n = plan.n_rows
    costs = plan.cost_proxy()
    order = list(range(n))
    aligned = backend == "jax"
    if aligned:
        hints = plan.shape_hints()
        order.sort(key=lambda i: (hints[i], costs[i]))
    else:
        order.sort(key=lambda i: costs[i])
    size = chunk_size or BACKEND_CHUNK_SIZE[backend]
    results: List[Optional[SimResult]] = [None] * n
    fabrics = getattr(plan, "fabrics", None)
    if fabrics is not None and any(f is not None for f in fabrics):
        uncoupled, coupled_parts = _group_atomic_parts(order, fabrics, size)
        parts = [
            uncoupled[lo:hi]
            for lo, hi in chunk_spans(
                len(uncoupled), size, pad_aligned=aligned
            )
        ] + coupled_parts
    else:
        parts = [
            order[lo:hi]
            for lo, hi in chunk_spans(n, size, pad_aligned=aligned)
        ]
    placed = getattr(cls, "supports_device_placement", False)
    # fleet-scale planes (at least one full chunk) floor every chunk's
    # padded row count at the batch's compaction floor: the remainder
    # spans then occupy the SAME device shape as the big chunks' bottom
    # rung instead of minting a one-off small program (each is seconds
    # of per-process trace + executable materialization). The floor is
    # the driver's own (PLAN_COMPACT_FLOOR for all-static planes), so
    # tail chunks share the plane's 256-row program.
    want_pad_floor = aligned and n >= size

    def make_chunk(part, dev):
        kwargs = {"device": dev} if placed else {}
        drv = cls(None, plan=plan.take(part), **kwargs)
        # coupled chunks never compact, so pinning the pad floor would
        # only inflate their fixed device shape
        if want_pad_floor and not drv.coupled:
            drv._pad_floor = drv.compact_floor()
        return drv

    execute_chunks(
        cls, parts, None, None, results, mode=executor,
        make_chunk=make_chunk, prep_workers=PLAN_PREP_WORKERS,
    )
    return results  # type: ignore[return-value]


def run_matrix(
    scenarios: Sequence[Scenario],
    backend: str = "numpy",
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
    executor: Optional[str] = None,
    ingest: Optional[str] = None,
) -> List[SimResult]:
    """Run every scenario; order of results matches the input order.

    Batched backends default to the columnar plan ingest (one vectorized
    build per transfer context, broadcast across candidate rows); the
    event reference — and ``ingest="legacy"`` /
    ``REPRO_FABRIC_INGEST=legacy`` — keeps the per-row object chain.
    """
    backend_r = _resolve_backend(backend)
    if backend_r == "event" and any(
        sc.shared_fabric is not None for sc in scenarios
    ):
        from .fabric.coupled_event import run_event_coupled

        return run_event_coupled(scenarios)
    if backend_r != "event" and ingest_mode(ingest) == "plan":
        from .fabric.plan import build_plan, plan_supported

        if plan_supported(scenarios):
            return run_plan(
                build_plan(scenarios), backend=backend_r,
                chunk_size=chunk_size, executor=executor,
            )
    return run_built(
        [
            (lambda sc=sc: build_simulation(sc))
            for sc in scenarios
        ],
        names=[sc.name for sc in scenarios],
        costs=[_cost_proxy(sc) for sc in scenarios],
        backend=backend,
        chunk_size=chunk_size,
        hints=[shape_hint(_effective_cc(sc)) for sc in scenarios],
        executor=executor,
        fabrics=[sc.shared_fabric for sc in scenarios],
    )


def run_simulations(
    sims: Sequence["Simulation"],
    names: Optional[Sequence[str]] = None,
    backend: str = "numpy",
) -> List[SimResult]:
    """Batch-execute prebuilt Simulations (for sweeps that don't fit the
    Scenario grid, e.g. the figure benchmarks' custom dataset scales)."""
    backend = _resolve_backend(backend)
    if backend == "event":
        return [sim.run() for sim in sims]
    return _driver_cls(backend)(sims, names=names).run()


# --------------------------------------------------------------------------
# golden snapshots
# --------------------------------------------------------------------------


def metrics_snapshot(
    scenarios: Sequence[Scenario], results: Sequence[SimResult]
) -> Dict[str, Dict[str, float]]:
    snap: Dict[str, Dict[str, float]] = {}
    for sc, r in zip(scenarios, results):
        snap[sc.name] = {
            "throughput_gbps": round(r.throughput_gbps, 6),
            "total_time": round(r.total_time, 6),
            "total_bytes": float(r.total_bytes),
            "n_moves": int(r.n_moves),
        }
    return snap


def save_golden(path: str, snapshot: Dict[str, Dict[str, float]]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(snapshot, f, indent=1, sort_keys=True)
        f.write("\n")


def load_golden(path: str) -> Dict[str, Dict[str, float]]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class GoldenDeviation:
    scenario: str
    field: str
    golden: float
    observed: float

    @property
    def rel_err(self) -> float:
        denom = max(abs(self.golden), 1e-12)
        return abs(self.observed - self.golden) / denom


def compare_golden(
    golden: Dict[str, Dict[str, float]],
    observed: Dict[str, Dict[str, float]],
    rtol: float = 1e-6,
    fields: Iterable[str] = ("throughput_gbps", "total_time"),
) -> List[GoldenDeviation]:
    """Deviations of ``observed`` from ``golden`` beyond ``rtol`` (plus any
    scenario missing from either side, reported with NaN metrics)."""
    out: List[GoldenDeviation] = []
    for name in sorted(set(golden) | set(observed)):
        if name not in golden or name not in observed:
            out.append(
                GoldenDeviation(name, "presence", float("nan"), float("nan"))
            )
            continue
        for f in fields:
            dev = GoldenDeviation(name, f, golden[name][f], observed[name][f])
            if dev.rel_err > rtol:
                out.append(dev)
    return out


MATRIX_NAMES = ("default", "smoke", "full", "tenant", "tenant-smoke")


def build_matrix(name: str) -> List[Scenario]:
    if name == "default":
        return default_matrix()
    if name == "smoke":
        return smoke_matrix()
    if name == "full":
        return full_matrix()
    if name == "tenant":
        return tenant_matrix()
    if name == "tenant-smoke":
        return tenant_matrix(n_groups=6)
    raise ValueError(
        f"unknown matrix {name!r}; options: {', '.join(MATRIX_NAMES)}"
    )


def run_tune(args, scenarios: Sequence[Scenario]) -> int:
    """The ``--tune`` subcommand: search the static knob space over the
    matrix and report every heuristic's regret against the result."""
    from . import tune

    history = tune.HistoryStore(args.history) if args.history else None
    searchers = {
        "oracle": tune.oracle_search,
        "sha": tune.successive_halving,
        "hill": tune.hill_climb,
    }
    search = searchers[args.tune]
    result = search(
        scenarios,
        backend=args.backend,
        n_candidates=args.candidates,
        history=history,
        chunk_size=args.chunk_size,
        executor=args.executor,
    )
    heuristics = run_matrix(
        scenarios, backend=args.backend, chunk_size=args.chunk_size,
        executor=args.executor,
    )
    report = tune.regret_report(scenarios, heuristics, result)
    n_ctx = len(result.tables)
    print(
        f"tune[{args.tune}]: {len(scenarios)} scenarios, {n_ctx} contexts, "
        f"{result.evals} candidate evaluations "
        f"({result.equivalent_evals:.1f} full-fidelity-equivalent)"
    )
    print(f"regret = heuristic_throughput / {args.tune}_throughput:")
    print(report.format_table())
    if history is not None:
        history.save()
        print(f"warm-start history ({len(history)} winners) -> {args.history}")
    if args.regret_out:
        tune.save_report(args.regret_out, report, result)
        print(f"regret report -> {args.regret_out}")
    return 0


def _print_wall_breakdown() -> None:
    """The ``--verbose`` prep-vs-compute split: aggregate thread-seconds
    per pipeline phase from the shared wall accumulators (phases overlap
    under the async executor, so they need not sum to elapsed time)."""
    from .fabric import stats as fabric_stats

    s = dict(fabric_stats.SYNC_STATS)
    build = s["build_wall_s"]
    compute = s["compute_wall_s"]
    download = s["download_wall_s"]
    total = max(build + compute, 1e-9)
    print(
        "wall breakdown (thread-seconds, phases overlap): "
        f"build {build:.3f}s ({100.0 * build / total:.1f}%) | "
        f"compute {compute:.3f}s ({100.0 * compute / total:.1f}%) | "
        f"download {download:.3f}s (inside compute)"
    )


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--matrix", choices=MATRIX_NAMES, default="default"
    )
    ap.add_argument(
        "--backend", choices=BACKENDS + ("batch",), default="event"
    )
    ap.add_argument(
        "--chunk-size", type=int, default=DEFAULT_CHUNK_SIZE,
        help="scenarios per batched execution chunk (bounds memory)",
    )
    ap.add_argument(
        "--executor", choices=EXECUTOR_MODES, default=None,
        help="chunk execution strategy: the overlap-pipelined multi-"
        "device default ('async') or the historical strictly-serial "
        "loop ('serial'); also via REPRO_FABRIC_EXECUTOR",
    )
    ap.add_argument("--out", default="tests/golden/eval_matrix.json")
    ap.add_argument("--refresh-golden", action="store_true")
    ap.add_argument(
        "--verbose", action="store_true",
        help="print the prep-vs-compute wall breakdown (host chunk "
        "build, driver run, device->host downloads) after the run",
    )
    ap.add_argument(
        "--tune", choices=("oracle", "sha", "hill"), default=None,
        help="search the static (pipelining, parallelism, concurrency) "
        "space over the matrix (exhaustive grid / successive halving / "
        "hill climbing) and report per-algorithm regret vs the result",
    )
    ap.add_argument(
        "--candidates", type=int, default=64,
        help="--tune: candidate-grid budget per scenario context",
    )
    ap.add_argument(
        "--history", default=None, metavar="PATH",
        help="--tune: JSON warm-start store; read to seed the search, "
        "updated with the winners afterwards",
    )
    ap.add_argument(
        "--regret-out", default=None, metavar="PATH",
        help="--tune: write the regret report + search tables as JSON",
    )
    args = ap.parse_args(argv)

    if args.backend == "jax":
        from .fabric.jaxenv import arm_compile_cache

        arm_compile_cache()
    scenarios = build_matrix(args.matrix)
    if args.verbose:
        from .fabric import stats as fabric_stats

        fabric_stats.reset_sync_stats()
    if args.tune:
        rc = run_tune(args, scenarios)
        if args.verbose:
            _print_wall_breakdown()
        return rc
    results = run_matrix(
        scenarios, backend=args.backend, chunk_size=args.chunk_size,
        executor=args.executor,
    )
    if args.verbose:
        _print_wall_breakdown()
    snap = metrics_snapshot(scenarios, results)
    if args.refresh_golden:
        save_golden(args.out, snap)
        print(f"wrote {len(snap)} scenario metrics to {args.out}")
        return 0
    golden = load_golden(args.out)
    devs = compare_golden(golden, snap)
    for d in devs[:20]:
        print(f"DEVIATION {d.scenario} {d.field}: "
              f"golden={d.golden} observed={d.observed}")
    print(f"{len(snap)} scenarios, {len(devs)} deviations")
    return 1 if devs else 0


if __name__ == "__main__":
    raise SystemExit(main())
