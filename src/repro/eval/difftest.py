"""Differential testing: fabric backends vs the event-driven reference.

The fabric drivers re-implement the event semantics with vectorized
numerics; this harness is the contract that keeps every implementation
equivalent. For each scenario in a matrix it runs a backend pair and
compares throughput (and completion time, which is 1:1 with throughput for
a fixed byte count) under a relative tolerance — the acceptance bar is 2%
on every scenario, not on the average. In practice agreement is bit-level
(~1e-16): all backends execute the same per-scenario event sequence.

The JAX backend is held to the bar twice: against the event simulator
(the semantics ground truth) *and* against the NumPy fast path (so the
two fabric instantiations cannot drift apart silently)::

    PYTHONPATH=src python -m repro.eval.difftest --backend jax --smoke
    PYTHONPATH=src python -m repro.eval.difftest --backend all --matrix full
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

from .runner import DEFAULT_CHUNK_SIZE, build_matrix, run_matrix
from .scenarios import Scenario

DEFAULT_RTOL = 0.02


@dataclasses.dataclass(frozen=True)
class DiffReport:
    scenario: str
    event_throughput: float  # reference backend
    batch_throughput: float  # backend under test
    event_time: float
    batch_time: float
    reference: str = "event"
    backend: str = "numpy"

    @property
    def rel_err(self) -> float:
        denom = max(abs(self.event_throughput), 1e-12)
        return abs(self.batch_throughput - self.event_throughput) / denom

    def ok(self, rtol: float = DEFAULT_RTOL) -> bool:
        return self.rel_err <= rtol


def pair_results(
    scenarios: Sequence[Scenario],
    ref_results,
    test_results,
    reference: str = "event",
    backend: str = "numpy",
) -> List[DiffReport]:
    """Pair two backends' already-computed results into DiffReports."""
    return [
        DiffReport(
            scenario=sc.name,
            event_throughput=e.throughput,
            batch_throughput=b.throughput,
            event_time=e.total_time,
            batch_time=b.total_time,
            reference=reference,
            backend=backend,
        )
        for sc, e, b in zip(scenarios, ref_results, test_results)
    ]


def diff_matrix(
    scenarios: Sequence[Scenario],
    backend: str = "numpy",
    reference: str = "event",
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
) -> List[DiffReport]:
    """Run ``reference`` and ``backend`` over the matrix and pair results."""
    ref = run_matrix(scenarios, backend=reference, chunk_size=chunk_size)
    test = run_matrix(scenarios, backend=backend, chunk_size=chunk_size)
    return pair_results(scenarios, ref, test, reference, backend)


def assert_agreement(
    reports: Sequence[DiffReport], rtol: float = DEFAULT_RTOL
) -> None:
    """Raise with a readable table of every violator (not just the first)."""
    bad = [r for r in reports if not r.ok(rtol)]
    if not bad:
        return
    lines = [
        f"{len(bad)}/{len(reports)} scenarios exceed rtol={rtol:.3%}:"
    ]
    for r in sorted(bad, key=lambda r: -r.rel_err)[:25]:
        lines.append(
            f"  {r.scenario}: {r.reference}={r.event_throughput:.4g} B/s "
            f"{r.backend}={r.batch_throughput:.4g} B/s rel_err={r.rel_err:.3%}"
        )
    raise AssertionError("\n".join(lines))


def diff_backend(
    scenarios: Sequence[Scenario],
    backend: str,
    rtol: float = DEFAULT_RTOL,
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
    results_cache: Optional[dict] = None,
) -> List[DiffReport]:
    """Enforce the bar for one backend: vs the event reference, and — for
    the JAX backend — additionally vs the NumPy fast path. Each backend
    runs at most once (pass ``results_cache`` to share runs across calls);
    the pairings reuse the computed results."""
    cache = results_cache if results_cache is not None else {}

    def results(b: str):
        if b not in cache:
            cache[b] = run_matrix(scenarios, backend=b, chunk_size=chunk_size)
        return cache[b]

    reports = pair_results(
        scenarios, results("event"), results(backend), "event", backend
    )
    assert_agreement(reports, rtol)
    if backend == "jax":
        cross = pair_results(
            scenarios, results("numpy"), results("jax"), "numpy", "jax"
        )
        assert_agreement(cross, rtol)
        reports = reports + cross
    return reports


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--backend", choices=("numpy", "jax", "batch", "all"),
        default="numpy",
    )
    ap.add_argument(
        "--matrix",
        choices=("smoke", "default", "full", "tenant", "tenant-smoke"),
        default="full",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="shorthand for --matrix smoke (CI fast path)",
    )
    ap.add_argument("--rtol", type=float, default=DEFAULT_RTOL)
    ap.add_argument("--chunk-size", type=int, default=None)
    ap.add_argument(
        "--sample", type=int, default=None, metavar="N",
        help="run a deterministic N-scenario subsample of the matrix "
        "(seeded shuffle; CI uses this for a fast full-matrix slice)",
    )
    ap.add_argument(
        "--sample-seed", type=int, default=0,
        help="seed for --sample (vary to cover different slices)",
    )
    ap.add_argument(
        "--expect-zero-replays", action="store_true",
        help="after a jax run, fail unless SYNC_STATS shows zero parked-"
        "row replays (the zero-host-round invariant for built-in "
        "schedulers; CI gates the fused-jit leg on this)",
    )
    args = ap.parse_args(argv)

    matrix = "smoke" if args.smoke else args.matrix
    scenarios = build_matrix(matrix)
    if args.sample is not None and args.sample < len(scenarios):
        import random

        scenarios = random.Random(args.sample_seed).sample(
            scenarios, args.sample
        )
        matrix = f"{matrix}[sample {args.sample}]"
    backends = ("numpy", "jax") if args.backend == "all" else (
        "numpy" if args.backend == "batch" else args.backend,
    )
    if args.expect_zero_replays and "jax" not in backends:
        ap.error(
            "--expect-zero-replays checks the jax backend's SYNC_STATS; "
            "run with --backend jax or --backend all"
        )
    if "jax" in backends:
        from .fabric.jaxenv import arm_compile_cache

        arm_compile_cache()
    if args.expect_zero_replays:
        from .fabric import jax_backend

        jax_backend.reset_sync_stats()
    cache: dict = {}
    for backend in backends:
        reports = diff_backend(
            scenarios, backend, rtol=args.rtol, chunk_size=args.chunk_size,
            results_cache=cache,
        )
        worst = max((r.rel_err for r in reports), default=0.0)
        print(
            f"difftest OK: backend={backend} matrix={matrix} "
            f"({len(scenarios)} scenarios, worst rel_err {worst:.3e})"
        )
    if args.expect_zero_replays:
        stats = jax_backend.SYNC_STATS
        if stats["post_row_replays"] or stats["replay_rounds"]:
            print(
                "FAIL: expected zero parked-row replays, got "
                f"{stats['post_row_replays']} parked rows across "
                f"{stats['replay_rounds']} replay rounds"
            )
            return 1
        print(
            "SYNC_STATS OK: 0 host rounds/scenario "
            f"(0 parked-row replays across {stats['runs']} runs, "
            f"{stats['scenarios']} scenario-runs)"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
