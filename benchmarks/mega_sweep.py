"""16k+-row oracle-plane sweep (or the tenant fleet) with its own telemetry.

:func:`sweep` runs one leg in the calling process and returns one JSON
row; peak RSS is the leg's own (the process high-water mark is reset
first, so earlier work in the same process does not leak into it). The
eval-matrix bench calls it in-process, which keeps one process on the
device.

As a command it prints that row as the last line of stdout.
``--assert-rss-mb`` and ``--assert-min-rows-per-s`` turn it into a
regression gate: non-zero exit when the sweep's peak RSS exceeds the
bound or its throughput falls below it. ``--devices N`` simulates N
host devices (the device count is fixed at jax import, so this needs its
own interpreter) and refuses to run on any platform but the CPU.

Usage::

    PYTHONPATH=src:. python -m benchmarks.mega_sweep \
        --devices 4 --candidates 64
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional


def reset_peak_rss() -> None:
    """Reset the process's peak-RSS high-water mark (Linux ``VmHWM``)."""
    with open("/proc/self/clear_refs", "w") as f:
        f.write("5")


def peak_rss_mb() -> float:
    """Peak RSS since the last :func:`reset_peak_rss`, in MB."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmHWM:"):
                return int(line.split()[1]) / 1024.0
    raise RuntimeError("no VmHWM line in /proc/self/status")


def sweep(
    matrix: str = "full", candidates: int = 64, backend: str = "jax",
    executor: Optional[str] = None,
) -> dict:
    """One leg: the oracle candidate plane over ``matrix`` or, for a
    ``tenant*`` matrix, the coupled fleet (steady, warm cache) with its
    uncoupled twin and contention report. Returns the JSON row."""
    import jax

    from repro.eval.fabric import executor as fabric_executor
    from repro.eval.fabric import jax_backend
    from repro.eval.runner import build_matrix
    from repro.eval.tune import oracle_search

    reset_peak_rss()
    scenarios = build_matrix(matrix)
    extra = {}
    if matrix.startswith("tenant"):
        # coupled throughput vs the same rows with the fabric stripped —
        # the coupled-path overhead — plus the contention report (greedy
        # per-tenant heuristics vs the contended static oracle, scored on
        # the NumPy ground truth)
        import dataclasses as _dc

        from repro.eval.runner import run_matrix
        from repro.eval.tune.contention import contention_report

        run_matrix(scenarios, backend=backend, executor=executor)  # warm
        t0 = time.perf_counter()
        run_matrix(scenarios, backend=backend, executor=executor)
        wall = time.perf_counter() - t0
        stripped = [
            _dc.replace(sc, shared_fabric=None) for sc in scenarios
        ]
        run_matrix(stripped, backend=backend, executor=executor)
        t0 = time.perf_counter()
        run_matrix(stripped, backend=backend, executor=executor)
        uncoupled_wall = time.perf_counter() - t0
        evals = len(scenarios)
        extra = {
            "uncoupled_wall_s": round(uncoupled_wall, 3),
            "coupled_overhead": round(
                wall / max(uncoupled_wall, 1e-9), 3
            ),
            "contention": contention_report(
                scenarios, backend="numpy",
                n_candidates=min(candidates, 8),
            ).summary(),
        }
    else:
        t0 = time.perf_counter()
        result = oracle_search(
            scenarios, backend=backend, n_candidates=candidates,
            executor=executor,
        )
        wall = time.perf_counter() - t0
        evals = result.evals
    return {
        "evals": evals,
        "wall_s": round(wall, 3),
        "rows_per_s": round(evals / max(wall, 1e-9), 1),
        "peak_rss_mb": round(peak_rss_mb(), 1),
        "backend": backend,
        "matrix": matrix,
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "executor": fabric_executor.executor_mode(executor),
        "donation": jax_backend.donation_enabled(),
        "compiled_programs": jax_backend.compiled_program_count(),
        **extra,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--devices", type=int, default=0,
        help="simulate N CPU host devices (0 = leave jax alone); applied "
        "before jax imports, and refused on any platform but the CPU",
    )
    ap.add_argument("--candidates", type=int, default=64)
    ap.add_argument("--backend", default="jax")
    ap.add_argument("--matrix", default="full",
                    choices=("smoke", "default", "full", "tenant",
                             "tenant-smoke"))
    ap.add_argument(
        "--executor", default=None, choices=("serial", "async"),
        help="chunk executor mode (default: REPRO_FABRIC_EXECUTOR/async)",
    )
    ap.add_argument(
        "--assert-rss-mb", type=float, default=None,
        help="fail (exit 1) if the sweep's peak RSS exceeds this bound",
    )
    ap.add_argument(
        "--assert-min-rows-per-s", type=float, default=None,
        help="fail (exit 1) if the sweep's throughput falls below this "
        "bound — CI runs the 4-device sweep against the measured "
        "1-device rate so multi-device scaling can't silently regress",
    )
    args = ap.parse_args(argv)

    if args.devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + flags
        )
    # everything jax-adjacent imports after the flag is set
    import jax

    from repro.eval.fabric.jaxenv import arm_compile_cache

    if args.devices and jax.default_backend() != "cpu":
        print(
            f"FAIL: --devices simulates CPU host devices; the platform "
            f"here is {jax.default_backend()!r}",
            file=sys.stderr,
        )
        return 2
    arm_compile_cache()
    row = sweep(args.matrix, args.candidates, args.backend, args.executor)
    print(json.dumps(row))
    if (
        args.assert_rss_mb is not None
        and row["peak_rss_mb"] > args.assert_rss_mb
    ):
        print(
            f"FAIL: peak RSS {row['peak_rss_mb']:.0f} MB exceeds the "
            f"{args.assert_rss_mb:.0f} MB gate",
            file=sys.stderr,
        )
        return 1
    if (
        args.assert_min_rows_per_s is not None
        and row["rows_per_s"] < args.assert_min_rows_per_s
    ):
        print(
            f"FAIL: {row['rows_per_s']:.1f} rows/s below the "
            f"{args.assert_min_rows_per_s:.1f} rows/s gate",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
