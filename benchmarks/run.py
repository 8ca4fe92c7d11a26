"""Benchmark harness entry point: one module per paper table/figure plus the
TPU-adaptation benches. Prints ``name,us_per_call,derived`` CSV rows and a
paper-claim validation summary.

    PYTHONPATH=src python -m benchmarks.run [--only fig1]
    PYTHONPATH=src python -m benchmarks.run --only eval_matrix \
        --bench-json BENCH_eval_matrix.json

``--bench-json`` writes the eval-matrix perf trajectory (scenarios/sec per
backend, wall times, grid size, jax/numpy crossover) so future PRs have a
baseline to beat; the checked-in ``BENCH_eval_matrix.json`` is the first
point of that trajectory.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks.common import Claims

MODULES = [
    ("fig1_2", "benchmarks.fig1_fig2_param_sweeps"),
    ("fig5_6", "benchmarks.fig5_fig6_chunk_counts"),
    ("fig7", "benchmarks.fig7_dataset_size"),
    ("fig9_11", "benchmarks.fig9_10_11_datasets"),
    ("fig12_13", "benchmarks.fig12_fig13_promc_lan"),
    ("eval_matrix", "benchmarks.eval_matrix_bench"),
    ("grad_sync", "benchmarks.grad_sync_bench"),
    ("checkpoint", "benchmarks.checkpoint_bench"),
    ("kernels", "benchmarks.kernel_bench"),
    ("roofline", "benchmarks.roofline_report"),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter on module")
    ap.add_argument(
        "--bench-json", default=None, metavar="PATH",
        help="write the eval_matrix perf snapshot to PATH "
        "(runs the eval_matrix bench if --only filtered it out)",
    )
    args = ap.parse_args()

    # the persistent compilation cache: JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache — the jax benches then measure cache reads,
    # not recompiles, once a previous run has filled it
    from repro.eval.fabric.jaxenv import arm_compile_cache

    arm_compile_cache()

    claims = Claims()
    failed = []
    print("name,us_per_call,derived")
    t_start = time.time()
    for key, modname in MODULES:
        if args.only and args.only not in key:
            if not (args.bench_json and key == "eval_matrix"):
                continue
        t0 = time.time()
        mod = __import__(modname, fromlist=["run"])
        try:
            rows = mod.run(claims)
        except Exception as e:  # reported, and the run exits nonzero
            print(f"{key}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            failed.append(key)
            continue
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}",
                  flush=True)
        print(f"# {key} done in {time.time()-t0:.1f}s", file=sys.stderr)

    if args.bench_json:
        from benchmarks import eval_matrix_bench

        if eval_matrix_bench.LAST_SNAPSHOT is None:
            print("# bench-json: eval_matrix did not produce a snapshot",
                  file=sys.stderr)
        else:
            with open(args.bench_json, "w") as f:
                json.dump(eval_matrix_bench.LAST_SNAPSHOT, f, indent=1)
                f.write("\n")
            print(f"# wrote {args.bench_json}", file=sys.stderr)

    print(claims.report())
    print(f"# total {time.time()-t_start:.1f}s", file=sys.stderr)
    n_missed = sum(not r["ok"] for r in claims.results)
    if n_missed:
        print(f"# WARNING: {n_missed} claim(s) missed", file=sys.stderr)
    if failed:
        print(f"# FAILED: bench(es) raised: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
