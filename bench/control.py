"""The lower-precision control of a cell's comparison.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

The program states float64 (it traces its device loop under
``jax.enable_x64`` and uploads float64 state). The control puts the plain
reference, computed in float32, in the program's place: for each seed it
answers every row of the cell's sweep on that seed's data, and
``check.compare`` holds those answers to the float64 reference exactly as
a run holds the program's. It has to fail: its ``worst_rel_err`` is the
upper reading the limit is set below. The benchmark's own runs do not
run it; it needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control(root: str, workload: str, seed: int) -> dict:
    import numpy as np

    from bench import check
    from bench.run import find, load_json
    from bench.sweep import Sweep

    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = find(bench["workloads"], workload, "workload")
    config = load_json(
        os.path.join(root, "bench", "configs", cell["config"] + ".json")
    )
    traffic = load_json(
        os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")
    )
    sweep = Sweep(cell["config"], config, traffic, seed)
    tasks = sweep.tasks()
    workers = traffic.get("reference_workers", 1)
    t0 = time.perf_counter()
    ref = check.reference_throughputs(tasks, workers=workers)
    t_ref = time.perf_counter() - t0
    low = check.reference_throughputs(tasks, np.float32, workers=workers)
    checks = check.compare([low], ref, traffic["limits"], len(tasks),
                           sweep.inputs_off())
    return {"seed": seed, "answers": len(tasks), "reference_s": t_ref,
            "checks": checks, "fails": not check.passed(checks)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    for seed in args.seeds:
        print(json.dumps(control(ROOT, args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT
    raise SystemExit(main())
