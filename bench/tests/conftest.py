"""Shared set-up of the benchmark's own tests: a checkout-like directory
holding smoke-size cells, so a whole run fits on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for path in (REPO, os.path.join(REPO, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


def smoke_config() -> dict:
    """The paper's deployment on two of its testbeds, with a few dozen
    files to each dataset, so that a run and its reference fit a test."""
    with open(os.path.join(BENCH, "configs", "paper_wan.json")) as f:
        cfg = json.load(f)
    nets = list(cfg["networks"])
    cfg["networks"] = {n: cfg["networks"][n] for n in (nets[0], nets[-1])}
    for ds, n in (("des", 12), ("genome", 60), ("mixed", 30)):
        cfg["datasets"][ds]["files"] = n
        if "total_mb" in cfg["datasets"][ds]:
            cfg["datasets"][ds]["total_mb"] *= n / 427
    return cfg


SMOKE_CELLS = [("smoke_wan.heuristics", "smoke_wan", "heuristics")]


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_smoke_root(tmp_path):
    """A checkout with the program's sources, the benchmark's metric
    readers and traffic, and smoke-size cells in its BENCHMARK.json."""
    root = tmp_path / "checkout"
    (root / "bench").mkdir(parents=True)
    os.symlink(os.path.join(REPO, "src"), root / "src")
    for sub in ("metrics", "traffic"):
        shutil.copytree(os.path.join(BENCH, sub), root / "bench" / sub)
    write_json(str(root / "bench" / "configs" / "smoke_wan.json"),
               smoke_config())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "smoke"}
        for n, c, t in SMOKE_CELLS
    ]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    write_json(str(root / "BENCHMARK.json"), bench)
    return root


@pytest.fixture
def smoke_root(tmp_path):
    return make_smoke_root(tmp_path)


def run_cell(root, workload, *extra, seconds="0.5", trace="0"):
    """One in-process run on the CPU; returns (exit code, result line)."""
    import io
    from contextlib import redirect_stdout

    from bench import run

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(
            ["--workload", workload, "--seed", "2147483659",
             "--seconds", seconds, "--trace", trace, *extra],
            require_tpu=False, root=str(root),
        )
    lines = buf.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
