"""The harness end to end on the CPU at smoke size: result line, cells,
refusal without a TPU, and cells found by name."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO, SMOKE_CELLS, run_cell, write_json

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}


@pytest.mark.parametrize("workload", [c[0] for c in SMOKE_CELLS])
def test_smoke_cell_runs_correct(smoke_root, workload):
    rc, res = run_cell(smoke_root, workload)
    assert rc == 0
    assert set(res) == RESULT_KEYS
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"rows_per_s", "host_peak_mb", "setup_s"}
    assert res["device"]["count"] == 1
    assert res["checks"]["median_rel_err"]["value"] < 1e-9


def test_trace_run_reports_per_layer_metrics(smoke_root):
    rc, res = run_cell(smoke_root, "smoke_wan.heuristics", trace="1")
    assert rc == 0 and res["correct"]
    # on the CPU the trace holds no device plane: the device readers
    # find nothing and their metrics are left out
    assert set(res["metrics"]) == {
        "ingest.build_ms", "executor.compute_ms",
        "executor.window_compiles", "loop.device_rounds",
        "setup.compile_s",
    }
    assert res["metrics"]["executor.window_compiles"]["value"] == 0
    assert res["metrics"]["loop.device_rounds"]["value"] > 0


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--workload", "paper_wan.heuristics", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_new_traffic_file_is_found_by_name(smoke_root):
    write_json(str(smoke_root / "bench" / "traffic" / "maxcc.json"), {
        "why": "added by data alone", "algorithms": ["mc", "promc"],
        "max_cc": [2, 16], "num_chunks": [4], "tick_period_s": 5.0,
        "limits": {"worst_rel_err": 2e-4, "median_rel_err": 1e-9},
    })
    path = smoke_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["workloads"].append({"name": "smoke_wan.maxcc",
                               "config": "smoke_wan",
                               "traffic": "maxcc", "chips": 1,
                               "why": "added by data alone"})
    write_json(str(path), bench)
    rc, res = run_cell(smoke_root, "smoke_wan.maxcc")
    assert rc == 0 and res["correct"]
    assert res["attempted"] % (2 * 3 * 2 * 2) == 0


def test_every_cell_has_its_files():
    from bench.run import load_json
    from bench.sweep import rows

    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        cfg = load_json(os.path.join(REPO, configs[cell["config"]]["file"]))
        assert {"networks", "datasets"} <= set(cfg)
        for key in configs[cell["config"]]["reduced"]:
            assert key in cfg
        traffic = load_json(
            os.path.join(BENCH, "traffic", cell["traffic"] + ".json")
        )
        assert rows(traffic, cfg)
        assert "worst_rel_err" in traffic["limits"]
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_paper_datasets_have_the_papers_file_counts():
    from bench.run import load_json
    from bench.sweep import draw_sizes
    import numpy as np

    cfg = load_json(os.path.join(BENCH, "configs", "paper_wan.json"))
    MB = 1 << 20
    rng = np.random.default_rng(5)
    des = draw_sizes(cfg["datasets"]["des"], rng)
    genome = draw_sizes(cfg["datasets"]["genome"], rng)
    mixed = draw_sizes(cfg["datasets"]["mixed"], rng)
    assert (len(des), len(genome), len(mixed)) == (427, 120_000, 6232)
    assert abs(des.sum() - 212 * 1024 * MB) <= 427
    assert np.mean(genome < 100 * 1024) >= 0.45
    assert np.mean(genome < MB) >= 0.93
    assert genome.max() <= 13 * 1024 * MB
    assert mixed.min() >= MB and mixed.max() <= 5 * 1024 * MB
