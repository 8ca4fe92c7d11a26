"""The cross-traffic cell at smoke size on the CPU: the configuration's
profiled paths run through the harness, every answer agrees with the
reference, and a traced run reports the profile-step and channel-pad
counters; the profile-step reader on hand-built runs."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

from conftest import BENCH, REPO, run_cell, write_json

from bench.run import load_reader

# the tier-1 tests' smoke size of the configuration: one testbed's two
# profiled paths, a few dozen files to each dataset, the step times
# scaled down so that the steps fall inside the transfers
sys.path.insert(0, os.path.join(REPO, "tests"))
from test_profiled_paths import smoke_config  # noqa: E402

CELL = "smoke_xtraffic.adaptive"


@pytest.fixture
def xtraffic_root(tmp_path):
    root = tmp_path / "checkout"
    (root / "bench").mkdir(parents=True)
    os.symlink(os.path.join(REPO, "src"), root / "src")
    for sub in ("metrics", "traffic"):
        shutil.copytree(os.path.join(BENCH, sub), root / "bench" / sub)
    write_json(str(root / "bench" / "configs" / "smoke_xtraffic.json"),
               smoke_config())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": CELL, "config": "smoke_xtraffic",
                           "traffic": "xtraffic", "chips": 1,
                           "why": "smoke"}]
    for m in bench["per_layer"]:
        if "paper_wan_xtraffic.adaptive" in m.get("workloads", []):
            m["workloads"] = [CELL]
    write_json(str(root / "BENCHMARK.json"), bench)
    return root


def test_smoke_cell_is_correct(xtraffic_root):
    rc, res = run_cell(xtraffic_root, CELL)
    assert rc == 0
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    # 2 paths x 2 datasets x 4 schedulers, each sweep of the window
    assert res["attempted"] > 0 and res["attempted"] % 16 == 0
    assert set(res["metrics"]) == {"rows_per_s", "host_peak_mb", "setup_s"}


def test_trace_run_reports_profile_steps_and_channel_pad(xtraffic_root):
    rc, res = run_cell(xtraffic_root, CELL, trace="1")
    assert rc == 0 and res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) == {"loop.profile_steps", "plan.channel_pad_share"}
    # 16 rows, 15 steps after t = 0 each: the scaled steps fall inside
    # the transfers
    assert 0 < m["loop.profile_steps"] <= 16 * 15
    # every row is profiled: C = 16 against at most 8 channels a row
    assert 50.0 <= m["plan.channel_pad_share"] < 100.0


def _run(sweeps, **sync):
    base = {"rounds": 4, "iterations": 9000, "runs": 2,
            "profile_steps": 700}
    base.update(sync)
    return {"sweeps": sweeps, "sync": base, "window_compiles": 0,
            "setup_compile_s": 9.0, "trace": None}


def test_profile_steps_reader_on_hand_built_runs():
    read = load_reader(REPO, "loop.profile_steps")
    assert read(_run(2)) == pytest.approx(350.0)
    assert read(_run(0)) is None


def test_profile_steps_reader_finds_nothing_without_the_counter():
    run = _run(2)
    del run["sync"]["profile_steps"]
    assert load_reader(REPO, "loop.profile_steps")(run) is None
