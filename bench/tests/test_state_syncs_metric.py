"""The reader of the program's whole-state sync counter, on hand-built
runs and in a traced smoke run on the CPU."""
from __future__ import annotations

import pytest

from conftest import REPO, run_cell

from bench.run import load_reader

NAME = "loop.state_syncs"


def _run(sweeps, **sync):
    base = {"rounds": 636, "iterations": 1_200_000, "state_syncs": 4,
            "replay_rounds": 0, "runs": 4}
    base.update(sync)
    return {"sweeps": sweeps, "sync": base, "window_compiles": 0,
            "setup_compile_s": 9.0, "trace": None}


def test_reader_gives_syncs_per_sweep():
    read = load_reader(REPO, NAME)
    assert read(_run(2)) == pytest.approx(2.0)
    assert read(_run(0)) is None


def test_reader_finds_nothing_in_a_program_without_the_counter():
    run = _run(2)
    del run["sync"]["state_syncs"]
    assert load_reader(REPO, NAME)(run) is None


def test_trace_run_reports_state_syncs(smoke_root):
    rc, res = run_cell(smoke_root, "smoke_wan.heuristics", trace="1")
    assert rc == 0 and res["correct"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # at least one sync a sweep (each driver run ends with one), never
    # more than one a round
    assert 1 <= m[NAME] <= m["loop.device_rounds"]
