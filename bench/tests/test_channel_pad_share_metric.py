"""The reader of the program's channel-axis counters, on hand-built runs
and in a traced smoke run on the CPU."""
from __future__ import annotations

import pytest

from conftest import REPO, run_cell

from bench.run import load_reader

NAME = "plan.channel_pad_share"


def _run(**sync):
    base = {"rounds": 636, "iterations": 1_200_000, "state_syncs": 4,
            "runs": 4, "channel_slots": 800, "channel_slots_live": 300}
    base.update(sync)
    return {"sweeps": 2, "sync": base, "window_compiles": 0,
            "setup_compile_s": 9.0, "trace": None}


def test_reader_gives_the_unused_share_of_the_channel_axis():
    read = load_reader(REPO, NAME)
    assert read(_run()) == pytest.approx(62.5)
    assert read(_run(channel_slots_live=800)) == pytest.approx(0.0)


@pytest.mark.parametrize("missing", ["channel_slots", "channel_slots_live"])
def test_reader_finds_nothing_in_a_program_without_the_counters(missing):
    run = _run()
    del run["sync"][missing]
    assert load_reader(REPO, NAME)(run) is None


def test_trace_run_reports_channel_pad_share(smoke_root):
    rc, res = run_cell(smoke_root, "smoke_wan.heuristics", trace="1")
    assert rc == 0 and res["correct"]
    share = res["metrics"][NAME]["value"]
    assert 0.0 <= share < 100.0
