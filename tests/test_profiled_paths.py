"""The jax backend on profiled (time-varying capacity) paths, at smoke
size: the cross-traffic deployment of ``bench/configs/
paper_wan_xtraffic.json`` on one testbed under both of its profiles,
with the step times scaled down so that the steps fall inside the
transfers. Every row agrees with the plain reference
(``bench/reference/transfer.py``) and with the event simulator, and the
loop's counters say how many steps fell inside the transfers and how
much of the channel axis is padding."""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from repro.eval.difftest import DEFAULT_RTOL
from repro.eval.fabric import jax_backend
from repro.eval.fabric import plan as plan_module
from repro.eval.fabric.jax_backend import JaxFabricSimulation
from repro.eval.fabric.plan import build_plan
from repro.eval.runner import run_matrix

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench.check import reference_throughputs, rel_gaps  # noqa: E402
from bench.reference import transfer  # noqa: E402
from bench.sweep import Sweep  # noqa: E402

TESTBED = "stampede-comet"
SHAPES = ("burst", "drain")
ALGORITHMS = ("sc", "mc", "promc", "globus")
#: the configuration's steps are 120 s apart; the smoke datasets move in
#: seconds
TIME_SCALE = 0.01
SEED = 2147483659
#: the program and the reference compute the same float64 steps: their
#: answers differ in the last places only
REFERENCE_RTOL = 1e-9


def smoke_config(profiled: bool = True) -> dict:
    """``paper_wan_xtraffic`` at smoke size: one testbed under both of its
    profiles, a few dozen files to each dataset, and the step times
    scaled by ``TIME_SCALE`` so that the steps fall inside the transfers;
    with ``profiled`` false, the same paths at constant capacity. The
    benchmark's smoke cell (``bench/tests/test_xtraffic_cell.py``) runs
    it too."""
    with open(os.path.join(REPO, "bench", "configs",
                           "paper_wan_xtraffic.json")) as f:
        cfg = json.load(f)
    nets = {}
    for shape in SHAPES:
        name = f"{TESTBED}-{shape}"
        spec = cfg["networks"][name]
        spec["bandwidth_profile"] = (
            [[t * TIME_SCALE, m] for t, m in spec["bandwidth_profile"]]
            if profiled else None
        )
        nets[name] = spec
    cfg["networks"] = nets
    for ds, n in (("des", 12), ("mixed", 30)):
        spec = cfg["datasets"][ds]
        if "total_mb" in spec:
            spec["total_mb"] *= n / spec["files"]
        spec["files"] = n
    return cfg


_TRAFFIC = {"algorithms": list(ALGORITHMS), "max_cc": [8],
            "num_chunks": [4], "tick_period_s": 5.0}


def _sweep(profiled: bool = True) -> Sweep:
    name = "test_xtraffic" if profiled else "test_xtraffic_static"
    return Sweep(name, smoke_config(profiled), _TRAFFIC, SEED)


@pytest.fixture(scope="module")
def answers():
    """Each row's throughput from the jax backend (with the loop's
    counters over that one sweep), the event simulator and the
    reference, and the reference's finish times."""
    sweep = _sweep()
    jax_backend.reset_sync_stats()
    got = sweep.run()
    stats = dict(jax_backend.SYNC_STATS)
    event = np.array([r.throughput for r in run_matrix(
        sweep.scenarios, backend="event")])
    tasks = sweep.tasks()
    ref = transfer.simulate(tasks)
    return {
        "sweep": sweep, "jax": got, "event": event, "stats": stats,
        "ref": np.array([r["throughput"] for r in ref]),
        "finish": np.array([r["total_time"] for r in ref]),
        "profiles": [t["network"]["bandwidth_profile"] for t in tasks],
    }


def _rows(answers, shape, algorithm):
    rows = [i for i, (net, _, algo, _, _) in enumerate(answers["sweep"].rows)
            if net == f"{TESTBED}-{shape}" and algo == algorithm]
    assert len(rows) == 2  # DES and mixed
    return rows


def _steps_inside(profile, finish):
    return sum(1 for t, _ in profile if 0.0 < t <= finish)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_profiled_rows_agree_with_reference_and_event_sim(
    answers, shape, algorithm
):
    rows = _rows(answers, shape, algorithm)
    # the capacity changes inside every one of these transfers
    for i in rows:
        assert _steps_inside(answers["profiles"][i], answers["finish"][i])
    got = answers["jax"][rows]
    assert np.all(rel_gaps(got, answers["ref"][rows]) <= REFERENCE_RTOL)
    assert np.all(rel_gaps(got, answers["event"][rows]) <= DEFAULT_RTOL)


def test_profile_steps_count_the_steps_inside_the_transfers(answers):
    want = sum(_steps_inside(p, f)
               for p, f in zip(answers["profiles"], answers["finish"]))
    assert want > 0
    assert answers["stats"]["profile_steps"] == want


def test_profile_steps_count_rows_retired_by_compaction():
    """A profiled batch that compacts mid-run: the rows it retires are
    counted from the sync before the compaction, and they and the rows
    it carries on at the smaller pad agree with the reference."""
    traffic = dict(_TRAFFIC, max_cc=[4, 8])
    sweep = Sweep("test_xtraffic_compact", smoke_config(), traffic, SEED)
    plan = build_plan(sweep.scenarios)
    assert plan.n_rows == 32
    sim = JaxFabricSimulation(None, plan=plan)
    # a floor under the batch's pad of 32, so the tail compacts to 8
    sim._compact_floor = 8
    jax_backend.reset_sync_stats()
    results = sim.run()
    stats = jax_backend.SYNC_STATS
    assert stats["state_syncs"] > stats["runs"] == 1
    profiles = [t["network"]["bandwidth_profile"] for t in sweep.tasks()]
    want = sum(_steps_inside(p, r.total_time)
               for p, r in zip(profiles, results))
    assert want > 0
    assert stats["profile_steps"] == want
    ref = transfer.simulate(sweep.tasks())
    got = np.array([r.throughput for r in results])
    assert np.all(rel_gaps(got, np.array([r["throughput"] for r in ref]))
                  <= REFERENCE_RTOL)


def test_profile_steps_read_zero_on_static_paths():
    sweep = _sweep(profiled=False)
    jax_backend.reset_sync_stats()
    got = sweep.run()
    assert jax_backend.SYNC_STATS["profile_steps"] == 0
    assert jax_backend.SYNC_STATS["runs"] > 0
    ref = reference_throughputs(sweep.tasks())
    assert np.all(rel_gaps(got, ref) <= REFERENCE_RTOL)


def _widened(scenarios: list) -> list:
    """The scenarios with the first MC row at maxCC 16: its ``cap_need``
    is 16, above the plan's channel floor."""
    i = next(i for i, sc in enumerate(scenarios) if sc.algorithm == "mc")
    return scenarios[:i] + [
        dataclasses.replace(scenarios[i], max_cc=16)
    ] + scenarios[i + 1:]


@pytest.mark.parametrize("profiled, widen, want_c", [
    (True, False, 8), (False, False, 8), (True, True, 16),
], ids=["profiled", "static", "profiled-wide"])
def test_channel_slots_match_the_batch_shape(profiled, widen, want_c):
    scenarios = _sweep(profiled).scenarios
    if widen:
        scenarios = _widened(scenarios)
    plan = build_plan(scenarios)
    assert plan.cap_need.max() == want_c
    sim = JaxFabricSimulation(None, plan=plan)
    jax_backend.reset_sync_stats()
    sim.run()
    stats = jax_backend.SYNC_STATS
    # profiled and static rows at maxCC 8 pad their channel axis to the
    # plan's floor; a batch with a row that needs more pads to its
    # capacity bucket and still runs without a host replay
    assert sim.C == want_c
    assert stats["replay_rounds"] == stats["post_row_replays"] == 0
    assert stats["runs"] == 1
    assert stats["channel_slots"] == plan.n_rows * sim.C
    assert stats["channel_slots_live"] == int(
        np.minimum(plan.cap_need, sim.C).sum()
    )
    assert 0 < stats["channel_slots_live"] <= stats["channel_slots"]


def test_channel_pad_changes_no_answer(answers, monkeypatch):
    """The profiled smoke sweep at a channel floor of 16 gives the same
    answers, in the same loop iterations, as at the plan's floor of 8:
    the padded columns are never selected."""
    assert plan_module.PLAN_C_FLOOR == 8
    monkeypatch.setattr(plan_module, "PLAN_C_FLOOR", 16)
    jax_backend.reset_sync_stats()
    wide = answers["sweep"].run()
    stats = jax_backend.SYNC_STATS
    narrow = answers["stats"]
    assert np.array_equal(wide, answers["jax"])
    assert stats["iterations"] == narrow["iterations"]
    assert stats["channel_slots_live"] == narrow["channel_slots_live"]
    assert stats["channel_slots"] == 2 * narrow["channel_slots"]
