"""The jax device loop keeps its state on the device between rounds: each
round's output state is the next round's input, and the host copies the
whole state back (``SYNC_STATS["state_syncs"]``) only before a parked
row's replay, a compaction, an error report or the end of a run.

A small ``_ROUND_CAP`` makes every batch here take many rounds, so most
rounds resume from the device-resident state. The loop programs are
traced with the cap inlined, so the jitted loops and the AOT cache are
cleared on both sides of each test.
"""
import dataclasses

import pytest

from repro.core import testbeds
from repro.core.schedulers import MultiChunkScheduler
from repro.core.simulator import Simulation
from repro.eval import Scenario
from repro.eval.fabric import jax_backend
from repro.eval.fabric.driver import FabricSimulation
from repro.eval.fabric.jax_backend import JaxFabricSimulation
from repro.eval.scenarios import build_simulation

#: iterations per device round: small enough that every batch below
#: needs several rounds
SMALL_CAP = 8

_LOOP_FNS = (jax_backend._device_rounds, jax_backend._device_rounds_coupled)


def _clear_loop_programs():
    for fn in _LOOP_FNS:
        fn.clear_cache()
    jax_backend.reset_aot_cache()


@pytest.fixture
def small_cap(monkeypatch):
    _clear_loop_programs()
    monkeypatch.setattr(jax_backend, "_ROUND_CAP", SMALL_CAP)
    yield
    _clear_loop_programs()


def _mixed(n):
    nets = (testbeds.LAN.name, testbeds.XSEDE.name, testbeds.LONI.name)
    algos = ("sc", "mc", "promc", "untuned")
    return [
        Scenario(
            network=nets[i % len(nets)],
            dataset="uniform_small" if i % 2 else "mixed",
            algorithm=algos[i % len(algos)],
            max_cc=2 + (i % 3) * 2,
            seed=i,
            record_timeline=i % 3 == 0,
        )
        for i in range(n)
    ]


def _run(driver_cls, make_sims, **kwargs):
    """One driver run over fresh simulations: (results, SYNC_STATS)."""
    sims = make_sims()
    jax_backend.reset_sync_stats()
    out = driver_cls(
        sims, names=[f"row{i}" for i in range(len(sims))], **kwargs
    ).run()
    return out, dict(jax_backend.SYNC_STATS)


def _assert_same_as_numpy(got, ref):
    """Every field of every row equal to the NumPy driver's, but the
    per-chunk byte totals: the two sum ``delivered`` in another order
    and may differ in the last place, with or without the resident
    state."""
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        g, r = dataclasses.asdict(g), dataclasses.asdict(r)
        g_bytes, r_bytes = g.pop("per_chunk_bytes"), r.pop("per_chunk_bytes")
        assert g == r
        assert g_bytes.keys() == r_bytes.keys()
        for k in g_bytes:
            assert g_bytes[k] == pytest.approx(r_bytes[k], rel=1e-14), k


def test_resident_rounds_match_one_round_and_numpy(monkeypatch):
    """(a) Built-in schedulers, no compaction: many resident rounds give
    results bit-identical to the same batch run in one device round, and
    equal to the NumPy driver's; the state is synced once, at the end."""
    batch = _mixed(6)
    make = lambda: [build_simulation(sc) for sc in batch]  # noqa: E731
    ref, _ = _run(FabricSimulation, make)
    one, one_stats = _run(JaxFabricSimulation, make)
    assert one_stats["rounds"] == 1

    _clear_loop_programs()
    monkeypatch.setattr(jax_backend, "_ROUND_CAP", SMALL_CAP)
    try:
        got, stats = _run(JaxFabricSimulation, make)
    finally:
        _clear_loop_programs()
    assert stats["rounds"] > 1
    assert stats["iterations"] == one_stats["iterations"]
    assert stats["state_syncs"] == stats["runs"] == 1
    assert stats["replay_rounds"] == 0
    assert [dataclasses.asdict(r) for r in got] == [
        dataclasses.asdict(r) for r in one
    ]
    _assert_same_as_numpy(got, ref)


def test_compaction_rungs_each_sync_once(small_cap, monkeypatch):
    """(b) A batch above its compaction floor: each rung syncs the state
    before the host rebuilds the rows, and the end syncs once more; the
    rounds between rungs stay resident."""
    # 72 rows pad to 128 > the floor of 64: once 40 long rows remain,
    # the batch compacts one rung to 64
    long = _mixed(8)
    short = [
        Scenario(
            network=testbeds.LAN.name, dataset="uniform_small",
            algorithm="untuned", max_cc=2, seed=100 + i,
        )
        for i in range(64)
    ]
    batch = long + short
    make = lambda: [build_simulation(sc) for sc in batch]  # noqa: E731
    rungs = []
    compact = JaxFabricSimulation._maybe_compact

    def counted(self):
        rungs.append(self._pad_rows())
        compact(self)

    monkeypatch.setattr(JaxFabricSimulation, "_maybe_compact", counted)
    got, stats = _run(JaxFabricSimulation, make)
    ref, _ = _run(FabricSimulation, make)
    assert rungs == [128]
    assert stats["state_syncs"] == len(rungs) + 1
    assert stats["rounds"] > stats["state_syncs"]
    _assert_same_as_numpy(got, ref)


class _ParkingMC(MultiChunkScheduler):
    """MC under another class: the fabric treats it as a custom
    scheduler, whose completions park the row for a host replay."""


def _parking_sims():
    sims = []
    for sc in _mixed(4)[:3]:
        base = build_simulation(sc)
        sched = _ParkingMC(base.scheduler.chunks, base.network, sc.max_cc)
        sims.append(
            Simulation(
                sched.chunks, base.network, sched,
                tick_period=sc.tick_period,
            )
        )
    return sims


def test_parked_rows_sync_before_each_replay(small_cap, monkeypatch):
    """(c) A custom scheduler parks rows: each replay reads synced host
    arrays and the next round starts from them again. One sync per
    replay, and one more at the end unless the run ended on a replay,
    whose host arrays are already current."""
    events = []
    for name in ("_upload", "_sync_host", "_post"):
        method = getattr(JaxFabricSimulation, name)

        def logged(self, *args, _name=name, _method=method, **kwargs):
            events.append(_name)
            return _method(self, *args, **kwargs)

        monkeypatch.setattr(JaxFabricSimulation, name, logged)
    got, stats = _run(JaxFabricSimulation, _parking_sims)
    ref, _ = _run(FabricSimulation, _parking_sims)
    replays = events.count("_post")
    assert replays == stats["replay_rounds"] > 0
    assert events.count("_sync_host") == stats["state_syncs"]
    ended_resident = events[-1] == "_sync_host"
    assert stats["state_syncs"] == replays + ended_resident
    for i, event in enumerate(events):
        if event == "_post":
            # synced just before, uploaded again before the next round
            assert events[i - 1] == "_sync_host"
            assert events[i + 1:i + 2] in ([], ["_upload"])
    assert events.count("_upload") == 1 + replays - (not ended_resident)
    assert stats["rounds"] > events.count("_upload")
    _assert_same_as_numpy(got, ref)


def test_error_reports_synced_state(small_cap):
    """An error flag raised after resident rounds syncs the state first:
    the message reads the row's clock as the NumPy driver's does."""
    sc = Scenario(
        network=testbeds.XSEDE.name, dataset="mixed", algorithm="mc",
        max_cc=4,
    )

    def make():
        base = build_simulation(sc)
        return [
            Simulation(
                base.scheduler.chunks, base.network, base.scheduler,
                tick_period=sc.tick_period, max_time=2.0,
            )
        ]

    with pytest.raises(RuntimeError, match="exceeded max_time") as ref:
        _run(FabricSimulation, make)
    jax_backend.reset_sync_stats()
    drv = JaxFabricSimulation(make(), names=["row0"])
    with pytest.raises(RuntimeError, match="exceeded max_time") as got:
        drv.run()
    stats = dict(jax_backend.SYNC_STATS)
    assert str(got.value) == str(ref.value)
    assert stats["rounds"] > 1
    assert stats["state_syncs"] == 1
