"""Scheduler edge cases exercised through the matrix harness plus direct
unit assertions: weighted-distribution invariants, ProMC streak reset,
laggard ETA discounting, SC with empty chunks."""
import math

import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.core import GB, MB, testbeds
from repro.core.schedulers import (
    ChunkView,
    Move,
    Open,
    ProActiveMultiChunkScheduler,
    Scheduler,
    SingleChunkScheduler,
    weighted_distribution,
)
from repro.core.types import Chunk, ChunkType, FileSpec
from repro.eval import Scenario, run_matrix
from repro.eval.fabric import FabricSimulation as BatchSimulation
from repro.eval.scenarios import build_simulation


def _chunk(ctype, n, size):
    return Chunk(
        ctype=ctype,
        files=[FileSpec(f"{ctype.name}{i}", size) for i in range(n)],
    )


# ------------------------------------------------------------------ #
# weighted_distribution: budget + min-1-channel invariants
# ------------------------------------------------------------------ #


@settings(max_examples=150, deadline=None)
@given(
    spec=st.lists(
        st.tuples(
            st.sampled_from(list(ChunkType)[:4]),
            st.integers(min_value=0, max_value=40),
            st.integers(min_value=1 * MB, max_value=int(4 * GB)),
        ),
        min_size=1,
        max_size=4,
        unique_by=lambda t: t[0],
    ),
    max_cc=st.integers(min_value=1, max_value=24),
)
def test_weighted_distribution_invariants(spec, max_cc):
    chunks = [_chunk(ct, n, size) for ct, n, size in spec]
    alloc = weighted_distribution(chunks, max_cc)
    live = [i for i, c in enumerate(chunks) if len(c) > 0]
    # every non-empty chunk gets at least one channel; empty chunks get none
    assert set(alloc) == set(live)
    assert all(alloc[i] >= 1 for i in live)
    # budget: exactly max(max_cc, #live) channels in total (the floor keeps
    # every chunk alive even when maxCC < #chunks)
    if live:
        assert sum(alloc.values()) == max(max_cc, len(live))


def test_weighted_distribution_empty_input():
    assert weighted_distribution([], 8) == {}
    assert weighted_distribution([_chunk(ChunkType.SMALL, 0, MB)], 8) == {}


# ------------------------------------------------------------------ #
# ProMC: streak reset on chunk completion
# ------------------------------------------------------------------ #


def _views(etas_and_channels):
    """(eta, n_channels) pairs -> ChunkViews with throughput arranged so
    eta = bytes_remaining / throughput."""
    views = []
    for i, (eta, n_ch) in enumerate(etas_and_channels):
        views.append(
            ChunkView(
                index=i,
                ctype=list(ChunkType)[i % 4],
                bytes_remaining=eta * 100.0 if math.isfinite(eta) else 1e12,
                files_remaining=5,
                throughput=100.0 if math.isfinite(eta) else 0.0,
                n_channels=n_ch,
                done=False,
                predicted_rate=0.0,
            )
        )
    return views


def _promc(patience=3):
    chunks = [
        _chunk(ChunkType.SMALL, 4, 1 * MB),
        _chunk(ChunkType.HUGE, 4, 2 * GB),
    ]
    return ProActiveMultiChunkScheduler(
        chunks, testbeds.XSEDE, max_cc=8, patience=patience
    )


def test_promc_streak_reset_on_chunk_completion():
    s = _promc(patience=3)
    imbalanced = _views([(10.0, 4), (100.0, 4)])  # 10x ETA gap
    assert s.on_tick(imbalanced) == []
    assert s.on_tick(imbalanced) == []
    assert s._streak == 2
    # a chunk completes between ticks: accumulated evidence must be dropped
    done_view = _views([(0.0, 4), (100.0, 4)])
    s.on_chunk_complete(done_view, 0)
    assert s._streak == 0 and s._streak_pair is None
    # the streak restarts from scratch afterwards
    assert s.on_tick(imbalanced) == []
    assert s._streak == 1


def test_promc_patience_then_single_move():
    s = _promc(patience=2)
    imbalanced = _views([(10.0, 4), (100.0, 4)])
    assert s.on_tick(imbalanced) == []
    actions = s.on_tick(imbalanced)
    assert actions == [Move(src=0, dst=1, n=1)]
    # streak resets after firing; no runaway moves
    assert s._streak == 0
    assert s.on_tick(imbalanced) == []


def test_promc_never_strands_fast_chunk():
    s = _promc(patience=1)
    views = _views([(10.0, 1), (100.0, 7)])  # fast chunk has its last channel
    assert s.on_tick(views) == []


# ------------------------------------------------------------------ #
# distribute_to_laggards: ETA discounting
# ------------------------------------------------------------------ #


def test_distribute_to_laggards_discounts_eta():
    """Freed channels spread across laggards instead of dogpiling the
    single largest-ETA chunk: each grant discounts the receiver's ETA by
    n/(n+1) before the next pick."""
    views = _views([(0.0, 4), (100.0, 2), (90.0, 2)])
    actions = Scheduler.distribute_to_laggards(views, src=0, n_channels=4)
    grants = {a.dst: a.n for a in actions}
    assert sum(grants.values()) == 4
    # 100s chunk: 100 -> 66.7 (3ch) -> 50 (4ch); 90s chunk: 90 -> 60
    # pick order: 100, 90, 66.7, 60 => 2 channels each
    assert grants == {1: 2, 2: 2}
    assert all(a.src == 0 for a in actions)


def test_distribute_to_laggards_infinite_eta_first_then_spreads():
    views = _views([(0.0, 3), (math.inf, 1), (50.0, 2)])
    actions = Scheduler.distribute_to_laggards(views, src=0, n_channels=3)
    grants = {a.dst: a.n for a in actions}
    # the starved (no-measurement) chunk keeps absorbing: inf stays inf
    # under multiplicative discounting — documented greedy behaviour
    assert grants[1] == 3


def test_distribute_to_laggards_no_live_targets():
    views = _views([(0.0, 4)])
    assert Scheduler.distribute_to_laggards(views, src=0, n_channels=4) == []


# ------------------------------------------------------------------ #
# SC ordering with empty chunks
# ------------------------------------------------------------------ #


def test_sc_skips_empty_chunks_and_orders_huge_first():
    chunks = [
        _chunk(ChunkType.SMALL, 3, 1 * MB),
        _chunk(ChunkType.MEDIUM, 0, 100 * MB),  # empty: must be skipped
        _chunk(ChunkType.HUGE, 2, 1 * GB),
    ]
    s = SingleChunkScheduler(chunks, testbeds.XSEDE, max_cc=4)
    first = s.initial_actions([])
    assert len(first) == 1 and isinstance(first[0], Open)
    assert first[0].chunk == 2  # HUGE first
    # completing HUGE must open SMALL (index 0), never the empty MEDIUM
    views = _views([(5.0, 0), (0.0, 0), (0.0, 4)])
    actions = s.on_chunk_complete(views, 2)
    opens = [a for a in actions if isinstance(a, Open)]
    assert [a.chunk for a in opens] == [0]


def test_sc_all_empty_dataset_opens_nothing():
    chunks = [_chunk(ChunkType.SMALL, 0, MB), _chunk(ChunkType.HUGE, 0, GB)]
    s = SingleChunkScheduler(chunks, testbeds.XSEDE, max_cc=4)
    assert s.initial_actions([]) == []


# ------------------------------------------------------------------ #
# the same edges end-to-end through the matrix harness
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("algorithm", ["sc", "mc", "promc"])
def test_matrix_run_with_missing_size_classes(algorithm):
    """uniform datasets produce empty chunks for absent classes; every
    scheduler must still complete them on both backends."""
    for ds in ("uniform_small", "uniform_huge"):
        sc = Scenario(
            network=testbeds.STAMPEDE_COMET.name, dataset=ds,
            algorithm=algorithm, num_chunks=4,
        )
        ev, ba = (
            run_matrix([sc], backend="event")[0],
            run_matrix([sc], backend="batch")[0],
        )
        assert ev.total_bytes > 0
        assert ba.throughput == pytest.approx(ev.throughput, rel=1e-9)


def test_bandwidth_profile_lookup_and_horizon():
    """Piecewise-constant capacity: bandwidth_at steps at the breakpoints
    and next_profile_change exposes the following one (inf at the end)."""
    net = testbeds.impaired_variant(
        testbeds.STAMPEDE_COMET, "step-test",
        bandwidth_steps=((10.0, 0.5), (20.0, 0.8)),
    )
    bw = net.bandwidth
    assert net.bandwidth_at(0.0) == bw
    assert net.bandwidth_at(9.999) == bw
    assert net.bandwidth_at(10.0) == 0.5 * bw
    assert net.bandwidth_at(25.0) == 0.8 * bw
    assert net.next_profile_change(0.0) == 10.0
    assert net.next_profile_change(10.0) == 20.0
    assert net.next_profile_change(20.0) == math.inf
    # static paths: nominal capacity, no horizon
    assert testbeds.STAMPEDE_COMET.bandwidth_at(1e9) == bw
    assert testbeds.STAMPEDE_COMET.next_profile_change(0.0) == math.inf


def test_bandwidth_ramp_builds_monotone_step_ladder():
    net = testbeds.impaired_variant(
        testbeds.LONI, "ramp-test", bandwidth_ramp=(5.0, 25.0, 0.5, 4)
    )
    prof = net.bandwidth_profile
    assert prof[0] == (0.0, 1.0)
    assert len(prof) == 5
    assert prof[-1] == (25.0, 0.5)
    mults = [m for _, m in prof]
    assert mults == sorted(mults, reverse=True)


@pytest.mark.parametrize("algorithm", ["promc", "mc", "untuned"])
def test_time_varying_bandwidth_scenarios_agree_across_backends(algorithm):
    """Step/ramp capacity profiles run through the profile-aware horizon
    on every backend; multi-channel schedulers (which actually reach the
    link capacity) lose throughput relative to the static base, while a
    single window-limited untuned stream is unaffected by design."""
    sc = Scenario(
        network=testbeds.STEPPY_BACKBONE.name, dataset="mixed",
        algorithm=algorithm,
    )
    base = Scenario(
        network=testbeds.STAMPEDE_COMET.name, dataset="mixed",
        algorithm=algorithm,
    )
    ev = run_matrix([sc], backend="event")[0]
    ba = run_matrix([sc], backend="batch")[0]
    assert ba.throughput == pytest.approx(ev.throughput, rel=1e-9)
    ev_base = run_matrix([base], backend="event")[0]
    if algorithm == "untuned":
        assert ev.throughput == pytest.approx(ev_base.throughput, rel=1e-6)
    else:
        assert ev.throughput < ev_base.throughput


def test_matrix_promc_starved_concurrency():
    """maxCC=1 with 4 live chunks: the min-1-channel floor overrides the
    budget and nothing deadlocks."""
    sc = Scenario(
        network=testbeds.LAN.name, dataset="mixed", algorithm="promc",
        max_cc=1, num_chunks=4,
    )
    ev = build_simulation(sc).run()
    ba = BatchSimulation([build_simulation(sc)], names=[sc.name]).run()[0]
    assert ev.total_time > 0
    assert ba.throughput == pytest.approx(ev.throughput, rel=1e-9)


# ------------------------------------------------------------------ #
# result-ordering invariant: the executor never reorders rows
# ------------------------------------------------------------------ #


def test_matrix_results_keep_input_order_across_executors():
    """``run_matrix`` promises results in input order. The pipelined
    executor completes chunks out of order (different devices, different
    runtimes), chunking regroups rows by shape hint, and per-scenario
    results must still land at the row's input index — pin that against
    a deliberately shuffled, heterogeneous matrix."""
    scenarios = [
        Scenario(
            network=net, dataset=ds, algorithm=algo, max_cc=cc, seed=i,
        )
        for i, (net, ds, algo, cc) in enumerate(
            (
                (testbeds.XSEDE.name, "mixed", "promc", 8),
                (testbeds.LAN.name, "uniform_small", "sc", 2),
                (testbeds.LONI.name, "mixed", "mc", 4),
                (testbeds.LAN.name, "mixed", "promc", 2),
                (testbeds.XSEDE.name, "uniform_small", "mc", 6),
                (testbeds.LONI.name, "uniform_small", "sc", 4),
                (testbeds.LAN.name, "mixed", "sc", 6),
            )
        )
    ]
    reference = {
        sc.name: build_simulation(sc).run() for sc in scenarios
    }
    for chunk_size in (2, 3, 64):
        out = run_matrix(scenarios, backend="numpy", chunk_size=chunk_size)
        assert len(out) == len(scenarios)
        for sc, r in zip(scenarios, out):
            ref = reference[sc.name]
            assert r.total_bytes == ref.total_bytes, sc.name
            assert r.throughput == pytest.approx(
                ref.throughput, rel=1e-9
            ), (chunk_size, sc.name)
