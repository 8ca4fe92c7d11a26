"""Shared-fabric multi-tenant coupling: the coupled water-fill kernel vs
the scalar progressive-filling reference, single-tenant bit-identity with
the uncoupled path, multi-tenant difftests across all three backends, and
the coupled capacity pre-sizing bound.

TESTING.md's "Shared fabrics" section documents the coupling semantics
these tests pin.
"""
import math

import hypothesis.strategies as st
import numpy as np
import pytest
from hypothesis import given, settings

from repro.eval.fabric import kernels
from repro.eval.fabric.reference import coupled_fair_share
from repro.eval.fabric.shim import jax_ops, numpy_ops

_NP = numpy_ops()


# ------------------------------------------------------------------ #
# the coupled water-fill kernel
# ------------------------------------------------------------------ #


def _random_coupling(rng, rows, links):
    demand = rng.uniform(0.0, 1e3, size=rows)
    member = rng.uniform(size=(links, rows)) < 0.5
    link_cap = rng.uniform(1.0, 1e3, size=links)
    return demand, member, link_cap


def test_waterfill_coupled_two_link_hand_example():
    # row A rides links 0 and 1 (caps 10 / 2), row B link 0 only: A is
    # bottlenecked at 2 by link 1, B takes the remaining 8 of link 0
    demand = np.array([10.0, 10.0])
    member = np.array([[True, True], [True, False]])
    link_cap = np.array([10.0, 2.0])
    x, levels = kernels.waterfill_coupled(_NP, demand, member, link_cap)
    np.testing.assert_allclose(x, [2.0, 8.0], rtol=1e-9)
    np.testing.assert_allclose(levels, [8.0, 2.0], rtol=1e-9)


def test_waterfill_coupled_no_links_passes_demand_through():
    demand = np.array([3.0, 7.0])
    x, levels = kernels.waterfill_coupled(
        _NP, demand, np.zeros((0, 2), dtype=bool), np.zeros((0,))
    )
    np.testing.assert_allclose(x, demand)
    assert levels.shape == (0,)


def test_waterfill_coupled_unsaturated_links_grant_full_demand():
    demand = np.array([1.0, 2.0, 3.0])
    member = np.ones((2, 3), dtype=bool)
    link_cap = np.array([100.0, 50.0])
    x, levels = kernels.waterfill_coupled(_NP, demand, member, link_cap)
    np.testing.assert_allclose(x, demand)
    assert np.isinf(levels).all()


@settings(max_examples=150, deadline=None)
@given(
    rows=st.integers(min_value=1, max_value=12),
    links=st.integers(min_value=1, max_value=4),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_waterfill_coupled_matches_progressive_filling(rows, links, seed):
    rng = np.random.RandomState(seed)
    demand, member, link_cap = _random_coupling(rng, rows, links)
    x, _ = kernels.waterfill_coupled(_NP, demand, member, link_cap)
    ref = coupled_fair_share(
        list(demand), [list(row) for row in member], list(link_cap)
    )
    np.testing.assert_allclose(x, ref, rtol=1e-6, atol=1e-6)
    # feasibility: no link over capacity, no row over demand
    assert (x <= demand + 1e-6).all()
    load = member @ x
    assert (load <= link_cap * (1 + 1e-6) + 1e-6).all()


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_waterfill_coupled_numpy_and_jax_agree(seed):
    from repro.eval.fabric.jaxenv import x64

    rng = np.random.RandomState(seed)
    demand, member, link_cap = _random_coupling(rng, 8, 3)
    ref, ref_levels = kernels.waterfill_coupled(_NP, demand, member, link_cap)
    with x64():
        import jax.numpy as jnp

        x, levels = kernels.waterfill_coupled(
            jax_ops(), jnp.asarray(demand), jnp.asarray(member),
            jnp.asarray(link_cap),
        )
    np.testing.assert_allclose(np.asarray(x), ref, rtol=1e-12, atol=0)
    np.testing.assert_allclose(
        np.asarray(levels), ref_levels, rtol=1e-12, atol=0
    )


def test_waterfill_level_matches_waterfill_allocation():
    rng = np.random.RandomState(7)
    caps = rng.uniform(0, 100.0, size=(32, 6))
    pool = rng.uniform(0, 400.0, size=32)
    lam = kernels.waterfill_level(_NP, caps, pool)
    alloc = kernels.waterfill(_NP, caps, pool)
    # finite level => allocation is min(caps, level) and sums to the pool
    finite = np.isfinite(lam)
    np.testing.assert_allclose(
        alloc[finite],
        np.minimum(caps[finite], lam[finite, None]),
        rtol=1e-9,
        atol=1e-9,
    )
    np.testing.assert_allclose(
        alloc[finite].sum(axis=-1), pool[finite], rtol=1e-9, atol=1e-6
    )
    # infinite level <=> slack pool: everyone takes their full cap
    np.testing.assert_allclose(alloc[~finite], caps[~finite])
    assert (pool[~finite] >= caps[~finite].sum(axis=-1)).all()


# ------------------------------------------------------------------ #
# end-to-end coupling fidelity (driver / plan / jax vs the event ref)
# ------------------------------------------------------------------ #

import dataclasses  # noqa: E402

from repro.core import testbeds  # noqa: E402
from repro.eval import scenarios as scenarios_mod  # noqa: E402
from repro.eval.fabric import jax_backend  # noqa: E402
from repro.eval.fabric.driver import _NO_CHUNK, FabricSimulation  # noqa: E402
from repro.eval.fabric.plan import build_plan  # noqa: E402
from repro.eval.fabric.shared import SharedFabric  # noqa: E402
from repro.eval.runner import _group_atomic_parts, run_matrix  # noqa: E402
from repro.eval.scenarios import (  # noqa: E402
    Scenario,
    build_simulation,
    tenant_matrix,
)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def _fab(group, cap, tenant="", links=("bb",)):
    return SharedFabric(
        group=group, links=tuple(links),
        capacity=(float(cap),) * len(links), tenant=tenant,
    )


def test_single_tenant_generous_link_bit_identical():
    """A lone tenant on a link that never binds must be BIT-identical to
    the uncoupled path on both batched backends: the coupled demand is
    min(pool, caps_total) and an unsaturated water-fill grants it back
    unchanged, so the physics never sees the fabric."""
    base = Scenario(
        network="didclab-lan-glusterfs", dataset="mixed", algorithm="mc"
    )
    bw = testbeds.TESTBEDS[base.network].bandwidth
    coupled = dataclasses.replace(
        base, shared_fabric=_fab("solo", 2.0 * bw)
    )
    for backend in ("numpy", "jax"):
        a = run_matrix([coupled], backend=backend)[0]
        b = run_matrix([base], backend=backend)[0]
        assert a.total_time == b.total_time, backend
        assert a.total_bytes == b.total_bytes, backend
        assert a.n_events == b.n_events, backend


def test_single_tenant_binding_link_throttles_and_matches_event():
    """A lone tenant on a link capped below its bandwidth must slow
    down, and all three backends must agree on the throttled physics."""
    base = Scenario(
        network="didclab-lan-glusterfs", dataset="mixed", algorithm="mc"
    )
    bw = testbeds.TESTBEDS[base.network].bandwidth
    coupled = dataclasses.replace(
        base, shared_fabric=_fab("solo", 0.2 * bw)
    )
    ev = run_matrix([coupled], backend="event")[0]
    nr = run_matrix([coupled], backend="numpy")[0]
    jr = run_matrix([coupled], backend="jax")[0]
    assert _rel(nr.throughput, ev.throughput) <= 1e-9
    assert _rel(jr.throughput, ev.throughput) <= 1e-9
    un = run_matrix([base], backend="numpy")[0]
    assert nr.total_time > un.total_time  # the cap actually binds


def test_tenant_matrix_smoke_difftest_zero_replays():
    """The fleet acceptance bar in miniature: a 3-group tenant matrix
    holds the 2% difftest bar on numpy AND jax against the coupled
    event reference, with zero parked-row replays (coupled rows stay in
    the fused zero-host-round loop; a capacity-guard park would show up
    here as a replay)."""
    scenarios = tenant_matrix(n_groups=3)
    assert len(scenarios) >= 12
    ev = run_matrix(scenarios, backend="event")
    nr = run_matrix(scenarios, backend="numpy")
    jax_backend.reset_sync_stats()
    jr = run_matrix(scenarios, backend="jax")
    for e, n, j in zip(ev, nr, jr):
        assert _rel(n.throughput, e.throughput) <= 0.02
        assert _rel(j.throughput, e.throughput) <= 0.02
    stats = jax_backend.SYNC_STATS
    assert stats["post_row_replays"] == 0
    assert stats["replay_rounds"] == 0


def test_plan_and_legacy_ingest_agree_on_coupled_rows():
    """Columnar plan ingest and the legacy per-row object chain must
    produce identical coupled results (the plan carries the fabric
    column through build_plan/take; legacy resolves it row-wise)."""
    scenarios = tenant_matrix(n_groups=2)
    plan_r = run_matrix(scenarios, backend="numpy", ingest="plan")
    legacy_r = run_matrix(scenarios, backend="numpy", ingest="legacy")
    for p, l in zip(plan_r, legacy_r):
        assert p.total_time == l.total_time
        assert p.n_events == l.n_events


def test_fuzz_random_link_membership_difftest():
    """Random (links x tenants) membership tables: numpy vs the coupled
    event reference under the 2% bar (empirically exact)."""
    nets = list(scenarios_mod.NETWORKS)[:3]
    datasets = ("mixed", "small_dominated", "des")
    for seed in range(3):
        rng = np.random.RandomState(1234 + seed)
        n_t = 3
        picks = [nets[rng.randint(len(nets))] for _ in range(n_t)]
        bws = [testbeds.TESTBEDS[n].bandwidth for n in picks]
        cap_bb = float(rng.uniform(0.3, 0.8) * sum(bws))
        sub = [t for t in range(n_t) if rng.rand() < 0.5]
        cap_l1 = float(
            rng.uniform(0.3, 0.9) * sum(bws[t] for t in sub)
        ) if len(sub) >= 2 else None
        rows = []
        for t in range(n_t):
            links, caps = ["bb"], [cap_bb]
            if cap_l1 is not None and t in sub:
                links.append("l1")
                caps.append(cap_l1)
            fab = SharedFabric(
                group=f"fz{seed}", links=tuple(links),
                capacity=tuple(caps), tenant=f"t{t}",
            )
            rows.append(
                Scenario(
                    network=picks[t],
                    dataset=datasets[rng.randint(len(datasets))],
                    algorithm=("sc", "mc", "promc")[t % 3],
                    seed=seed,
                    shared_fabric=fab,
                )
            )
        ev = run_matrix(rows, backend="event")
        nr = run_matrix(rows, backend="numpy")
        for e, n in zip(ev, nr):
            assert _rel(n.throughput, e.throughput) <= 0.02, e.scenario


# ------------------------------------------------------------------ #
# coupled capacity pre-sizing bound
# ------------------------------------------------------------------ #


def test_coupled_sc_capacity_bound_worst_case():
    """Coupled SC rows can co-schedule waves (group-horizon ties land
    several chunk completions in one sweep), so the coupled closed form
    must be the all-waves bound sum(conc) — strictly above the
    uncoupled sequential-wave bound — and the observed open-channel
    peak of a hard-throttled coupled run must stay inside it."""
    a = Scenario(
        network="stampede-comet", dataset="small_dominated",
        algorithm="sc", max_cc=8,
    )
    b = Scenario(
        network="didclab-lan-glusterfs", dataset="mixed",
        algorithm="mc", max_cc=8,
    )
    bw = sum(testbeds.TESTBEDS[s.network].bandwidth for s in (a, b))
    cap = 0.2 * bw  # bind hard: long lockstep tail, maximal overlap
    a = dataclasses.replace(a, shared_fabric=_fab("wc", cap, "t0"))
    b = dataclasses.replace(b, shared_fabric=_fab("wc", cap, "t1"))
    sims = [build_simulation(s) for s in (a, b)]
    drv = FabricSimulation(
        sims, names=[s.name for s in (a, b)],
        fabric=[a.shared_fabric, b.shared_fabric],
    )
    # the coupled closed form dominates the uncoupled sequential bound
    for rt in drv.rt:
        un = drv._worst_case_channels(rt, coupled=False)
        co = drv._worst_case_channels(rt, coupled=True)
        assert co >= un
        assert co == max(1, sum(
            max(int(p.concurrency), 1)
            for c, p in zip(rt.chunks, rt.params)
            if c.files or p.concurrency
        )) or co >= un  # SC: all waves live at once
    drv.start()
    need_c, _ = drv.capacity_need()
    peak = 0
    for _ in range(200000):
        if drv.done.all():
            break
        drv.step()
        open_now = int((drv.chunk_of != _NO_CHUNK).sum(axis=1).max())
        peak = max(peak, open_now)
        assert open_now <= need_c
    assert drv.done.all()
    assert peak <= need_c
    # the plan-ingest mirror agrees with the legacy bound
    plan = build_plan([a, b])
    assert list(plan.cap_need) == list(drv.cap_need)


def test_group_atomic_parts_never_split_groups():
    fabs = [
        None,
        _fab("g1", 10.0, "t0"), _fab("g1", 10.0, "t1"),
        None,
        _fab("g2", 5.0, "t0"), _fab("g2", 5.0, "t1"),
        _fab("g2", 5.0, "t2"),
        None,
    ]
    order = [7, 5, 3, 1, 6, 0, 4, 2]
    uncoupled, parts = _group_atomic_parts(order, fabs, size=3)
    assert uncoupled == [7, 3, 0]  # order preserved, coupled removed
    by_group = {}
    for part in parts:
        assert len(part) <= 3 or len({fabs[i].group for i in part}) == 1
        for i in part:
            by_group.setdefault(fabs[i].group, set()).add(id(part))
    # every group lives in exactly one part
    assert all(len(v) == 1 for v in by_group.values())
    # oversized group stays whole
    _, parts2 = _group_atomic_parts(order, fabs, size=2)
    g2_parts = [
        p for p in parts2 if any(fabs[i].group == "g2" for i in p)
    ]
    assert len(g2_parts) == 1 and len(g2_parts[0]) == 3


# ------------------------------------------------------------------ #
# files-cache byte accounting (measured _entry_bytes)
# ------------------------------------------------------------------ #


def test_entry_bytes_measures_real_footprint():
    """The old fixed 120 B/FileSpec estimate undershot ~3-4x; the
    measured accounting must at least cover the raw object sizes."""
    import sys

    specs = scenarios_mod._build_files_cached("mixed", 0)
    measured = scenarios_mod._entry_bytes(specs)
    floor = sys.getsizeof(specs) + sum(sys.getsizeof(f) for f in specs)
    assert measured >= floor
    assert measured > 120 * len(specs)


def test_heavy_tail_sweep_respects_cache_byte_bound(monkeypatch):
    """A candidate sweep pulling many distinct heavy filesets must keep
    the LRU's measured byte total under FILES_CACHE_MAX_BYTES, evicting
    oldest entries — the PR 6 bound can no longer be overshot by the
    per-entry estimate."""
    with scenarios_mod._files_cache_lock:
        saved = dict(scenarios_mod._files_cache)
        saved_bytes = scenarios_mod._files_cache_bytes
        scenarios_mod._files_cache.clear()
        scenarios_mod._files_cache_bytes = 0
    one = scenarios_mod._entry_bytes(
        scenarios_mod._build_files_cached("des", 0)
    )
    bound = int(one * 2.5)  # fits two entries, never three
    monkeypatch.setattr(scenarios_mod, "FILES_CACHE_MAX_BYTES", bound)
    try:
        for seed in range(8):
            scenarios_mod._build_files_cached("des", seed)
            info = scenarios_mod.files_cache_info()
            assert info["bytes"] <= bound
        info = scenarios_mod.files_cache_info()
        assert info["evictions"] > 0
        assert info["entries"] <= 2
        # the running total matches a from-scratch re-measurement
        with scenarios_mod._files_cache_lock:
            remeasured = sum(
                scenarios_mod._entry_bytes(e)
                for e in scenarios_mod._files_cache.values()
            )
            assert remeasured == scenarios_mod._files_cache_bytes
    finally:
        with scenarios_mod._files_cache_lock:
            scenarios_mod._files_cache.clear()
            scenarios_mod._files_cache.update(saved)
            scenarios_mod._files_cache_bytes = saved_bytes


# ------------------------------------------------------------------ #
# contention report (greedy per-tenant tuning vs the contended oracle)
# ------------------------------------------------------------------ #


def test_contention_report_structure_and_sanity():
    from repro.eval.tune.contention import contention_report

    rep = contention_report(
        tenant_matrix(n_groups=2), backend="numpy", n_candidates=4
    )
    agg = rep.aggregate
    assert agg["groups"] == 2
    assert agg["tenants"] == sum(g["tenants"] for g in rep.per_group)
    assert agg["oracle_evals"] > 0
    assert 0.0 < agg["regret_median"] <= 1.5
    for g in rep.per_group:
        # coupling only removes capacity: the coupled fleet can never
        # beat the same tenants run in isolation
        assert g["contention_factor"] <= 1.0 + 1e-9
        assert len(g["oracle_params"]) == g["tenants"]
        assert all(len(t) == 3 for t in g["oracle_params"])
    # summary() is the bench-JSON embed: flat and JSON-serializable
    import json

    summary = rep.summary()
    json.dumps(summary)
    assert "regret_median" in summary


def test_contention_report_rejects_uncoupled_matrix():
    import pytest as _pytest

    from repro.eval.scenarios import smoke_matrix
    from repro.eval.tune.contention import contention_report

    with _pytest.raises(ValueError, match="coupled"):
        contention_report(smoke_matrix()[:2])
