"""Columnar ingest equivalence: the ``ScenarioPlan`` batch constructor
must be *bit-identical* to the legacy per-row object path.

The plan path (``build_plan`` -> ``FabricSimulation(None, plan=...)``)
replaces ``build_simulation`` -> per-row ``Simulation`` objects -> driver
array packing with vectorized NumPy, building each transfer context
(network, dataset, seed, effective chunks) once and broadcasting it
across candidate-expanded rows. Nothing about that is allowed to change
numerics: every resident driver array, the runtime metadata, and the
final results must match the legacy build exactly — not within
tolerance — so the legacy path stays a usable difftest reference.
"""
from __future__ import annotations

import queue
import threading

import numpy as np
import pytest

from repro.eval.fabric import plan as plan_mod
from repro.eval.fabric.driver import (
    _ROW_ARRAYS,
    KIND_MC,
    KIND_PROMC,
    KIND_SC,
    FabricSimulation,
)
from repro.eval.fabric.plan import ScenarioPlan, build_plan, plan_supported
from repro.eval.runner import run_matrix
from repro.eval.scenarios import (
    build_simulation,
    expand_candidates,
    full_matrix,
)

#: a slice covering every algorithm family, the k/max_cc sweeps, and the
#: time-varying (profiled-bandwidth) tail of the full grid, plus a
#: candidate-expanded block so the broadcast path is exercised
_CANDS = [(0, 1, 1), (4, 4, 8), (16, 2, 2)]


def _slice():
    m = full_matrix()
    scs = m[:40] + m[700:740] + m[930:970] + m[1090:1116]
    return scs + expand_candidates(m[:5] + m[1090:1093], _CANDS)


@pytest.fixture(scope="module")
def pair():
    scs = _slice()
    assert plan_supported(scs)
    legacy = FabricSimulation(
        [build_simulation(sc) for sc in scs], names=[sc.name for sc in scs]
    )
    planned = FabricSimulation(None, plan=build_plan(scs))
    return legacy, planned


def _assert_rows_identical(legacy, planned):
    for a in _ROW_ARRAYS:
        if a == "qoff":
            continue  # buffer layouts differ; gathered slices checked below
        x, y = getattr(legacy, a), getattr(planned, a)
        assert x.shape == y.shape, a
        assert x.dtype == y.dtype, a
        if np.issubdtype(x.dtype, np.floating):
            eq = (x == y) | (np.isnan(x) & np.isnan(y))
        else:
            eq = x == y
        assert np.all(eq), (a, np.argwhere(~np.asarray(eq))[:3])


def test_plan_arrays_bit_identical(pair):
    legacy, planned = pair
    _assert_rows_identical(legacy, planned)


def test_plan_qsizes_slices_identical(pair):
    # qoff points into differently-laid-out flat buffers, so compare the
    # gathered per-(row, chunk) file-size slices instead of the offsets
    legacy, planned = pair
    for s in range(legacy.S):
        for k in range(legacy.K):
            n = int(legacy.qlen[s, k])
            lo_l, lo_p = int(legacy.qoff[s, k]), int(planned.qoff[s, k])
            assert np.array_equal(
                legacy.qsizes[lo_l:lo_l + n],
                planned.qsizes[lo_p:lo_p + n],
            ), (s, k)


def test_plan_runtime_metadata(pair):
    legacy, planned = pair
    for rl, rp in zip(legacy.rt, planned.rt):
        assert rl.name == rp.name
        assert rl.scheduler.name == rp.scheduler.name, rl.name
        assert [c.name for c in rl.chunks] == [c.name for c in rp.chunks]
        assert rl.total_bytes == rp.total_bytes
        assert rl.network.name == rp.network.name


def test_plan_run_bit_identical(pair):
    # start() materializes the remaining derived state; run() drives the
    # NumPy backend end to end — both must agree exactly, row by row
    legacy, planned = pair
    legacy.start()
    planned.start()
    _assert_rows_identical(legacy, planned)
    for a, b in zip(legacy.run(), planned.run()):
        assert a.throughput == b.throughput, a.scheduler
        assert a.total_time == b.total_time, a.scheduler
        assert a.n_events == b.n_events, a.scheduler
        assert a.per_chunk_time == b.per_chunk_time, a.scheduler


def test_run_matrix_plan_equals_legacy():
    # the runner-level toggle: same chunking, executor, and results
    # whether rows arrive as a plan or as per-row objects
    scs = _slice()[:60]
    res_p = run_matrix(scs, backend="numpy", ingest="plan")
    res_l = run_matrix(scs, backend="numpy", ingest="legacy")
    assert len(res_p) == len(res_l) == len(scs)
    for a, b in zip(res_p, res_l):
        assert a.total_time == b.total_time
        assert a.throughput == b.throughput
        assert a.n_events == b.n_events


def test_full_matrix_groups_to_258_contexts():
    # the 1116-row grid dedups to exactly the documented 258 transfer
    # contexts — the oracle plane's outer axis (258 contexts x 64
    # candidates = 16,675 evals with the heuristic rows included)
    from repro.eval.tune.oracle import context_key, group_contexts

    m = full_matrix()
    keys, reps = group_contexts(m)
    assert len(keys) == 258
    assert len(reps) == 258
    # every scenario maps onto one of the deduped keys
    assert {context_key(sc) for sc in m} == set(keys)


def test_context_key_ignores_candidate_suffix():
    # expand_candidates rewrites algorithm/static_params and suffixes the
    # name; the context key must see through all of that so candidate
    # rows land in their base scenario's context
    from repro.eval.tune.oracle import context_key

    base = full_matrix()[:24]
    for sc in base:
        for cand in expand_candidates([sc], _CANDS):
            assert cand.name != sc.name  # suffixed
            assert context_key(cand) == context_key(sc)


def test_candidate_expansion_shares_plan_contexts():
    # candidate rows differ only in static params + a name suffix, so
    # the plan's context dedup (network, dataset, seed, effective
    # chunks) partitions each context's file set once and broadcasts —
    # widening the candidate axis must add zero new contexts
    base = full_matrix()[:12]

    def n_ctx(scs):
        p = build_plan(scs)
        return len(
            {
                (int(p.net_idx[i]),)
                + tuple(p.qoff[i])
                + tuple(p.qlen[i])
                for i in range(len(p))
            }
        )

    one = n_ctx(base + expand_candidates(base, _CANDS[:1]))
    many = n_ctx(base + expand_candidates(base, _CANDS))
    assert many == one


def test_plan_kind_codes_pinned():
    # the plan's scheduler-kind codes feed straight into the driver's
    # kind column; a renumbering on either side would silently swap
    # controller semantics
    assert plan_mod._KIND_SC == KIND_SC
    assert plan_mod._KIND_MC == KIND_MC
    assert plan_mod._KIND_PROMC == KIND_PROMC


def test_take_preserves_columns():
    plan = build_plan(_slice())
    idx = [5, 0, 17, 101]
    sub = plan.take(idx)
    assert isinstance(sub, ScenarioPlan)
    assert sub.names == [plan.names[i] for i in idx]
    assert np.array_equal(sub.kind, plan.kind[idx])
    assert np.array_equal(sub.qoff, plan.qoff[idx])
    assert sub.qsizes is plan.qsizes  # shared, not copied


def test_warm_loop_stop_drops_pending():
    # fail-fast contract: once the pipeline's stop event is set, queued
    # warm work is discarded (no stray multi-second compiles after a
    # worker error) but the sentinel still terminates the thread
    from repro.eval.fabric.executor import _warm_loop

    warmed = []
    stop = threading.Event()
    q = queue.Queue()
    q.put("a")
    q.put("b")
    q.put(None)
    _warm_loop(q, stop, warm=warmed.append)
    assert warmed == ["a", "b"]

    warmed.clear()
    stop.set()
    q.put("c")
    q.put("d")
    q.put(None)
    t = threading.Thread(target=_warm_loop, args=(q, stop, warmed.append))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert warmed == []


def test_warm_loop_failure_fails_the_pipeline():
    # a warm that raises is a fault, not a prefetch miss: it reaches the
    # pipeline's fail hook (or the caller, without one)
    from repro.eval.fabric.executor import _warm_loop

    def bad_warm(driver):
        raise RuntimeError(f"compiler refused {driver}")

    failures = []
    q = queue.Queue()
    q.put("a")
    q.put(None)
    _warm_loop(q, threading.Event(), bad_warm, failures.append)
    assert [str(e) for e in failures] == ["compiler refused a"]
    q.put("b")
    with pytest.raises(RuntimeError, match="compiler refused b"):
        _warm_loop(q, threading.Event(), bad_warm)
