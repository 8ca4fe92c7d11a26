"""Canonical shape bucketing + compile-tax regressions.

The jax driver's only ``jax.jit`` (``jax_backend._device_rounds``) keys
its compile cache on the shape of every carried array, so any raw shape
that leaks into the signature is a fresh multi-second XLA compile. These
tests pin the three layers that keep the signature set small:

* the pad-ladder primitives (:mod:`repro.eval.fabric.bucketing`);
* the *compile count* itself — two batches with different raw shapes
  that bucket identically must share one compiled program;
* the ladder canary — the planned full 1116-scenario grid, including
  every quarter-step compaction rung, stays within 8 signatures.

Plus the two bugfix satellites that ride along: the byte-bounded
fileset cache (:mod:`repro.eval.scenarios`) and the fused Pallas
advance+feed step (:mod:`repro.eval.fabric.kernels.fused_step_pallas`).
"""
import tracemalloc

import pytest

from repro.eval import Scenario
from repro.eval import scenarios as scenarios_mod
from repro.eval.fabric import jax_backend
from repro.eval.fabric.bucketing import (
    MIN_ROW_PAD,
    MIN_SPAN,
    QSIZES_FLOOR,
    bucket,
    canonical_signature,
    chunk_spans,
    signature_ladder,
)
from repro.eval.fabric.driver import FabricSimulation
from repro.eval.fabric.jax_backend import JaxFabricSimulation
from repro.eval.fabric.kernels import waterfill_pallas as wf_pallas
from repro.eval.runner import (
    BACKEND_CHUNK_SIZE,
    _cost_proxy,
    _effective_cc,
    build_matrix,
    shape_hint,
)
from repro.eval.scenarios import build_simulation

# ------------------------------------------------------------------ #
# pad-ladder primitives
# ------------------------------------------------------------------ #


def test_bucket_is_pow2_ceiling():
    assert bucket(1) == 1
    assert bucket(2) == 2
    assert bucket(3) == 4
    assert bucket(276) == 512
    assert bucket(1024) == 1024
    assert bucket(1025) == 2048
    # floors
    assert bucket(0) == 1
    assert bucket(3, MIN_ROW_PAD) == MIN_ROW_PAD
    assert bucket(5, QSIZES_FLOOR) == QSIZES_FLOOR


@pytest.mark.parametrize("n", [1, 7, 63, 64, 276, 1000, 1116, 4096])
@pytest.mark.parametrize("size", [64, 256, 1024])
@pytest.mark.parametrize("aligned", [False, True])
def test_chunk_spans_cover_exactly(n, size, aligned):
    spans = chunk_spans(n, size, pad_aligned=aligned)
    # contiguous, non-overlapping, complete
    assert spans[0][0] == 0 and spans[-1][1] == n
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
    assert all(hi > lo for lo, hi in spans)


def test_chunk_spans_aligned_cuts_pow2():
    # the motivating case: 276 rows become 256 + 20(pad 32), not one
    # 276-row batch sweeping a 512-row device shape
    spans = chunk_spans(276, 1024, pad_aligned=True)
    assert spans == ((0, 256), (256, 276))
    # every span except the final scraps is a power of two >= MIN_SPAN
    for lo, hi in chunk_spans(1116, 1024, pad_aligned=True)[:-1]:
        w = hi - lo
        assert w >= MIN_SPAN and w & (w - 1) == 0


def test_chunk_spans_plain_is_uniform():
    assert chunk_spans(10, 4) == ((0, 4), (4, 8), (8, 10))


# ------------------------------------------------------------------ #
# compile-count regression: raw-shape-different, bucket-identical
# ------------------------------------------------------------------ #

from repro.core import testbeds

_SMALL = Scenario(
    network=testbeds.LAN.name, dataset="uniform_small",
    algorithm="promc", max_cc=1,
)


def _jax_batch(n_rows):
    sims = [build_simulation(_SMALL) for _ in range(n_rows)]
    return JaxFabricSimulation(sims, names=[f"r{i}" for i in range(n_rows)])


def test_bucketed_batches_share_one_compiled_program():
    """3 rows / 120 files and 5 rows / 200 files land on the same
    (S=8, ..., Q=1024) signature: the second batch must add zero
    compiled programs (jit caches for both donation twins plus the
    AOT cache — direct runs may use either)."""
    a, b = _jax_batch(3), _jax_batch(5)
    assert a.S != b.S  # genuinely different raw shapes
    assert a.qsizes.shape != b.qsizes.shape
    ra = a.run()
    n_compiles = jax_backend.compiled_program_count()
    rb = b.run()
    assert jax_backend.compiled_program_count() == n_compiles
    # same scenario -> identical results regardless of batch shape
    assert rb[0].total_time == pytest.approx(ra[0].total_time)
    assert rb[0].total_bytes == ra[0].total_bytes


def test_canonical_signature_matches_planned_shapes():
    fs = FabricSimulation(
        [build_simulation(_SMALL) for _ in range(3)], names=list("abc")
    )
    need_c, need_p = fs.capacity_need()
    while fs.C < need_c:
        fs._grow()
    while fs.P < need_p:
        fs._grow_prepend()
    rows, C, K, P, B, T, Q = canonical_signature(fs)
    assert rows == MIN_ROW_PAD  # 3 -> 8
    assert Q == QSIZES_FLOOR  # 120 files -> 1024 slots
    for axis in (C, K, P, B):
        assert axis & (axis - 1) == 0  # on the ladder


# ------------------------------------------------------------------ #
# pad-ladder canary: the full grid plans to <= 8 signatures
# ------------------------------------------------------------------ #


def test_full_grid_pad_ladder_stays_small():
    """Plan the full 1116-scenario grid exactly as ``run_matrix`` chunks
    it for jax (hint-grouped, cost-sorted, pow2-aligned spans) and count
    canonical signatures, including every quarter-step compaction rung
    each batch could descend through. More than 8 means a shape axis
    started leaking raw values into the jit signature again."""
    m = build_matrix("full")
    size = BACKEND_CHUNK_SIZE["jax"]
    costs = [_cost_proxy(s) for s in m]
    hints = [shape_hint(_effective_cc(s)) for s in m]
    order = sorted(range(len(m)), key=lambda i: (hints[i], costs[i]))
    sigs = set()
    for lo, hi in chunk_spans(len(m), size, pad_aligned=True):
        part = [m[i] for i in order[lo:hi]]
        fs = FabricSimulation(
            [build_simulation(s) for s in part],
            names=[s.name for s in part],
        )
        need_c, need_p = fs.capacity_need()
        while fs.C < need_c:
            fs._grow()
        while fs.P < need_p:
            fs._grow_prepend()
        sig = canonical_signature(fs)
        sigs.add(sig)
        # deterministic quarter-step compaction rungs, COMPACT_FLOOR
        # floor (JaxFabricSimulation._maybe_compact) — the same ladder
        # the executor AOT-warms per chunk
        sigs.update(signature_ladder(sig))
    assert len(sigs) <= 8, sorted(sigs)
    # and each one is entirely on the ladder
    for rows, C, K, P, B, T, Q in sigs:
        for axis in (rows, C, K, P, B, Q):
            assert axis & (axis - 1) == 0


# ------------------------------------------------------------------ #
# byte-bounded fileset cache
# ------------------------------------------------------------------ #


def _drain_files_cache():
    scenarios_mod._files_cache.clear()
    scenarios_mod._files_cache_bytes = 0


def test_files_cache_bounded_by_bytes(monkeypatch):
    """A 64-candidate sweep over distinct filesets must not pin memory
    proportional to the sweep: the cache evicts by approximate bytes and
    the allocation high-water mark stays flat."""
    cap = 64 * 1024  # small enough that 64 uniform_small sets overflow
    monkeypatch.setattr(scenarios_mod, "FILES_CACHE_MAX_BYTES", cap)
    _drain_files_cache()
    before = dict(scenarios_mod.files_cache_info())
    tracemalloc.start()
    try:
        for seed in range(64):
            sc = Scenario(
                network=testbeds.LAN.name, dataset="uniform_small",
                algorithm="sc", seed=seed,
            )
            files = scenarios_mod.build_files(sc)
            assert files  # the builder still works under eviction
            assert scenarios_mod.files_cache_info()["bytes"] <= cap
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    info = scenarios_mod.files_cache_info()
    assert info["evictions"] > before["evictions"]
    assert info["bytes"] <= cap
    # 64 sweeps of a ~6 KB fileset under a 64 KB cap: peak Python
    # allocations stay within a couple MB, not O(sweep size) growth
    assert peak < 8 * 1024 * 1024
    _drain_files_cache()


def test_files_cache_hits_and_identity():
    _drain_files_cache()
    sc = Scenario(network=testbeds.LAN.name, dataset="uniform_small", algorithm="sc")
    a = scenarios_mod.build_files(sc)
    h0 = scenarios_mod.files_cache_info()["hits"]
    b = scenarios_mod.build_files(sc)
    assert scenarios_mod.files_cache_info()["hits"] == h0 + 1
    # fresh list per call, shared frozen specs underneath
    assert a is not b
    assert all(x is y for x, y in zip(a, b))


def test_files_cache_oversized_entry_not_pinned(monkeypatch):
    monkeypatch.setattr(scenarios_mod, "FILES_CACHE_MAX_BYTES", 128)
    _drain_files_cache()
    sc = Scenario(network=testbeds.LAN.name, dataset="uniform_small", algorithm="sc")
    files = scenarios_mod.build_files(sc)
    assert len(files) == 40
    info = scenarios_mod.files_cache_info()
    assert info["entries"] == 0 and info["bytes"] == 0
    _drain_files_cache()


# ------------------------------------------------------------------ #
# Pallas: lowering detection, call caching, fused step fidelity
# ------------------------------------------------------------------ #


def test_pallas_lowering_detection_matches_backend():
    import jax

    expected = jax.default_backend() in wf_pallas._COMPILED_BACKENDS
    assert wf_pallas.supports_compiled_pallas() is expected


def test_pallas_call_cached_per_shape():
    wf_pallas._build_call.cache_clear()
    a = wf_pallas._build_call(8, 4, "float64", True)
    b = wf_pallas._build_call(8, 4, "float64", True)
    c = wf_pallas._build_call(8, 8, "float64", True)
    assert a is b and a is not c
    assert wf_pallas._build_call.cache_info().hits == 1


@pytest.mark.parametrize(
    "kwarg", [{"waterfill_impl": "pallas"}, {"fused_step": "pallas"}]
)
def test_f64_pallas_refused_at_selection_on_tpu(monkeypatch, kwarg):
    """TPU's kernel compiler has no f64: selecting either f64 kernel
    there fails when the driver is built, not mid-sweep."""
    monkeypatch.setattr(wf_pallas.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="float64 Pallas kernel"):
        FabricSimulation([build_simulation(_SMALL)], names=["a"], **kwarg)


def test_fused_pallas_step_matches_classic_driver():
    """REPRO_FABRIC_FUSED_STEP=pallas routes resume-free sweeps through
    the single fused kernel; results must match the split-kernel NumPy
    path running the same bisected water level (the Pallas water-fill).
    Against the closed-form level the ~1e-12 difference can split a
    near-coincident pair of events, so event counts would differ."""
    scs = [
        _SMALL,
        Scenario(network=testbeds.XSEDE.name, dataset="mixed",
                 algorithm="mc", max_cc=16),
    ]
    classic = FabricSimulation(
        [build_simulation(s) for s in scs], names=[s.name for s in scs],
        waterfill_impl="pallas",
    ).run()
    fused = FabricSimulation(
        [build_simulation(s) for s in scs],
        names=[s.name for s in scs],
        fused_step="pallas",
    ).run()
    for c, f in zip(classic, fused):
        assert f.total_bytes == c.total_bytes
        assert f.total_time == pytest.approx(c.total_time, rel=1e-9)
        assert f.n_events == c.n_events
