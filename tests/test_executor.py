"""The overlap-pipelined executor: ordering, equivalence, donation, AOT
warm-start, thread-safety of the shared caches, and the multi-device
shard layer.

The invariants under test are the ones ``eval.runner`` promises:

  * results come back in **input order**, independent of chunk
    interleaving and device assignment, and equal to one direct driver
    call on the same rows (the NumPy driver is the bitwise reference);
  * the device loop's carry is donated, and donated programs read back
    from the persistent cache give the reference's results;
  * ``SYNC_STATS`` totals are identical whether runs go one after
    another or interleaved (per-run private accumulation, one locked
    merge);
  * errors in a chunk's build or run reach the caller and drop the
    chunks after it;
  * the ``build_files`` byte-bounded LRU survives concurrent access;
  * AOT-warmed signatures serve runs without a fresh jit trace, and
    executor and direct runs share one compiled program per signature.
"""
import dataclasses
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.core import testbeds
from repro.eval import Scenario
from repro.eval import scenarios as scenarios_mod
from repro.eval.fabric import executor as executor_mod
from repro.eval.fabric import jax_backend
from repro.eval.fabric.bucketing import (
    COMPACT_FLOOR,
    canonical_signature,
    signature_ladder,
)
from repro.eval.fabric.driver import FabricSimulation
from repro.core.simulator import Simulation
from repro.eval.fabric.executor import execute_chunks
from repro.eval.fabric.jax_backend import JaxFabricSimulation
from repro.eval.fabric.jaxenv import x64
from repro.eval.runner import run_matrix
from repro.eval.scenarios import build_simulation, smoke_matrix
from test_resident_carry import _assert_same_as_numpy


def _mixed_batch(n=10):
    """Scenarios with heterogeneous runtimes so interleaving reorders
    completion (but must never reorder results)."""
    nets = (testbeds.LAN.name, testbeds.XSEDE.name, testbeds.LONI.name)
    algos = ("sc", "mc", "promc")
    return [
        Scenario(
            network=nets[i % len(nets)],
            dataset="uniform_small" if i % 2 else "mixed",
            algorithm=algos[i % len(algos)],
            max_cc=2 + (i % 3) * 2,
            seed=i,
        )
        for i in range(n)
    ]


# ------------------------------------------------------------------ #
# result ordering + equivalence with one direct driver call
# ------------------------------------------------------------------ #


def _direct(cls, scenarios):
    """One driver call on every row, outside the executor."""
    return cls(
        [build_simulation(sc) for sc in scenarios],
        names=[sc.name for sc in scenarios],
    ).run()


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_async_matches_serial_bitwise(backend):
    """The pipeline over chunks of 4 gives every row the result one
    direct driver call on all rows gives it."""
    m = _mixed_batch(10)
    cls = JaxFabricSimulation if backend == "jax" else FabricSimulation
    direct = _direct(cls, m)
    pipelined = run_matrix(m, backend=backend, chunk_size=4)
    for s, a in zip(direct, pipelined):
        assert a.total_bytes == s.total_bytes
        assert a.total_time == s.total_time
        assert a.n_events == s.n_events
        assert a.n_moves == s.n_moves


def test_results_in_input_order_any_chunking():
    """Per-row results are independent of chunk composition and always
    land at the row's input index (scenarios never interact)."""
    m = _mixed_batch(9)
    baseline = _direct(FabricSimulation, m)
    for chunk_size in (1, 2, 5, 64):
        out = run_matrix(m, backend="numpy", chunk_size=chunk_size)
        for b, o in zip(baseline, out):
            assert o.total_time == b.total_time
            assert o.total_bytes == b.total_bytes


def test_execute_chunks_writes_original_indices():
    m = _mixed_batch(6)
    builders = [(lambda sc=sc: build_simulation(sc)) for sc in m]
    names = [sc.name for sc in m]
    results = [None] * 6
    # deliberately scrambled, overlapping-free parts
    parts = [[4, 1], [5, 0], [2, 3]]
    execute_chunks(FabricSimulation, parts, builders, names, results)
    assert all(r is not None for r in results)
    direct = FabricSimulation(
        [build_simulation(m[1])], names=[m[1].name]
    ).run()[0]
    assert results[1].total_time == direct.total_time


def test_executor_propagates_builder_errors():
    m = _mixed_batch(4)
    builders = [(lambda sc=sc: build_simulation(sc)) for sc in m]
    names = [sc.name for sc in m]

    def boom():
        raise RuntimeError("builder exploded")

    builders[2] = boom
    with pytest.raises(RuntimeError, match="builder exploded"):
        execute_chunks(
            FabricSimulation, [[0, 1], [2, 3]], builders, names, [None] * 4,
        )


def test_executor_propagates_run_errors_and_drops_later_chunks():
    """A row past its ``max_time`` raises in the compute worker's
    ``driver.run()``: the error reaches the caller, and no chunk after
    the failing one writes a result."""
    m = _mixed_batch(4)

    def make_chunk(part, dev):
        sims = []
        for i in part:
            base = build_simulation(m[i])
            sims.append(
                Simulation(
                    base.scheduler.chunks, base.network, base.scheduler,
                    tick_period=m[i].tick_period,
                    max_time=2.0 if i == 0 else base.max_time,
                )
            )
        return JaxFabricSimulation(
            sims, names=[m[i].name for i in part], device=dev
        )

    results = [None] * 4
    with pytest.raises(RuntimeError, match="exceeded max_time"):
        execute_chunks(
            JaxFabricSimulation, [[0], [1], [2], [3]], None, None, results,
            make_chunk=make_chunk,
        )
    assert results == [None] * 4


def test_execute_chunks_without_parts_starts_no_thread(monkeypatch):
    """No parts: ``results`` comes back untouched and no pipeline thread
    (prep, compute or warm) is started."""
    spawned = []
    orig = threading.Thread

    class SpyThread(orig):
        def __init__(self, *a, **kw):
            spawned.append(kw.get("name"))
            super().__init__(*a, **kw)

    monkeypatch.setattr(threading, "Thread", SpyThread)
    results = ["kept"]
    out = execute_chunks(JaxFabricSimulation, [], [], [], results)
    assert out is results and results == ["kept"]
    assert spawned == []


def test_prep_stays_within_the_staging_bound():
    """While the first chunk's run blocks, prep builds only what the
    queue bound admits: the running chunk, ``DEFAULT_QUEUE_DEPTH``
    staged ones, and one built and waiting for room."""
    release = threading.Event()
    built = []

    class _Blocking:
        def __init__(self, part):
            self.part = part

        def run(self):
            if self.part == [0]:
                assert release.wait(timeout=60)
            return [f"row{i}" for i in self.part]

    def make_chunk(part, dev):
        built.append(part[0])
        return _Blocking(part)

    results = [None] * 8
    t = threading.Thread(
        target=execute_chunks,
        args=(_Blocking, [[i] for i in range(8)], None, None, results),
        kwargs={"make_chunk": make_chunk},
    )
    bound = 2 + executor_mod.DEFAULT_QUEUE_DEPTH
    t.start()
    try:
        for _ in range(200):
            if len(built) >= bound:
                break
            threading.Event().wait(0.01)
        threading.Event().wait(0.3)  # room for prep to overrun, were it able
        n_built = len(built)
    finally:
        release.set()
        t.join(timeout=60)
    assert not t.is_alive()
    assert n_built == bound
    assert results == [f"row{i}" for i in range(8)]


# ------------------------------------------------------------------ #
# donation
# ------------------------------------------------------------------ #


def test_donation_on_off_identical_results():
    """The donated device loop gives the NumPy driver's results."""
    m = _mixed_batch(4)
    _assert_same_as_numpy(
        _direct(JaxFabricSimulation, m), _direct(FabricSimulation, m)
    )


def test_device_round_consumes_the_donated_carry():
    """One round of ``_device_call`` deletes the state arrays passed in
    ``mut`` (their buffers now back the output state) and leaves the
    read-only ``const`` tables and the file sizes alive."""
    sc = Scenario(
        network=testbeds.XSEDE.name, dataset="mixed", algorithm="promc",
        max_cc=4,
    )
    drv = JaxFabricSimulation(
        [build_simulation(sc) for _ in range(3)], names=list("abc")
    )
    drv.start()
    drv._stall = np.zeros(drv.S, dtype=np.int64)
    drv._q_pad = drv.qsizes.shape[0]
    with x64():
        mut, const = drv._upload()
        qsizes = drv._to_device(drv.qsizes)
        state, _ = drv._device_call(mut, const, qsizes)
        state["t"].block_until_ready()
    for key in ("t", "rem", "done", "busy", "chunk_of", "queue_bytes"):
        assert mut[key].is_deleted(), key
        assert not state[key].is_deleted(), key
    assert not any(v.is_deleted() for v in const.values())
    assert not qsizes.is_deleted()


@pytest.fixture
def fresh_compile_cache(tmp_path):
    """Point JAX's persistent compilation cache at an empty directory for
    one test (every program is written), then restore the previous
    configuration."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {
        k: getattr(jax.config, k)
        for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _cached_files(path):
    return sorted(p.name for p in path.iterdir() if p.is_file())


def test_arm_compile_cache_placement(monkeypatch, tmp_path):
    """Entry points keep the cache where JAX_COMPILATION_CACHE_DIR says
    (JAX reads it itself), else at the fixed checkout path."""
    import jax

    from repro.eval.fabric import jaxenv

    assert jaxenv.CHECKOUT_CACHE_DIR.endswith(os.path.join("", ".jax_cache"))
    assert os.path.isdir(
        os.path.join(os.path.dirname(jaxenv.CHECKOUT_CACHE_DIR), "src")
    )
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {k: getattr(jax.config, k) for k in keys}
    made = []
    monkeypatch.setattr(jaxenv.os, "makedirs", lambda p, **kw: made.append(p))
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert jaxenv.arm_compile_cache() == str(tmp_path)
        assert made == []
        jax.config.update("jax_compilation_cache_dir", None)
        assert jaxenv.arm_compile_cache() == jaxenv.CHECKOUT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == (
            jaxenv.CHECKOUT_CACHE_DIR
        )
        assert made == [jaxenv.CHECKOUT_CACHE_DIR]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_donated_run_correct_with_cache_dir_configured(fresh_compile_cache):
    """With a persistent compilation cache configured, donated programs
    are written to it and a donated run matches the NumPy driver."""
    import jax

    m = _mixed_batch(4)
    jax.clear_caches()
    on = _direct(JaxFabricSimulation, m)
    assert any(
        "device_rounds" in f for f in _cached_files(fresh_compile_cache)
    )
    _assert_same_as_numpy(on, _direct(FabricSimulation, m))


def test_donated_program_read_back_from_cache(fresh_compile_cache):
    """The stale-alias regression: a donated executable *read back* from
    the persistent cache (in-memory caches cleared, so nothing is
    recompiled) must give the NumPy driver's results, run after run —
    jax 0.4.37 on CPU aliased stale buffers on exactly this path."""
    import jax

    m = _mixed_batch(6)
    ref = _direct(FabricSimulation, m)
    jax.clear_caches()
    _direct(JaxFabricSimulation, m)
    written = _cached_files(fresh_compile_cache)
    for _ in range(3):
        jax.clear_caches()
        _assert_same_as_numpy(_direct(JaxFabricSimulation, m), ref)
    # the reruns were served from disk: nothing new was compiled
    assert _cached_files(fresh_compile_cache) == written


# ------------------------------------------------------------------ #
# SYNC_STATS: interleaved == one after another
# ------------------------------------------------------------------ #


def test_sync_stats_interleaved_equals_serial():
    m = _mixed_batch(8)
    half_a = [build_simulation(sc) for sc in m[:4]]
    half_b = [build_simulation(sc) for sc in m[4:]]
    names_a = [sc.name for sc in m[:4]]
    names_b = [sc.name for sc in m[4:]]

    jax_backend.reset_sync_stats()
    JaxFabricSimulation(half_a, names=names_a).run()
    JaxFabricSimulation(half_b, names=names_b).run()
    serial_stats = dict(jax_backend.SYNC_STATS)

    jax_backend.reset_sync_stats()
    drivers = [
        JaxFabricSimulation(
            [build_simulation(sc) for sc in part],
            names=[sc.name for sc in part],
        )
        for part in (m[:4], m[4:])
    ]
    threads = [
        threading.Thread(target=d.run) for d in drivers
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    interleaved_stats = dict(jax_backend.SYNC_STATS)
    # wall keys are elapsed-seconds telemetry, not counters — they vary
    # run to run; the atomic-merge contract covers the counters
    from repro.eval.fabric.stats import WALL_KEYS

    strip = lambda d: {k: v for k, v in d.items() if k not in WALL_KEYS}
    assert strip(interleaved_stats) == strip(serial_stats)
    assert interleaved_stats["runs"] == 2
    assert interleaved_stats["scenarios"] == 8


def test_state_syncs_equal_in_serial_and_async_modes():
    """The device loop's whole-state syncs count one per driver run at
    any chunk size, as no batch here compacts or parks."""
    m = _mixed_batch(10)
    counts = {}
    for chunk_size in (4, 2):
        jax_backend.reset_sync_stats()
        run_matrix(m, backend="jax", chunk_size=chunk_size)
        stats = jax_backend.SYNC_STATS
        counts[chunk_size] = (stats["state_syncs"], stats["runs"])
    for syncs, runs in counts.values():
        assert syncs == runs > 0
    assert counts[2][1] > counts[4][1]


# ------------------------------------------------------------------ #
# build_files cache under concurrency
# ------------------------------------------------------------------ #


def test_files_cache_concurrent_access(monkeypatch):
    """Hammer the byte-bounded LRU from several threads with a cap small
    enough to force constant eviction: no exceptions, consistent
    accounting, correct filesets."""
    monkeypatch.setattr(scenarios_mod, "FILES_CACHE_MAX_BYTES", 16 * 1024)
    with scenarios_mod._files_cache_lock:
        scenarios_mod._files_cache.clear()
        scenarios_mod._files_cache_bytes = 0
    expected = {
        seed: scenarios_mod.build_files(
            Scenario(
                network=testbeds.LAN.name, dataset="uniform_small",
                algorithm="sc", seed=seed,
            )
        )
        for seed in range(6)
    }
    errors = []

    def worker(tid):
        try:
            for i in range(200):
                seed = (tid + i) % 6
                files = scenarios_mod.build_files(
                    Scenario(
                        network=testbeds.LAN.name, dataset="uniform_small",
                        algorithm="sc", seed=seed,
                    )
                )
                assert [f.size for f in files] == [
                    f.size for f in expected[seed]
                ]
                info = scenarios_mod.files_cache_info()
                assert 0 <= info["bytes"] <= info["max_bytes"]
        except BaseException as exc:  # surfaced after join
            errors.append(exc)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    info = scenarios_mod.files_cache_info()
    assert info["bytes"] <= info["max_bytes"]
    with scenarios_mod._files_cache_lock:
        scenarios_mod._files_cache.clear()
        scenarios_mod._files_cache_bytes = 0


# ------------------------------------------------------------------ #
# AOT warm-start
# ------------------------------------------------------------------ #


def test_signature_ladder_rungs():
    sig = (1024, 8, 4, 8, 1, 1, 1024)
    assert signature_ladder(sig) == (
        (1024, 8, 4, 8, 1, 1, 1024),
        (256, 8, 4, 8, 1, 1, 1024),
        (64, 8, 4, 8, 1, 1, 1024),
    )
    # below the floor: no rungs
    assert signature_ladder((8, 4, 1, 8, 1, 1, 1024)) == (
        (8, 4, 1, 8, 1, 1, 1024),
    )
    assert (
        signature_ladder((4096, 4, 1, 4, 1, 1, 1024))[-1][0] == COMPACT_FLOOR
    )
    # all-static candidate planes stop at their own (shallower) floor
    assert signature_ladder(sig, floor=256) == (
        (1024, 8, 4, 8, 1, 1, 1024),
        (256, 8, 4, 8, 1, 1, 1024),
    )


def test_plan_batches_get_plane_compact_floor():
    """All-static plan batches compact no further than PLAN_COMPACT_FLOOR
    (a static jit argument — plane and grid programs stay disjoint);
    batches holding controller rows keep the grid floor."""
    from repro.eval.fabric.bucketing import COMPACT_FLOOR
    from repro.eval.fabric.driver import FabricSimulation
    from repro.eval.fabric.plan import PLAN_COMPACT_FLOOR, build_plan

    static = [
        Scenario(
            network=testbeds.XSEDE.name, dataset="mixed",
            algorithm="static", max_cc=4, static_params=(0, 1, cc),
        )
        for cc in (1, 2, 4)
    ]
    mixed = static + [
        Scenario(
            network=testbeds.XSEDE.name, dataset="mixed",
            algorithm="promc", max_cc=4,
        )
    ]
    drv = FabricSimulation(None, plan=build_plan(static))
    assert drv.compact_floor() == PLAN_COMPACT_FLOOR
    drv = FabricSimulation(None, plan=build_plan(mixed))
    assert drv.compact_floor() == COMPACT_FLOOR


def test_signature_shapes_matches_real_upload():
    """The AOT aval table must mirror ``_upload`` exactly — a drifted
    dtype or axis silently downgrades every warmed signature to a jit
    fallback (or worse, a runtime mismatch)."""
    from repro.eval.fabric.jaxenv import x64

    from repro.eval.fabric.bucketing import qsizes_pad

    sc = Scenario(
        network=testbeds.XSEDE.name, dataset="mixed", algorithm="promc",
        max_cc=8,
    )
    drv = JaxFabricSimulation(
        [build_simulation(sc) for _ in range(3)], names=list("abc")
    )
    drv.start()
    need_c, need_p = drv.capacity_need()
    while drv.C < need_c:
        drv._grow()
    while drv.P < need_p:
        drv._grow_prepend()
    drv._stall = np.zeros(drv.S, dtype=np.int64)
    drv._q_pad = qsizes_pad(drv.qsizes.shape[0])
    with x64():
        mut, const = drv._upload()
    em, ec, eq = jax_backend.signature_shapes(drv._rounds_signature())
    assert set(mut) == set(em) and set(const) == set(ec)
    for real, exp in ((mut, em), (const, ec)):
        for k in real:
            assert tuple(real[k].shape) == tuple(exp[k].shape), k
            assert real[k].dtype == np.dtype(exp[k].dtype), k


def test_warm_signature_serves_runs_without_fresh_trace():
    sc = Scenario(
        network=testbeds.LONI.name, dataset="uniform_small",
        algorithm="sc", max_cc=2, seed=7,
    )
    sims = [build_simulation(sc) for _ in range(3)]
    probe = JaxFabricSimulation(sims, names=list("abc"))
    sig = canonical_signature(probe)
    jax_backend.warm_signature(sig)
    # warming twice is a no-op (exactly-once per process)
    assert jax_backend.warm_signature(sig) is False
    before = jax_backend._device_rounds._cache_size()
    out = probe.run()
    after = jax_backend._device_rounds._cache_size()
    assert after == before  # the AOT executable served the run
    assert out[0].total_bytes > 0
    assert jax_backend.compiled_program_count() >= 1


def test_executor_and_direct_runs_share_one_program():
    """A ``run_matrix`` sweep on one device keys its programs as a direct
    run does (``device=None``): a direct ``JaxFabricSimulation.run()`` of
    the same signature afterwards compiles nothing more."""
    sc = Scenario(
        network=testbeds.XSEDE.name, dataset="uniform_small",
        algorithm="mc", max_cc=2, seed=11,
    )
    m = [dataclasses.replace(sc, seed=11 + i) for i in range(3)]
    swept = run_matrix(m, backend="jax")
    n_programs = jax_backend.compiled_program_count()
    assert n_programs >= 1
    direct = _direct(JaxFabricSimulation, m)
    assert jax_backend.compiled_program_count() == n_programs
    for a, b in zip(swept, direct):
        assert (a.total_time, a.total_bytes) == (b.total_time, b.total_bytes)


# ------------------------------------------------------------------ #
# multi-device shard layer (own process: device count is import-time)
# ------------------------------------------------------------------ #

_MULTIDEV_SCRIPT = """
import jax
assert jax.device_count() == 4, jax.device_count()
from repro.eval.runner import run_matrix
from repro.eval.scenarios import smoke_matrix
m = smoke_matrix()[:8]
ev = run_matrix(m, backend="event")
ax = run_matrix(m, backend="jax", chunk_size=2)
for e, a in zip(ev, ax):
    assert a.total_bytes == e.total_bytes
    rel = abs(a.throughput - e.throughput) / max(e.throughput, 1e-12)
    assert rel < 0.02, rel
print("MULTIDEV-OK")
"""


@pytest.mark.slow
def test_four_device_round_robin_subprocess():
    """The shard layer on 4 simulated host devices: chunks round-robin
    across ``jax.devices()`` and results stay bit-clean vs the event
    reference. Subprocess because the XLA host device count is fixed at
    jax import."""
    import os

    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 "
        + env.get("XLA_FLAGS", "")
    )
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MULTIDEV-OK" in proc.stdout
