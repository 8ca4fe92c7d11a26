"""The overlap-pipelined executor: ordering, equivalence, donation, AOT
warm-start, thread-safety of the shared caches, and the multi-device
shard layer.

The invariants under test are the ones ``eval.runner`` promises:

  * results come back in **input order**, independent of chunk
    interleaving, device assignment, executor mode, and donation;
  * ``REPRO_FABRIC_EXECUTOR=serial`` preserves the historical strictly
    serial path (and the async pipeline matches it bitwise);
  * ``SYNC_STATS`` totals are identical whether chunks run serially or
    interleaved (per-run private accumulation, one locked merge);
  * the ``build_files`` byte-bounded LRU survives concurrent access;
  * AOT-warmed signatures serve runs without a fresh jit trace.
"""
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
from repro.eval.fabric.executor import execute_chunks, executor_mode
from repro.eval.fabric.jax_backend import JaxFabricSimulation
from repro.eval.runner import run_matrix
from repro.eval.scenarios import build_simulation, smoke_matrix


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
# mode resolution + serial escape hatch
# ------------------------------------------------------------------ #


def test_executor_mode_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_FABRIC_EXECUTOR", raising=False)
    assert executor_mode() == "async"
    assert executor_mode("serial") == "serial"
    monkeypatch.setenv("REPRO_FABRIC_EXECUTOR", "serial")
    assert executor_mode() == "serial"
    assert executor_mode("async") == "async"  # explicit arg wins
    monkeypatch.setenv("REPRO_FABRIC_EXECUTOR", "bogus")
    with pytest.raises(ValueError):
        executor_mode()


def test_donation_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_FABRIC_DONATE", raising=False)
    monkeypatch.delenv("REPRO_FABRIC_EXECUTOR", raising=False)
    assert jax_backend.donation_enabled() is True  # async default
    monkeypatch.setenv("REPRO_FABRIC_EXECUTOR", "serial")
    assert jax_backend.donation_enabled() is False  # historical path
    monkeypatch.setenv("REPRO_FABRIC_DONATE", "1")
    assert jax_backend.donation_enabled() is True  # env overrides
    monkeypatch.setenv("REPRO_FABRIC_DONATE", "0")
    monkeypatch.delenv("REPRO_FABRIC_EXECUTOR", raising=False)
    assert jax_backend.donation_enabled() is False
    assert jax_backend.donation_enabled(True) is False  # env beats kwarg


def test_donation_survives_persistent_cache(monkeypatch, tmp_path):
    """A configured persistent compilation cache leaves donation exactly
    as it resolves without one (donated programs are cached like any
    other); the env override still beats everything both ways."""
    import jax

    monkeypatch.delenv("REPRO_FABRIC_DONATE", raising=False)
    monkeypatch.delenv("REPRO_FABRIC_EXECUTOR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        assert jax_backend.donation_enabled() is True  # async default
        assert jax_backend.donation_enabled(True) is True
        monkeypatch.setenv("REPRO_FABRIC_EXECUTOR", "serial")
        assert jax_backend.donation_enabled() is False  # serial undonated
        monkeypatch.setenv("REPRO_FABRIC_DONATE", "0")
        monkeypatch.delenv("REPRO_FABRIC_EXECUTOR", raising=False)
        assert jax_backend.donation_enabled() is False  # kill switch wins
        monkeypatch.setenv("REPRO_FABRIC_DONATE", "1")
        monkeypatch.setenv("REPRO_FABRIC_EXECUTOR", "serial")
        assert jax_backend.donation_enabled() is True  # force wins
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serial_env_escape_hatch(monkeypatch):
    """REPRO_FABRIC_EXECUTOR=serial must route through the plain loop:
    no prep/compute threads are spawned at all."""
    monkeypatch.setenv("REPRO_FABRIC_EXECUTOR", "serial")
    spawned = []
    orig = threading.Thread

    class SpyThread(orig):
        def __init__(self, *a, **kw):
            spawned.append(kw.get("name"))
            super().__init__(*a, **kw)

    monkeypatch.setattr(threading, "Thread", SpyThread)
    m = _mixed_batch(6)
    out = run_matrix(m, backend="numpy", chunk_size=2)
    assert len(out) == 6 and all(r is not None for r in out)
    assert not any(n and n.startswith("fabric-") for n in spawned)


# ------------------------------------------------------------------ #
# result ordering + serial/async equivalence
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_async_matches_serial_bitwise(backend):
    m = _mixed_batch(10)
    serial = run_matrix(m, backend=backend, chunk_size=4, executor="serial")
    pipelined = run_matrix(m, backend=backend, chunk_size=4, executor="async")
    for s, a in zip(serial, pipelined):
        assert a.total_bytes == s.total_bytes
        assert a.total_time == s.total_time
        assert a.n_events == s.n_events
        assert a.n_moves == s.n_moves


def test_results_in_input_order_any_chunking():
    """Per-row results are independent of chunk composition and always
    land at the row's input index (scenarios never interact)."""
    m = _mixed_batch(9)
    baseline = run_matrix(m, backend="numpy", executor="serial")
    for chunk_size in (1, 2, 5, 64):
        out = run_matrix(
            m, backend="numpy", chunk_size=chunk_size, executor="async"
        )
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
    execute_chunks(
        FabricSimulation, parts, builders, names, results, mode="async"
    )
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
            FabricSimulation, [[0, 1], [2, 3]], builders, names,
            [None] * 4, mode="async",
        )


# ------------------------------------------------------------------ #
# donation
# ------------------------------------------------------------------ #


def test_donation_on_off_identical_results():
    m = _mixed_batch(4)
    sims = lambda: [build_simulation(sc) for sc in m]  # noqa: E731
    names = [sc.name for sc in m]
    on = JaxFabricSimulation(sims(), names=names, donate=True).run()
    off = JaxFabricSimulation(sims(), names=names, donate=False).run()
    for a, b in zip(on, off):
        assert a.total_time == b.total_time
        assert a.total_bytes == b.total_bytes
        assert a.n_events == b.n_events


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
    are written to it and a donated run matches the undonated one."""
    import jax

    m = _mixed_batch(4)
    sims = lambda: [build_simulation(sc) for sc in m]  # noqa: E731
    names = [sc.name for sc in m]
    jax.clear_caches()
    on = JaxFabricSimulation(sims(), names=names, donate=True).run()
    off = JaxFabricSimulation(sims(), names=names, donate=False).run()
    assert any(
        "device_rounds" in f for f in _cached_files(fresh_compile_cache)
    )
    for a, b in zip(on, off):
        assert a.total_time == b.total_time
        assert a.total_bytes == b.total_bytes
        assert a.n_events == b.n_events


def test_donated_program_read_back_from_cache(fresh_compile_cache):
    """The stale-alias regression: a donated executable *read back* from
    the persistent cache (in-memory caches cleared, so nothing is
    recompiled) must give the undonated results, run after run — jax
    0.4.37 on CPU aliased stale buffers on exactly this path."""
    import jax

    m = _mixed_batch(6)
    sims = lambda: [build_simulation(sc) for sc in m]  # noqa: E731
    names = [sc.name for sc in m]
    off = JaxFabricSimulation(sims(), names=names, donate=False).run()
    jax.clear_caches()
    JaxFabricSimulation(sims(), names=names, donate=True).run()
    written = _cached_files(fresh_compile_cache)
    for _ in range(3):
        jax.clear_caches()
        on = JaxFabricSimulation(sims(), names=names, donate=True).run()
        for a, b in zip(on, off):
            assert (a.total_time, a.total_bytes, a.n_events) == (
                b.total_time, b.total_bytes, b.n_events
            )
    # the reruns were served from disk: nothing new was compiled
    assert _cached_files(fresh_compile_cache) == written


# ------------------------------------------------------------------ #
# SYNC_STATS: interleaved == serial
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
    jax_backend.warm_signature(sig, donate=probe.donate)
    # warming twice is a no-op (exactly-once per process)
    assert jax_backend.warm_signature(sig, donate=probe.donate) is False
    before = (
        jax_backend._device_rounds._cache_size()
        + jax_backend._device_rounds_donated._cache_size()
    )
    out = probe.run()
    after = (
        jax_backend._device_rounds._cache_size()
        + jax_backend._device_rounds_donated._cache_size()
    )
    assert after == before  # the AOT executable served the run
    assert out[0].total_bytes > 0
    assert jax_backend.compiled_program_count() >= 1


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
ax = run_matrix(m, backend="jax", chunk_size=2, executor="async")
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
