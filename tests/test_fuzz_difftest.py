"""Randomized cross-backend differential fuzz: the seeded counterpart of
the fixed golden matrix.

Each example draws a random ``NetworkSpec`` (bandwidth / RTT / buffers /
disk contention, optional control-RTT asymmetry and a time-varying
bandwidth profile), a random fileset (degenerate cases included: 1-file
datasets, zero-size files, single-class swarms), and a random scheduler
configuration, then runs the *same* simulation through the event
reference, the batched NumPy fabric driver, and the JAX device loop and
holds all three to the matrix difftest's 2% bar (agreement is bit-level
in practice).

Seeding is fixed either way: the vendored offline hypothesis shim seeds
draws from the test's qualified name, and the real library runs with
``derandomize=True`` — CI replays the identical example set on every
push. This is the harness that caught the channel-ordering divergence
(recycled columns vs. the event simulator's list order) now pinned by
``tests/test_zero_host_rounds.py::test_channel_order_tie_regression``.
"""
import hypothesis.strategies as st
import numpy as np
from hypothesis import given, settings

from repro.core.runner import build_scheduler
from repro.core.simulator import Simulation
from repro.core.types import GB, KB, MB, DiskSpec, FileSpec, NetworkSpec, gbps
from repro.eval.runner import run_simulations

RTOL = 0.02

#: file-size pool: spans all four size classes on every generated network,
#: plus the degenerate zero-size file (metadata-only transfer)
SIZE_POOL = (
    0, 64 * KB, 1 * MB, 4 * MB, 48 * MB, 200 * MB, 900 * MB, 2 * GB,
    8 * GB,
)

#: piecewise-constant capacity profiles (None = static path)
PROFILES = (
    None,
    ((0.0, 1.0), (10.0, 0.5)),
    ((0.0, 1.0), (5.0, 0.4), (30.0, 0.9)),
    ((0.0, 0.7), (20.0, 1.0)),
)


def _network(bw_gbps, rtt_ms, buf_mb, disk_frac, sat_cc, contention,
             unhidden_ms, ctrl_mult, profile):
    bw = gbps(bw_gbps)
    return NetworkSpec(
        name="fuzz-net",
        bandwidth=bw,
        rtt=rtt_ms * 1e-3,
        buffer_size=buf_mb * MB,
        disk=DiskSpec(
            streaming_rate=bw * disk_frac,
            per_file_overhead=0.004,
            saturation_cc=sat_cc,
            contention=contention,
            per_channel_rate=bw * 0.35,
        ),
        unhidden_overhead=unhidden_ms * 1e-3,
        control_rtt=None if ctrl_mult is None else rtt_ms * 1e-3 * ctrl_mult,
        bandwidth_profile=profile,
    )


#: fixed (pp, p, cc) draws for the static controller kind (the
#: autotuner's candidate rows): the same event/numpy/jax 2% bar as the
#: adaptive schedulers, including a deliberately oversubscribed cc=16
STATIC_PARAMS = ((0, 1, 1), (8, 2, 2), (32, 4, 8), (4, 8, 4), (128, 1, 16))


def _run(backend, files, net, algo, max_cc, num_chunks, tick,
         static_params=None):
    # fresh scheduler per backend: controllers are stateful
    kw = {"static_params": static_params} if algo == "static" else {}
    sched = build_scheduler(
        algo, files, net, max_cc=max_cc, num_chunks=num_chunks, **kw
    )
    sim = Simulation(
        sched.chunks, sched.network, sched, tick_period=tick
    )
    return run_simulations([sim], names=["fuzz"], backend=backend)[0]


@settings(max_examples=8, deadline=None, derandomize=True)
@given(
    bw_gbps=st.sampled_from([0.5, 2.0, 10.0, 30.0]),
    rtt_ms=st.sampled_from([0.2, 10.0, 60.0, 150.0]),
    buf_mb=st.sampled_from([1, 4, 32]),
    disk_frac=st.sampled_from([0.3, 0.9, 1.5]),
    sat_cc=st.sampled_from([2, 8, 12]),
    contention=st.sampled_from([0.0, 0.02, 0.08]),
    unhidden_ms=st.sampled_from([0.0, 12.0, 55.0]),
    ctrl_mult=st.sampled_from([None, 1.0, 4.0, 15.0]),
    profile=st.sampled_from(PROFILES),
    sizes=st.lists(
        st.sampled_from(SIZE_POOL), min_size=1, max_size=14
    ),
    algo=st.sampled_from(
        ["sc", "mc", "promc", "globus", "untuned", "static"]
    ),
    static_params=st.sampled_from(STATIC_PARAMS),
    max_cc=st.sampled_from([1, 2, 8, 16]),
    num_chunks=st.sampled_from([1, 2, 3, 4]),
    tick=st.sampled_from([1.0, 2.5, 5.0]),
)
def test_fuzz_event_numpy_jax_agree(
    bw_gbps, rtt_ms, buf_mb, disk_frac, sat_cc, contention, unhidden_ms,
    ctrl_mult, profile, sizes, algo, static_params, max_cc, num_chunks,
    tick,
):
    net = _network(
        bw_gbps, rtt_ms, buf_mb, disk_frac, sat_cc, contention,
        unhidden_ms, ctrl_mult, profile,
    )
    files = [FileSpec(f"f{i}", s) for i, s in enumerate(sizes)]
    results = {
        backend: _run(
            backend, files, net, algo, max_cc, num_chunks, tick,
            static_params=static_params,
        )
        for backend in ("event", "numpy", "jax")
    }
    ev = results["event"]
    for backend in ("numpy", "jax"):
        r = results[backend]
        assert r.total_bytes == ev.total_bytes
        denom = max(abs(ev.throughput), 1e-12)
        rel = abs(r.throughput - ev.throughput) / denom
        assert rel <= RTOL, (
            f"{backend} diverged: event={ev.throughput:.6g} "
            f"{backend}={r.throughput:.6g} rel={rel:.3%} "
            f"(net bw={bw_gbps}g rtt={rtt_ms}ms ctrl={ctrl_mult} "
            f"prof={profile is not None} algo={algo} cc={max_cc} "
            f"k={num_chunks} tick={tick} files={len(sizes)})"
        )
    # the fabric instantiations must not drift apart either
    rel_nj = abs(
        results["numpy"].throughput - results["jax"].throughput
    ) / max(abs(results["numpy"].throughput), 1e-12)
    assert rel_nj <= RTOL


def test_fuzz_degenerate_single_zero_file():
    """The fully degenerate corner pinned explicitly (not left to the
    draw): a 1-file dataset whose only file is zero bytes."""
    net = _network(2.0, 10.0, 4, 0.9, 8, 0.02, 12.0, None, None)
    files = [FileSpec("empty", 0)]
    out = {
        b: _run(b, files, net, a, 4, 2, 5.0)
        for b in ("event", "numpy", "jax")
        for a in ("sc",)
    }
    for b, r in out.items():
        assert r.total_bytes == 0
        assert np.isfinite(r.total_time)
    assert out["numpy"].total_time == out["jax"].total_time
    assert abs(
        out["numpy"].total_time - out["event"].total_time
    ) <= 1e-9 * max(out["event"].total_time, 1.0)


@settings(max_examples=4, deadline=None, derandomize=True)
@given(
    bw_gbps=st.sampled_from([2.0, 30.0]),
    rtt_ms=st.sampled_from([0.2, 60.0]),
    algo=st.sampled_from(["sc", "mc", "promc"]),
    max_cc=st.sampled_from([2, 8]),
    n_variants=st.sampled_from([5, 9]),
)
def test_fuzz_async_executor_matches_event(
    bw_gbps, rtt_ms, algo, max_cc, n_variants,
):
    """The same fuzz-style draws pushed through ``run_built`` with the
    overlap-pipelined executor and a tiny chunk size (forcing several
    in-flight chunks): per-row results must match the event reference
    within the difftest bar and land at their input index."""
    from repro.eval.runner import run_built

    net = _network(bw_gbps, rtt_ms, 4, 0.9, 8, 0.02, 12.0, None, None)
    variants = [
        [FileSpec(f"f{i}", SIZE_POOL[(i + v) % len(SIZE_POOL)])
         for i in range(1 + v)]
        for v in range(n_variants)
    ]

    def make_builder(files):
        def build():
            sched = build_scheduler(
                algo, files, net, max_cc=max_cc, num_chunks=2
            )
            return Simulation(
                sched.chunks, sched.network, sched, tick_period=2.5
            )
        return build

    builders = [make_builder(f) for f in variants]
    names = [f"v{v}" for v in range(n_variants)]
    ev = [b().run() for b in builders]
    for backend in ("numpy", "jax"):
        out = run_built(builders, names, backend=backend, chunk_size=2)
        for i, (e, r) in enumerate(zip(ev, out)):
            assert r.total_bytes == e.total_bytes, i
            rel = abs(r.throughput - e.throughput) / max(
                abs(e.throughput), 1e-12
            )
            assert rel <= RTOL, (backend, i, rel)
