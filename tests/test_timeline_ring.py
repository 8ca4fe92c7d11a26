"""On-device timeline ring buffer: decimation-kernel properties and
cross-backend timeline equivalence.

The fabric backends record (t, aggregate rate) samples through
``kernels.timeline_push`` — a streaming uniform-stride decimator over a
fixed per-scenario budget — instead of host-side list appends, which is
what lets timeline-recording scenarios stay inside the JAX device loop.
These tests pin:

  * the kernel's invariants (monotone t, first/last sample preserved,
    budget respected, stored samples a uniform-stride subsequence);
  * bit-identical recording between the NumPy and JAX instantiations on
    the same sample stream;
  * end-to-end equivalence on every timeline-recording scenario of the
    matrix (``timeline_matrix``): numpy == jax bit-for-bit, and both
    match the event backend's host-appended timeline at the decimation
    stride's candidate indices.
"""
import hypothesis.strategies as st
import numpy as np
import pytest
from hypothesis import given, settings

from repro.eval.fabric import kernels
from repro.eval.fabric.driver import FabricSimulation
from repro.eval.fabric.shim import jax_ops, numpy_ops
from repro.eval.scenarios import build_simulation, timeline_matrix

# ------------------------------------------------------------------ #
# decimation-kernel properties (scalar stream through the batched kernel)
# ------------------------------------------------------------------ #


def _record_stream(ops, xp, samples, budget):
    """Push a (t, rate) stream through timeline_push on one row."""
    buf_t = xp.zeros((1, budget))
    buf_r = xp.zeros((1, budget))
    length = xp.zeros(1, dtype=xp.int64)
    stride = xp.ones(1, dtype=xp.int64)
    seen = xp.zeros(1, dtype=xp.int64)
    last_t = xp.zeros(1)
    last_r = xp.zeros(1)
    rec = xp.ones(1, dtype=bool)
    for t, r in samples:
        buf_t, buf_r, length, stride, seen, last_t, last_r = (
            kernels.timeline_push(
                ops, rec, xp.full(1, t), xp.full(1, r), buf_t, buf_r,
                length, stride, seen, last_t, last_r,
            )
        )
    return buf_t, buf_r, length, stride, seen, last_t, last_r


@settings(max_examples=60, deadline=None)
@given(
    dts=st.lists(
        st.floats(min_value=1e-3, max_value=100.0), min_size=1, max_size=200
    ),
    budget=st.sampled_from([2, 3, 4, 7, 8, 16, 32]),
)
def test_timeline_push_invariants(dts, budget):
    ops = numpy_ops()
    ts = np.cumsum(dts)
    samples = [(float(t), float(i)) for i, t in enumerate(ts)]
    state = _record_stream(ops, np, samples, budget)
    buf_t, buf_r, length, stride, seen, last_t, last_r = (
        np.asarray(a) for a in state
    )
    n, s = int(length[0]), int(stride[0])
    assert int(seen[0]) == len(samples)
    assert 0 < n <= budget
    # stored samples are exactly the candidates at indices {0, s, 2s, ...}
    for j in range(n):
        want_t, want_r = samples[j * s]
        assert buf_t[0, j] == want_t and buf_r[0, j] == want_r
    # monotone t, first sample preserved
    assert (np.diff(buf_t[0, :n]) > 0).all() or n == 1
    assert buf_t[0, 0] == samples[0][0]
    # finalize: budget respected, first/last preserved
    out = kernels.timeline_samples(
        buf_t[0], buf_r[0], length[0], stride[0], seen[0], last_t[0],
        last_r[0],
    )
    assert len(out) <= budget
    assert out[0] == samples[0] or len(samples) > 1 and out[0] == samples[0]
    assert out[-1] == samples[-1]
    assert all(a[0] < b[0] for a, b in zip(out, out[1:]))


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=120),
    budget=st.sampled_from([2, 4, 8, 16]),
)
def test_timeline_push_numpy_jax_bit_identical(n, budget):
    """The same sample stream records bit-identically on both ArrayOps
    instantiations (the kernel is pure selects — no float arithmetic)."""
    import jax.numpy as jnp
    from repro.eval.fabric.jaxenv import x64

    rng = np.random.RandomState(n * 1000 + budget)
    ts = np.cumsum(rng.uniform(1e-3, 10.0, size=n))
    samples = [(float(t), float(rng.uniform(0, 1e9))) for t in ts]
    np_state = _record_stream(numpy_ops(), np, samples, budget)
    with x64():
        jx_state = _record_stream(jax_ops(), jnp, samples, budget)
    for a, b in zip(np_state, jx_state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_timeline_push_masked_rows_freeze():
    """Rows with rec=False pass through every array untouched."""
    ops = numpy_ops()
    buf_t = np.arange(8, dtype=np.float64).reshape(1, 8)
    buf_r = buf_t * 2
    state = kernels.timeline_push(
        ops, np.zeros(1, dtype=bool), np.full(1, 99.0), np.full(1, 1.0),
        buf_t, buf_r, np.full(1, 3, dtype=np.int64),
        np.ones(1, dtype=np.int64), np.full(1, 3, dtype=np.int64),
        np.zeros(1), np.zeros(1),
    )
    np.testing.assert_array_equal(state[0], buf_t)
    np.testing.assert_array_equal(state[1], buf_r)
    assert int(state[2][0]) == 3 and int(state[4][0]) == 3


# ------------------------------------------------------------------ #
# end-to-end: every timeline-recording scenario of the matrix
# ------------------------------------------------------------------ #


def _fabric_timelines(cls, scenarios, **kw):
    sims = [build_simulation(s) for s in scenarios]
    results = cls(sims, names=[s.name for s in scenarios], **kw).run()
    return [r.timeline for r in results]


def _assert_ordered_submatch(sub, full, name, rtol=1e-9, atol=1e-6):
    """Every (t, rate) of ``sub`` matches some sample of ``full``, in
    order. The fluid backends may coalesce a zero-dt event boundary the
    scalar loop splits in two (their sweep counts differ by a handful of
    duplicate-t samples), so sample-for-sample equality is too strict —
    an ordered match within tolerance is the contract."""

    def close(a, b):
        return all(
            abs(x - y) <= atol + rtol * abs(y) for x, y in zip(a, b)
        )

    i = 0
    for s in sub:
        while i < len(full) and not close(s, full[i]):
            i += 1
        assert i < len(full), (
            f"{name}: fabric sample {s} not found in order in the event "
            "timeline"
        )
        i += 1


def _check_timeline_grid(scenarios):
    from repro.eval.fabric.jax_backend import JaxFabricSimulation

    assert scenarios and all(s.record_timeline for s in scenarios)
    event = [build_simulation(s).run().timeline for s in scenarios]
    numpy_tl = _fabric_timelines(FabricSimulation, scenarios)
    jax_tl = _fabric_timelines(JaxFabricSimulation, scenarios)
    for s, te, tn, tj in zip(scenarios, event, numpy_tl, jax_tl):
        assert tn == tj, f"numpy/jax timelines differ on {s.name}"
        assert abs(len(tn) - len(te)) <= max(2, len(te) // 20), s.name
        assert tn[0] == te[0], s.name
        np.testing.assert_allclose(
            np.asarray(tn[-1]), np.asarray(te[-1]), rtol=1e-9, atol=1e-6,
            err_msg=s.name,
        )
        _assert_ordered_submatch(tn, te, s.name)


def test_timeline_slice_backends_agree():
    """Tier-1 slice: the jax ring buffer is bit-identical to the numpy
    kernel's and both match the event backend's host-appended timeline
    (ordered match within tolerance) on a cross-section of the
    timeline-recording matrix."""
    _check_timeline_grid(timeline_matrix()[::4])


@pytest.mark.slow
def test_timeline_matrix_backends_agree():
    """Every timeline-recording scenario of the matrix, all three
    backends (the satellite acceptance grid; tier-1 runs the slice)."""
    _check_timeline_grid(timeline_matrix())


def test_timeline_decimated_slice():
    """Force decimation with a tiny budget: the decimated timeline is the
    exact uniform-stride subsequence of the same backend's full recording
    (bit-for-bit, first/last preserved), and numpy/jax stay identical."""
    from repro.eval.fabric.jax_backend import JaxFabricSimulation

    scenarios = timeline_matrix()[:3]
    budget = 16
    full_tl = _fabric_timelines(
        FabricSimulation, scenarios, timeline_budget=1 << 16
    )
    numpy_tl = _fabric_timelines(
        FabricSimulation, scenarios, timeline_budget=budget
    )
    jax_tl = _fabric_timelines(
        JaxFabricSimulation, scenarios, timeline_budget=budget
    )
    for s, tf, tn, tj in zip(scenarios, full_tl, numpy_tl, jax_tl):
        assert tn == tj, f"numpy/jax timelines differ on {s.name}"
        assert len(tf) > budget, (
            f"{s.name} too short to exercise decimation"
        )
        assert len(tn) <= budget
        # expected stride follows the kernel's halve-when-full walk over
        # the same candidate stream the full recording captured
        stride, length = 1, 0
        for i in range(len(tf)):
            if i % stride == 0:
                if length >= budget:
                    length, stride = (length + 1) // 2, stride * 2
                if i % stride == 0 and length < budget:
                    length += 1
        body, last = tn[:-1], tn[-1]
        assert body == [tf[j * stride] for j in range(len(body))], s.name
        assert last == tf[-1], s.name


def test_timeline_budget_validation():
    sims = [build_simulation(timeline_matrix()[0])]
    with pytest.raises(ValueError):
        FabricSimulation(sims, timeline_budget=1)
