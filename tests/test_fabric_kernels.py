"""Fabric-kernel equivalence: the backend-neutral array kernels must match
the scalar references (``netmodel.waterfill``, ``fabric.reference``) on
random inputs, and the NumPy and JAX instantiations must agree with each
other bit-for-bit on the same inputs.

These are the property tests backing the fidelity contract in the
``repro.eval.fabric`` package docstring.
"""
import math

import hypothesis.strategies as st
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import netmodel, testbeds
from repro.core.types import TransferParams
from repro.eval.fabric import kernels
from repro.eval.fabric.reference import next_event_dt, tick_rate_update
from repro.eval.fabric.shim import jax_ops, numpy_ops

_NP = numpy_ops()

# ------------------------------------------------------------------ #
# water-filling
# ------------------------------------------------------------------ #


@settings(max_examples=200, deadline=None)
@given(
    caps=st.lists(
        st.floats(min_value=0.0, max_value=1e10), min_size=1, max_size=12
    ),
    pool=st.floats(min_value=0.0, max_value=5e10),
)
def test_waterfill_kernel_matches_scalar_reference(caps, pool):
    batch = kernels.waterfill_batch(np.array([caps]), np.array([pool]))[0]
    scalar = netmodel.waterfill(caps, pool)
    assert batch.shape == (len(caps),)
    np.testing.assert_allclose(batch, scalar, rtol=1e-9, atol=1e-3)


def _rates(max_value):
    """0 or a rate far enough above the subnormal range that no share or
    difference of rates falls into it: XLA flushes subnormal operands and
    results to zero (NumPy keeps them), and a 1e-309 B/s share is no
    transfer, so the agreement contract is over normal floats."""
    return st.one_of(
        st.just(0.0), st.floats(min_value=1e-200, max_value=max_value)
    )


@settings(max_examples=50, deadline=None)
@given(
    caps=st.lists(_rates(1e10), min_size=1, max_size=12),
    pool=_rates(5e10),
)
def test_waterfill_numpy_and_jax_agree(caps, pool):
    from repro.eval.fabric.jaxenv import x64

    ref = kernels.waterfill(_NP, np.array([caps]), np.array([pool]))
    with x64():
        import jax.numpy as jnp

        out = kernels.waterfill(
            jax_ops(), jnp.asarray(np.array([caps])),
            jnp.asarray(np.array([pool])),
        )
    # XLA may contract the water-level arithmetic into FMAs, so agreement
    # is to the ulp, not bitwise
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-12, atol=0)


def test_waterfill_conservation_many_rows():
    rng = np.random.RandomState(0)
    caps = rng.uniform(0, 1e9, size=(64, 8))
    caps[rng.uniform(size=caps.shape) < 0.3] = 0.0  # idle channels
    pool = rng.uniform(0, 4e9, size=64)
    out = kernels.waterfill_batch(caps, pool)
    assert (out <= caps + 1e-6).all()
    assert (out.sum(axis=1) <= pool + 1e-3).all()


def test_waterfill_pallas_matches_closed_form():
    pytest.importorskip("jax.experimental.pallas")
    from repro.eval.fabric.kernels.waterfill_pallas import waterfill_pallas_f64

    rng = np.random.RandomState(1)
    caps = rng.uniform(0, 1e9, size=(32, 8))
    caps[rng.uniform(size=caps.shape) < 0.3] = 0.0
    pool = rng.uniform(0, 4e9, size=32)
    ref = kernels.waterfill_batch(caps, pool)
    out = waterfill_pallas_f64(caps, pool)
    np.testing.assert_allclose(out, ref, rtol=1e-9, atol=1e-3)


# ------------------------------------------------------------------ #
# per-file dead time
# ------------------------------------------------------------------ #


@settings(max_examples=100, deadline=None)
@given(
    pp=st.integers(min_value=0, max_value=32),
    net=st.sampled_from(list(testbeds.TESTBEDS)),
)
def test_dead_time_kernel_matches_netmodel(pp, net):
    network = testbeds.TESTBEDS[net]
    params = TransferParams(pipelining=pp, parallelism=2, concurrency=1)
    scalar = netmodel.file_start_dead_time(network, params)
    control = (
        network.control_rtt if network.control_rtt is not None
        else network.rtt
    )
    batch = kernels.file_dead_time(
        _NP,
        np.full(3, control),
        np.full(3, float(pp)),
        np.full(3, network.unhidden_overhead),
        np.full(3, network.disk.per_file_overhead),
    )
    np.testing.assert_allclose(batch, scalar, rtol=1e-12)


# ------------------------------------------------------------------ #
# tick EMA
# ------------------------------------------------------------------ #


@settings(max_examples=200, deadline=None)
@given(
    prev=st.floats(min_value=0.0, max_value=1e10),
    delta=st.floats(min_value=0.0, max_value=1e12),
    period=st.floats(min_value=1e-3, max_value=60.0),
)
def test_tick_ema_kernel_matches_scalar_reference(prev, delta, period):
    scalar = tick_rate_update(prev, delta, period)
    batch = kernels.tick_ema(
        _NP, np.array([[prev]]), np.array([[delta]]), np.array([[0.0]]),
        np.array([[period]]),
    )
    np.testing.assert_allclose(batch[0, 0], scalar, rtol=1e-12)


# ------------------------------------------------------------------ #
# next-event horizon
# ------------------------------------------------------------------ #


@settings(max_examples=200, deadline=None)
@given(
    tick_dt=st.floats(min_value=0.0, max_value=10.0),
    chans=st.lists(
        st.tuples(
            st.floats(min_value=0.0, max_value=5.0),   # dead
            st.floats(min_value=0.0, max_value=1e9),   # remaining
            st.floats(min_value=0.0, max_value=1e9),   # rate
            st.integers(min_value=0, max_value=1),     # busy
        ),
        min_size=1,
        max_size=8,
    ),
)
def test_event_horizon_matches_scalar_reference(tick_dt, chans):
    dead = np.array([c[0] for c in chans])
    rem = np.array([c[1] for c in chans])
    rate = np.array([c[2] for c in chans])
    busy = np.array([bool(c[3]) for c in chans])
    transferring = busy & (dead <= 1e-12)
    # scalar reference considers only busy channels; in-dead-time channels
    # contribute their dead-time expiry, transferring ones rem/rate
    scalar = next_event_dt(
        tick_dt,
        dead[busy],
        rem[busy],
        np.where(transferring, rate, 0.0)[busy],
    )
    batch = kernels.event_horizon(
        _NP, np.array([tick_dt]), busy[None], dead[None],
        transferring[None], rem[None], np.where(transferring, rate, 0.0)[None],
    )
    np.testing.assert_allclose(batch[0], scalar, rtol=1e-12)


# ------------------------------------------------------------------ #
# queue feeding
# ------------------------------------------------------------------ #


def _scalar_feed(chunk_of, busy, qsizes, qoff, qlen, qptr):
    """Deque-free reference of the FIFO feed for one scenario."""
    busy = list(busy)
    qptr = list(qptr)
    assign = {}
    for c, k in enumerate(chunk_of):
        if k < 0 or busy[c]:
            continue
        if qptr[k] < qlen[k]:
            assign[c] = qsizes[qoff[k] + qptr[k]]
            qptr[k] += 1
            busy[c] = True
    return assign, qptr


@settings(max_examples=100, deadline=None)
@given(
    layout=st.lists(
        st.tuples(
            st.integers(min_value=-1, max_value=2),  # chunk_of
            st.integers(min_value=0, max_value=1),   # busy
        ),
        min_size=1,
        max_size=10,
    ),
    lens=st.lists(
        st.integers(min_value=0, max_value=4), min_size=3, max_size=3
    ),
    ptrs=st.lists(
        st.integers(min_value=0, max_value=4), min_size=3, max_size=3
    ),
)
def test_feed_kernel_matches_scalar_fifo(layout, lens, ptrs):
    K = 3
    chunk_of = np.array([c for c, _ in layout], dtype=np.int64)
    busy = np.array([bool(b) for _, b in layout])
    qlen = np.array(lens, dtype=np.int64)
    qptr = np.array([min(p, l) for p, l in zip(ptrs, lens)], dtype=np.int64)
    qoff = np.array([0, qlen[0], qlen[0] + qlen[1]], dtype=np.int64)
    qsizes = np.arange(1.0, float(qlen.sum()) + 1.0) * 1e6
    C = len(layout)
    busy2, dead2, rem2, qptr2, qb2, _pn2 = kernels.feed_queues(
        _NP, np.array([True]), chunk_of[None], busy[None],
        np.zeros((1, C)), np.zeros((1, C)), qsizes, qoff[None], qlen[None],
        qptr[None], np.zeros((1, K)), np.full((1, K), 0.25),
    )
    assign, qptr_ref = _scalar_feed(chunk_of, busy, qsizes, qoff, qlen, qptr)
    np.testing.assert_array_equal(qptr2[0], qptr_ref)
    for c in range(C):
        if c in assign:
            assert busy2[0, c] and rem2[0, c] == assign[c]
            assert dead2[0, c] == 0.25
        else:
            assert busy2[0, c] == busy[c]
            assert rem2[0, c] == 0.0


def test_feed_kernel_numpy_and_jax_agree():
    from repro.eval.fabric.jaxenv import x64

    rng = np.random.RandomState(2)
    S, C, K, P = 16, 6, 3, 4
    chunk_of = rng.randint(-1, K, size=(S, C)).astype(np.int64)
    busy = rng.uniform(size=(S, C)) < 0.4
    qlen = rng.randint(0, 5, size=(S, K)).astype(np.int64)
    qptr = np.minimum(rng.randint(0, 5, size=(S, K)), qlen).astype(np.int64)
    qoff = np.cumsum(np.concatenate([[0], qlen.ravel()[:-1]])).reshape(S, K)
    qsizes = rng.uniform(1e6, 1e9, size=int(qlen.sum()) + 1)
    dead = rng.uniform(0, 1, size=(S, C))
    rem = np.where(busy, rng.uniform(1e6, 1e9, size=(S, C)), 0.0)
    qb = rng.uniform(0, 1e10, size=(S, K))
    fsdt = rng.uniform(0, 1, size=(S, K))
    enabled = rng.uniform(size=S) < 0.8
    pn = rng.randint(0, P + 1, size=(S, K)).astype(np.int64)
    ps = rng.uniform(1e5, 1e8, size=(S, K, P))

    ref = kernels.feed_queues(
        _NP, enabled, chunk_of, busy, dead, rem, qsizes, qoff, qlen, qptr,
        qb, fsdt, ps, pn,
    )
    with x64():
        import jax.numpy as jnp

        out = kernels.feed_queues(
            jax_ops(), jnp.asarray(enabled), jnp.asarray(chunk_of),
            jnp.asarray(busy), jnp.asarray(dead), jnp.asarray(rem),
            jnp.asarray(qsizes), jnp.asarray(qoff), jnp.asarray(qlen),
            jnp.asarray(qptr), jnp.asarray(qb), jnp.asarray(fsdt),
            jnp.asarray(ps), jnp.asarray(pn),
        )
    for r, o in zip(ref, out):
        np.testing.assert_allclose(np.asarray(o), r, rtol=1e-12, atol=0)


def test_feed_kernel_lifo_resume_stack_before_fifo():
    """Idle channels consume resume files newest-first, then fall back to
    the FIFO queue — deque.appendleft/popleft order."""
    chunk_of = np.array([[0, 0, 0, -1]], dtype=np.int64)
    busy = np.zeros((1, 4), dtype=bool)
    qsizes = np.array([111.0, 222.0])
    qoff = np.array([[0]], dtype=np.int64)
    qlen = np.array([[2]], dtype=np.int64)
    qptr = np.array([[0]], dtype=np.int64)
    qb = np.array([[999.0]])
    fsdt = np.array([[0.5]])
    ps = np.array([[[7.0, 9.0, 0.0, 0.0]]])  # stack: bottom 7, top 9
    pn = np.array([[2]], dtype=np.int64)
    busy2, dead2, rem2, qptr2, qb2, pn2 = kernels.feed_queues(
        _NP, np.array([True]), chunk_of, busy, np.zeros((1, 4)),
        np.zeros((1, 4)), qsizes, qoff, qlen, qptr, qb, fsdt, ps, pn,
    )
    # col0 pops the top (9), col1 the next (7), col2 takes FIFO head (111)
    np.testing.assert_array_equal(rem2[0, :3], [9.0, 7.0, 111.0])
    assert busy2[0, :3].all() and not busy2[0, 3]
    assert pn2[0, 0] == 0 and qptr2[0, 0] == 1
    np.testing.assert_allclose(qb2[0, 0], 999.0 - 9.0 - 7.0 - 111.0)


# ------------------------------------------------------------------ #
# disk pool + advance
# ------------------------------------------------------------------ #


@settings(max_examples=100, deadline=None)
@given(
    n_t=st.integers(min_value=0, max_value=64),
    net=st.sampled_from(list(testbeds.TESTBEDS)),
)
def test_disk_pool_matches_allocate_rates_pool(n_t, net):
    network = testbeds.TESTBEDS[net]
    pool = kernels.disk_pool(
        _NP, np.array([n_t]), np.array([network.bandwidth]),
        np.array([network.disk.streaming_rate]),
        np.array([network.disk.saturation_cc], dtype=np.int64),
        np.array([network.disk.contention]),
    )[0]
    if n_t == 0:
        assert pool == 0.0
    else:
        expected = min(
            network.bandwidth, network.disk.aggregate_rate(n_t)
        )
        np.testing.assert_allclose(pool, expected, rtol=1e-12)


def test_advance_channels_moves_fluid_and_finishes_files():
    busy = np.array([[True, True, False]])
    dead = np.array([[0.5, 0.0, 0.0]])
    rem = np.array([[1e6, 2e6, 0.0]])
    transferring = busy & (dead <= 1e-12)
    rates = np.array([[0.0, 1e6, 0.0]])
    busy2, dead2, rem2, moved, finished = kernels.advance_channels(
        _NP, np.array([True]), np.array([2.0]), busy, dead, transferring,
        rem, rates,
    )
    assert dead2[0, 0] == 0.0  # dead time burned
    assert moved[0, 1] == 2e6 and finished[0, 1]  # file completed
    assert not busy2[0, 1] and rem2[0, 1] == 0.0
    assert busy2[0, 0]  # still holds its file
