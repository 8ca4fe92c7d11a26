"""Controller-kernel equivalence: the array-native decision layer
(`repro.eval.fabric.controllers`) must reproduce the paper's scalar
control algorithms bit-for-bit, and its NumPy and JAX instantiations must
agree with each other.

`core.schedulers` / `core.params` are now *facades* over these kernels,
so the reference implementations here are standalone re-statements of the
original pure-Python logic (Algorithms 1-3 as PR 1 shipped them) — not
calls back into the facade, which would be circular.
"""
import math

import hypothesis.strategies as st
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core.types import MC_ROUND_ROBIN_ORDER, PROMC_DELTA, ChunkType
from repro.eval.fabric import controllers
from repro.eval.fabric.shim import jax_ops, numpy_ops

_NP = numpy_ops()
_CTYPES = list(ChunkType)[:4]
_RR_RANK = {ct: i for i, ct in enumerate(MC_ROUND_ROBIN_ORDER)}


# ------------------------------------------------------------------ #
# scalar references (the pre-facade implementations, verbatim logic)
# ------------------------------------------------------------------ #


def _ref_optimal_params(avg, bdp, buf, max_cc, num_files, max_pp):
    pp = max(0, min(int(math.ceil(bdp / avg)), max_pp))
    par = max(1, min(int(math.ceil(bdp / buf)), int(math.ceil(avg / buf))))
    cc = max(1, int(min(max(bdp / avg, 2.0), float(max_cc))))
    if num_files is not None and num_files > 0:
        pp = min(pp, max(0, num_files - 1))
        cc = min(cc, num_files)
    return pp, par, cc


def _ref_round_robin(ctypes, nonempty, max_cc):
    order = [
        i
        for ct in MC_ROUND_ROBIN_ORDER
        for i, (c, ne) in enumerate(zip(ctypes, nonempty))
        if c == ct and ne
    ]
    alloc = {i: 0 for i in order}
    if not order:
        return alloc
    k = 0
    for _ in range(max_cc):
        alloc[order[k % len(order)]] += 1
        k += 1
    return alloc


def _ref_weighted(ctypes, total_bytes, nonempty, max_cc):
    live = [i for i, ne in enumerate(nonempty) if ne]
    if not live:
        return {}
    weights = {i: PROMC_DELTA[ctypes[i]] * total_bytes[i] for i in live}
    total = sum(weights.values()) or 1.0
    shares = {i: weights[i] / total * max_cc for i in live}
    alloc = {i: int(math.floor(shares[i])) for i in live}
    for i in live:
        if alloc[i] == 0:
            alloc[i] = 1
    budget = max(max_cc, len(live))
    while sum(alloc.values()) > budget:
        i = max(alloc, key=lambda j: (alloc[j], -shares[j]))
        if alloc[i] <= 1:
            break
        alloc[i] -= 1
    frac = sorted(live, key=lambda i: shares[i] - math.floor(shares[i]), reverse=True)
    k = 0
    while sum(alloc.values()) < budget and frac:
        alloc[frac[k % len(frac)]] += 1
        k += 1
    return alloc


def _ref_eta(bytes_rem, thr, pred, done):
    if done or bytes_rem <= 0:
        return 0.0
    rate = thr if thr > 0 else pred
    if rate <= 0:
        return math.inf
    return bytes_rem / rate


def _ref_laggards(etas0, owners0, live, n_channels):
    """distribute_to_laggards' grant loop: dict of grants + emit order."""
    etas = {i: etas0[i] for i in live}
    owners = {i: owners0[i] for i in live}
    moves = {}
    if not live:
        return moves
    for _ in range(n_channels):
        dst = max(etas, key=lambda i: etas[i])
        moves[dst] = moves.get(dst, 0) + 1
        n = owners[dst] + moves[dst]
        if math.isfinite(etas[dst]) and n > 0:
            etas[dst] *= (n - 1) / n if n > 1 else 0.5
    return moves


class _RefPromcStreak:
    """The scalar ProMC on_tick state machine (pre-facade, verbatim)."""

    def __init__(self, ratio=2.0, patience=3):
        self.ratio, self.patience = ratio, patience
        self.streak, self.pair = 0, None

    def tick(self, etas, thrs, n_chs, live):
        lv = [i for i in live if n_chs[i] > 0]
        if len(lv) < 2:
            self.streak, self.pair = 0, None
            return None
        fast = min(lv, key=lambda i: etas[i])
        slow = max(lv, key=lambda i: etas[i])
        if not math.isfinite(etas[slow]) and thrs[slow] == 0:
            return None
        imb = (
            etas[slow] >= self.ratio * etas[fast]
            and fast != slow
            and n_chs[fast] > 1
        )
        pair = (fast, slow)
        if imb and pair == self.pair:
            self.streak += 1
        elif imb:
            self.streak, self.pair = 1, pair
        else:
            self.streak, self.pair = 0, None
            return None
        if self.streak >= self.patience:
            self.streak, self.pair = 0, None
            return (fast, slow)
        return None


# ------------------------------------------------------------------ #
# Algorithm 1 (tuning)
# ------------------------------------------------------------------ #


@settings(max_examples=200, deadline=None)
@given(
    avg=st.floats(min_value=1.0, max_value=1e12),
    bdp=st.floats(min_value=0.0, max_value=1e10),
    buf=st.floats(min_value=1024.0, max_value=1e9),
    max_cc=st.integers(min_value=1, max_value=64),
    nf=st.integers(min_value=0, max_value=500),
)
def test_optimal_params_matches_scalar_algorithm1(avg, bdp, buf, max_cc, nf):
    pp, par, cc = controllers.optimal_params(
        _NP, np.float64(avg), np.float64(bdp), np.float64(buf),
        np.float64(max_cc), np.int64(nf), 4096,
    )
    ref = _ref_optimal_params(avg, bdp, buf, max_cc, nf or None, 4096)
    assert (int(pp), int(par), int(cc)) == ref


def test_sc_chunk_order_is_stable_largest_first():
    ct = np.array([0, 3, 1, 3, 2], dtype=np.int64)
    order = controllers.sc_chunk_order(_NP, ct)
    assert list(order) == sorted(range(5), key=lambda i: -int(ct[i]))


# ------------------------------------------------------------------ #
# channel distributions (Alg. 2 round-robin, Alg. 3 weighted)
# ------------------------------------------------------------------ #


@settings(max_examples=200, deadline=None)
@given(
    spec=st.lists(
        st.tuples(
            st.sampled_from(_CTYPES),
            st.integers(min_value=0, max_value=1),  # nonempty
        ),
        min_size=1,
        max_size=4,
        unique_by=lambda t: t[0],
    ),
    max_cc=st.integers(min_value=1, max_value=32),
)
def test_round_robin_kernel_matches_scalar(spec, max_cc):
    ctypes = [ct for ct, _ in spec]
    nonempty = [bool(ne) for _, ne in spec]
    rank = np.array([_RR_RANK[ct] for ct in ctypes], dtype=np.int64)
    alloc = controllers.round_robin_alloc(
        _NP, rank, np.array(nonempty), max_cc
    )
    ref = _ref_round_robin(ctypes, nonempty, max_cc)
    for i in range(len(spec)):
        assert int(alloc[i]) == ref.get(i, 0)


@settings(max_examples=200, deadline=None)
@given(
    spec=st.lists(
        st.tuples(
            st.sampled_from(_CTYPES),
            st.integers(min_value=0, max_value=int(5e12)),  # bytes (0=empty)
        ),
        min_size=1,
        max_size=4,
        unique_by=lambda t: t[0],
    ),
    max_cc=st.integers(min_value=1, max_value=32),
)
def test_weighted_kernel_matches_scalar(spec, max_cc):
    ctypes = [ct for ct, _ in spec]
    sizes = [b for _, b in spec]
    nonempty = np.array([b > 0 for b in sizes])
    weights = np.array(
        [PROMC_DELTA[ct] * b for ct, b in spec], dtype=np.float64
    )
    alloc = controllers.weighted_alloc(
        _NP, weights, nonempty, max_cc, trim_iters=len(spec)
    )
    ref = _ref_weighted(ctypes, sizes, list(nonempty), max_cc)
    for i in range(len(spec)):
        assert int(alloc[i]) == ref.get(i, 0)


# ------------------------------------------------------------------ #
# laggard-ETA discounting (Sec. 3.3 re-allocation)
# ------------------------------------------------------------------ #


@settings(max_examples=200, deadline=None)
@given(
    chunks=st.lists(
        st.tuples(
            st.floats(min_value=0.0, max_value=1e12),  # bytes_remaining
            st.floats(min_value=0.0, max_value=1e9),   # throughput
            st.integers(min_value=0, max_value=8),     # n_channels
            st.integers(min_value=0, max_value=1),     # done
        ),
        min_size=1,
        max_size=5,
    ),
    src=st.integers(min_value=0, max_value=4),
    freed=st.integers(min_value=0, max_value=10),
)
def test_laggard_grants_match_scalar_discount_loop(chunks, src, freed):
    src = src % len(chunks)
    bytes_rem = np.array([c[0] for c in chunks])
    thr = np.array([c[1] for c in chunks])
    owners = np.array([c[2] for c in chunks], dtype=np.int64)
    done = np.array([bool(c[3]) for c in chunks])
    pred = np.zeros(len(chunks))
    etas = [
        _ref_eta(bytes_rem[i], thr[i], pred[i], done[i])
        for i in range(len(chunks))
    ]
    live_idx = [
        i for i in range(len(chunks))
        if not done[i] and i != src and bytes_rem[i] > 0
    ]
    ref = _ref_laggards(etas, owners, live_idx, freed)

    eta_arr = controllers.chunk_eta(_NP, bytes_rem, thr, pred, done)
    live = ~done & (np.arange(len(chunks)) != src) & (bytes_rem > 0)
    grants, first = controllers.laggard_grants(
        _NP, eta_arr, owners, live, np.int64(freed if live_idx else 0),
        max(freed, 1),
    )
    for i in range(len(chunks)):
        assert int(grants[i]) == ref.get(i, 0)
    # emission order == dict insertion order (first grant)
    order = sorted(np.flatnonzero(grants > 0), key=lambda d: first[d])
    assert [int(d) for d in order] == list(ref)


# ------------------------------------------------------------------ #
# ProMC streak state machine (Sec. 3.4) incl. reset semantics
# ------------------------------------------------------------------ #


@settings(max_examples=150, deadline=None)
@given(
    ticks=st.lists(
        st.lists(
            st.tuples(
                st.floats(min_value=0.0, max_value=1e10),  # bytes
                st.sampled_from([0.0, 10.0, 100.0, 1e6]),  # throughput
                st.integers(min_value=0, max_value=6),     # n_channels
            ),
            min_size=3,
            max_size=3,
        ),
        min_size=1,
        max_size=8,
    ),
    patience=st.integers(min_value=1, max_value=4),
)
def test_promc_streak_machine_matches_scalar(ticks, patience):
    """Drive the same tick sequence through the scalar reference machine
    and the kernel; streak state and fired moves must match at every
    step (including resets on balance and the patience threshold)."""
    ref = _RefPromcStreak(ratio=2.0, patience=patience)
    streak, pf, ps = np.int64(0), np.int64(-1), np.int64(-1)
    for views in ticks:
        bytes_rem = np.array([v[0] for v in views])
        thr = np.array([v[1] for v in views])
        n_ch = np.array([v[2] for v in views], dtype=np.int64)
        done = np.zeros(3, dtype=bool)
        pred = np.zeros(3)
        etas = [
            _ref_eta(bytes_rem[i], thr[i], pred[i], done[i])
            for i in range(3)
        ]
        live_idx = [i for i in range(3) if bytes_rem[i] > 0]
        ref_move = ref.tick(etas, thr, n_ch, live_idx)

        eta_arr = controllers.chunk_eta(_NP, bytes_rem, thr, pred, done)
        live = ~done & (bytes_rem > 0)
        streak, pf, ps, move, src, dst = controllers.promc_tick(
            _NP, eta_arr, thr, n_ch, live, streak, pf, ps, 2.0,
            np.int64(patience),
        )
        if ref_move is None:
            assert not bool(move)
        else:
            assert bool(move) and (int(src), int(dst)) == ref_move
        assert int(streak) == ref.streak
        ref_pair = ref.pair or (-1, -1)
        assert (int(pf), int(ps)) == ref_pair


def test_tick_ctrl_grows_full_resume_stack_even_without_a_move():
    """A row parked by the device's resume-stack-overflow guard replays
    its tick on the host; the replay must leave stack headroom even when
    no move fires, or the row would re-park at every subsequent tick
    (degrading the O(1)-syncs property to O(ticks))."""
    from repro.eval.fabric.driver import FabricSimulation
    from repro.eval.scenarios import Scenario, build_simulation

    sc = Scenario(
        network="stampede-comet", dataset="mixed", algorithm="promc"
    )
    drv = FabricSimulation([build_simulation(sc)], names=[sc.name])
    drv.start()
    p0 = drv.P
    drv.prepend_n[0, 0] = p0  # stack full
    rows = np.ones(1, dtype=bool)
    drv._tick_ctrl(rows)  # fresh streak: patience not reached => no move
    assert drv.P > p0
    assert (drv.prepend_n < drv.P).all()


def test_promc_completion_reset_is_wired_in_driver():
    """The batched driver drops the accumulated streak on any chunk
    completion, mirroring the scalar on_chunk_complete reset."""
    from repro.eval.fabric.driver import FabricSimulation
    from repro.eval.scenarios import Scenario, build_simulation

    sc = Scenario(
        network="stampede-comet", dataset="mixed", algorithm="promc"
    )
    drv = FabricSimulation([build_simulation(sc)], names=[sc.name])
    drv.start()
    drv.streak[0] = 2  # pretend accumulated imbalance evidence
    drv.pair_fast[0], drv.pair_slow[0] = 0, 1
    m = np.zeros((1, drv.K), dtype=bool)
    m[0, 0] = True
    drv._complete_ctrl(m)
    assert drv.streak[0] == 0
    assert drv.pair_fast[0] == -1 and drv.pair_slow[0] == -1


# ------------------------------------------------------------------ #
# SC cursor walk over empty size classes
# ------------------------------------------------------------------ #


@settings(max_examples=200, deadline=None)
@given(
    nfiles=st.lists(
        st.integers(min_value=0, max_value=5), min_size=1, max_size=5
    ),
    ctypes=st.lists(
        st.integers(min_value=0, max_value=3), min_size=5, max_size=5
    ),
    cursor=st.integers(min_value=0, max_value=5),
)
def test_sc_cursor_advance_skips_empty_chunks(nfiles, ctypes, cursor):
    K = len(nfiles)
    order = sorted(range(K), key=lambda i: -ctypes[i])
    # scalar: cursor += 1 then walk while the pointed chunk is empty
    ref = min(cursor, K) + 1
    while ref < K and nfiles[order[ref]] == 0:
        ref += 1
    out = controllers.sc_advance_cursor(
        _NP,
        np.array(True),
        np.int64(min(cursor, K)),
        np.array(order, dtype=np.int64),
        np.array(nfiles, dtype=np.int64),
        np.int64(K),
    )
    assert int(out) == ref


# ------------------------------------------------------------------ #
# NumPy / JAX instantiations agree
# ------------------------------------------------------------------ #


def test_controller_kernels_numpy_and_jax_agree():
    from repro.eval.fabric.jaxenv import x64

    rng = np.random.RandomState(7)
    S, K = 32, 4
    eta = np.where(
        rng.uniform(size=(S, K)) < 0.15, np.inf, rng.uniform(1.0, 1e4, (S, K))
    )
    thr = np.where(rng.uniform(size=(S, K)) < 0.3, 0.0, rng.uniform(1, 1e9, (S, K)))
    n_ch = rng.randint(0, 6, size=(S, K)).astype(np.int64)
    live = rng.uniform(size=(S, K)) < 0.8
    streak = rng.randint(0, 3, size=S).astype(np.int64)
    pf = rng.randint(-1, K, size=S).astype(np.int64)
    ps = rng.randint(-1, K, size=S).astype(np.int64)
    n_grants = rng.randint(0, 6, size=S).astype(np.int64)
    weights = rng.uniform(0, 1e12, size=(S, K))
    nonempty = rng.uniform(size=(S, K)) < 0.8
    max_cc = rng.randint(1, 17, size=S).astype(np.int64)

    ref_tick = controllers.promc_tick(
        _NP, eta, thr, n_ch, live, streak, pf, ps, 2.0, 3
    )
    ref_lag = controllers.laggard_grants(_NP, eta, n_ch, live, n_grants, 6)
    ref_w = controllers.weighted_alloc(_NP, weights, nonempty, max_cc, K)
    with x64():
        import jax.numpy as jnp

        J = jax_ops()
        out_tick = controllers.promc_tick(
            J, jnp.asarray(eta), jnp.asarray(thr), jnp.asarray(n_ch),
            jnp.asarray(live), jnp.asarray(streak), jnp.asarray(pf),
            jnp.asarray(ps), 2.0, 3,
        )
        out_lag = controllers.laggard_grants(
            J, jnp.asarray(eta), jnp.asarray(n_ch), jnp.asarray(live),
            jnp.asarray(n_grants), 6,
        )
        out_w = controllers.weighted_alloc(
            J, jnp.asarray(weights), jnp.asarray(nonempty),
            jnp.asarray(max_cc), K,
        )
    for r, o in zip(ref_tick, out_tick):
        np.testing.assert_array_equal(np.asarray(o), r)
    for r, o in zip(ref_lag, out_lag):
        np.testing.assert_array_equal(np.asarray(o), r)
    np.testing.assert_array_equal(np.asarray(out_w), ref_w)


def test_facade_and_kernels_share_decisions_end_to_end():
    """Spot check that the facade (event sim) and the fused JAX loop make
    identical move decisions on a ProMC scenario (n_moves match)."""
    from repro.eval.runner import run_matrix
    from repro.eval.scenarios import Scenario

    sc = Scenario(
        network="bluewaters-stampede", dataset="mixed", algorithm="promc"
    )
    ev = run_matrix([sc], backend="event")[0]
    jx = run_matrix([sc], backend="jax")[0]
    assert jx.n_moves == ev.n_moves
    assert jx.throughput == pytest.approx(ev.throughput, rel=1e-9)
