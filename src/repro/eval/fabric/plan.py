"""Columnar scenario ingest: ``Scenario`` specs -> driver-ready columns.

The legacy ingest path builds every matrix row as a chain of Python
objects — ``build_simulation`` -> partition -> per-chunk ``Chunk`` lists
-> a scheduler facade -> ``Simulation`` -> per-row packing loops in
``FabricSimulation.__init__``. At sweep scale (16k+ candidate rows) that
host-side build tax dominates the wall clock: the device loop is fast,
the per-row Python is not.

:func:`build_plan` replaces the chain with one vectorized pass:

  * rows are grouped by **transfer context** ``(network, dataset,
    dataset_seed, effective_chunks)``; each context's file set is built
    (via the shared LRU) and partitioned exactly once with array ops
    (``np.searchsorted`` over the Fig.-3 thresholds == ``classify``),
    and its per-chunk columns (queue offsets, totals, averages, SC
    order, round-robin ranks, ProMC weights) are shared by every row in
    the context — the tuner's candidate planes
    (``scenarios.expand_candidates``: contexts x 64 candidates) reuse
    one context build per 64 rows instead of re-deriving it 64x;
  * per-row parameters go through the *same* array kernels the scalar
    facades wrap — Algorithm 1 via
    :func:`repro.eval.fabric.controllers.tuning.optimal_params`, the
    initial channel allocations via
    :func:`repro.eval.fabric.controllers.alloc.round_robin_alloc` /
    :func:`weighted_alloc` — so the resulting state is bit-identical to
    the legacy path (``tests/test_plan_ingest.py`` pins every row array
    exactly);
  * file sizes land in ONE flat ``qsizes`` buffer shared by every row
    referencing the context (rows address it through per-row offsets),
    which also collapses the jax backend's queue-pad signature axis to
    a single rung per plan.

``FabricSimulation(None, plan=plan.take(rows))`` then materializes the
resident ``(S, K, C, P, B)`` state directly from the columns — no
``Simulation`` objects, no scalar packing loop. ``plan.take`` is plain
array slicing: thread-safe, so the executor can parallelize chunk prep.

Every built-in algorithm is supported (``sc``/``mc``/``promc``/
``globus``/``untuned``/``static`` — the whole ``Scenario`` vocabulary);
custom scheduler subclasses have no ``Scenario`` spelling and keep the
legacy object path.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import netmodel, testbeds
from repro.core.baselines import GLOBUS_PRESETS, MB
from repro.core.chunking import _CLASS_LABELS, size_thresholds
from repro.core.params import MAX_PIPELINING
from repro.core.types import (
    MC_ROUND_ROBIN_ORDER,
    PROMC_DELTA,
    ChunkType,
    NetworkSpec,
)

from .bucketing import bucket
from .controllers.alloc import round_robin_alloc, weighted_alloc
from .controllers.tuning import optimal_params, sc_chunk_order
from .shim import numpy_ops

#: algorithms the columnar path can ingest (== the Scenario vocabulary)
PLAN_ALGORITHMS = frozenset(
    {"sc", "mc", "promc", "globus", "untuned", "static"}
)

#: floor on the plan path's channel-capacity hint bucket: the driver pads
#: every plan chunk's worst-case channel axis up to this, merging the
#: cc<=4 and cc<=8 rows into ONE compiled (C=8) program family — the pad
#: is a few never-selected channel columns, while each extra C value is a
#: full trace+compile (or cache read) per rows-rung per process. Batches
#: holding profiled rows take the same floor: a chunk whose rows need
#: more channels pads to their capacity bucket (16, 32, ...), as a static
#: chunk does
PLAN_C_FLOOR = 8

#: compaction floor for all-static candidate-plane batches (every row
#: ``kind <= _KIND_STATIC``, no timelines): plane rows of one context
#: share parameters up to the candidate axis, so whole chunks drain
#: together and the narrow straggler rungs the heterogeneous grid needs
#: (``bucketing.COMPACT_FLOOR`` = 64) never earn their keep — each rung
#: below 256 is one more device re-entry plus a download sync per chunk.
#: The floor is a *static* argument of the fused device loop, so plane
#: and grid batches occupy disjoint compiled programs by construction
PLAN_COMPACT_FLOOR = 256

#: shape-hint value that sorts rows on profiled (time-varying) networks
#: after all static-bandwidth rows, so the B=16 profile pad stays
#: confined to the trailing chunks instead of widening every chunk the
#: cost sort scatters a profiled row into
_PROFILED_HINT = 1 << 16

#: driver kind codes, mirrored from the driver to avoid an import cycle
#: (`driver.py` imports this module's runtime refs); pinned by a test.
_KIND_TRIVIAL, _KIND_STATIC, _KIND_SC, _KIND_MC, _KIND_PROMC = 0, 1, 2, 3, 4

_KIND_OF = {
    "sc": _KIND_SC,
    "mc": _KIND_MC,
    "promc": _KIND_PROMC,
    "static": _KIND_STATIC,
    "globus": _KIND_TRIVIAL,
    "untuned": _KIND_TRIVIAL,
}

#: (trivial_tick, trivial_complete) per kind: which controller callbacks
#: are the base no-op (SC/MC override completion, ProMC also ticks)
_TRIVIAL_OF = {
    _KIND_TRIVIAL: (True, True),
    _KIND_STATIC: (True, True),
    _KIND_SC: (True, False),
    _KIND_MC: (True, False),
    _KIND_PROMC: (False, False),
}

#: round-robin service rank by int ChunkType (Alg. 2 order H,S,L,M,A)
_RR_RANK_BY_CT = np.zeros(len(ChunkType), dtype=np.int64)
for _i, _ct in enumerate(MC_ROUND_ROBIN_ORDER):
    _RR_RANK_BY_CT[int(_ct)] = _i

#: ProMC delta weight by int ChunkType (Alg. 3)
_DELTA_BY_CT = np.array(
    [PROMC_DELTA[ChunkType(_i)] for _i in range(len(ChunkType))],
    dtype=np.int64,
)

#: Globus Online class presets as parallel (pp, p, cc) columns
_GLOBUS_CLASSES = ("small", "medium", "large")
_GLOBUS_PP = np.array(
    [GLOBUS_PRESETS[c].pipelining for c in _GLOBUS_CLASSES], dtype=np.int64
)
_GLOBUS_P = np.array(
    [GLOBUS_PRESETS[c].parallelism for c in _GLOBUS_CLASSES], dtype=np.int64
)
_GLOBUS_CC = np.array(
    [GLOBUS_PRESETS[c].concurrency for c in _GLOBUS_CLASSES], dtype=np.int64
)

#: pad-slot chunk type: large-negative so the SC order kernel sorts pads
#: strictly after every real chunk (its key grows with ``hi - ctype``)
_PAD_CTYPE = -(10**6)

#: pad-slot round-robin rank: sorts pads after every real chunk in the
#: MC service order (real ranks are < len(ChunkType))
_PAD_RANK = 10**6


class _NameRef:
    """Tiny shared stand-in for scheduler/chunk objects: the driver's
    result assembly and error paths only ever read ``.name``."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"_NameRef({self.name!r})"


@dataclasses.dataclass
class _Context:
    """One transfer context: a (network, fileset, partitioning) triple
    whose chunk columns every row in the context shares."""

    net_idx: int
    n_chunks: int
    chunk_refs: tuple  # shared _NameRef per chunk
    total_bytes: int  # exact int byte total over all files
    n_files: int
    globus_avg: float  # unclamped avg file size (Globus preset class)
    qoff: np.ndarray  # (n_chunks,) int64 offsets into the shared buffer
    qlen: np.ndarray  # (n_chunks,) int64
    chunk_total: np.ndarray  # (n_chunks,) int64
    ctype: np.ndarray  # (n_chunks,) int64


@dataclasses.dataclass
class ScenarioPlan:
    """Columnar scenario table: everything ``FabricSimulation`` needs to
    materialize its resident arrays, pre-padded to a shared chunk width
    ``K``. Row order == input scenario order. ``take(rows)`` slices a
    sub-plan (shared ``networks``/``qsizes``, copied row axes) — plain
    array work, safe to call from several executor prep threads."""

    K: int
    networks: List[NetworkSpec]
    qsizes: np.ndarray  # flat f64 file-size buffer, shared by all rows
    names: List[str]
    sched_refs: List[_NameRef]
    chunk_refs: List[tuple]
    #: per-row Optional[SharedFabric] coupling spec (None == uncoupled);
    #: value-like frozen dataclasses shared with the source scenarios
    fabrics: List
    # (S,) row columns
    net_idx: np.ndarray
    kind: np.ndarray
    trivial_tick: np.ndarray
    trivial_complete: np.ndarray
    tick_period: np.ndarray
    record_timeline: np.ndarray
    max_cc: np.ndarray
    eff_cc: np.ndarray
    total_bytes: np.ndarray  # f64 (exact int values)
    n_files: np.ndarray
    n_chunks: np.ndarray
    cap_need: np.ndarray
    # (S, K) row-chunk columns
    qoff: np.ndarray
    qlen: np.ndarray
    queue_bytes: np.ndarray
    avg_fs_k: np.ndarray
    conc: np.ndarray
    par: np.ndarray
    cap_k: np.ndarray
    fsdt: np.ndarray
    sc_order: np.ndarray
    open_n: np.ndarray
    visit_rank: np.ndarray

    @property
    def n_rows(self) -> int:
        return len(self.names)

    def __len__(self) -> int:
        return self.n_rows

    def take(self, rows: Sequence[int]) -> "ScenarioPlan":
        idx = np.asarray(list(rows), dtype=np.int64)
        pick = lambda seq: [seq[int(i)] for i in idx]  # noqa: E731
        return ScenarioPlan(
            K=self.K,
            networks=self.networks,
            qsizes=self.qsizes,
            names=pick(self.names),
            sched_refs=pick(self.sched_refs),
            chunk_refs=pick(self.chunk_refs),
            fabrics=pick(self.fabrics),
            **{
                f.name: getattr(self, f.name)[idx]
                for f in dataclasses.fields(self)
                if f.name
                not in (
                    "K", "networks", "qsizes", "names", "sched_refs",
                    "chunk_refs", "fabrics",
                )
            },
        )

    # ------------------------------------------------------------------ #
    # chunking keys for the matrix runner (vectorized twins of
    # runner._cost_proxy / runner.shape_hint — same values, no file I/O)
    # ------------------------------------------------------------------ #

    def cost_proxy(self) -> np.ndarray:
        """Vectorized :func:`repro.eval.runner.cost_estimate` over the
        rows (bit-identical: same FP operation order on the same
        doubles), so plan-ordered chunks match the legacy ordering."""
        nets = self.networks
        bw = np.array([n.bandwidth for n in nets], dtype=np.float64)
        sr = np.array(
            [n.disk.streaming_rate for n in nets], dtype=np.float64
        )
        crc4 = np.array(
            [netmodel.channel_rate_cap(n, 4) for n in nets],
            dtype=np.float64,
        )
        ni = self.net_idx
        est = np.minimum(
            np.minimum(bw[ni], sr[ni]),
            np.maximum(1, self.eff_cc) * crc4[ni],
        )
        duration = self.total_bytes / np.maximum(est, 1.0)
        return duration / np.maximum(self.tick_period, 1e-9) + self.n_files

    def shape_hints(self) -> List[int]:
        """Chunk-grouping keys for shape-homogeneous batches.

        Two refinements over the legacy ``runner.shape_hint`` (which
        buckets the worst-case channel axis alone):

        * the capacity bucket is floored at :data:`PLAN_C_FLOOR` — the
          driver pads every plan chunk's channel axis to at least that,
          so merging the tiny-cc buckets into one group costs nothing
          and halves the distinct compiled ``C`` values;
        * rows on profiled (time-varying) networks form ONE trailing
          group regardless of capacity: one scattered profiled row
          widens its whole chunk's bandwidth-profile axis to the B=16
          pad, so letting the cost sort deal them everywhere used to
          mint a ``B=16`` twin of nearly every ``(rows, C)`` program.
          The driver still pads a profiled chunk's channel axis to what
          its rows need, floored at :data:`PLAN_C_FLOOR`, so profiled
          rows of two capacity buckets in one process compile a
          ``(C, B=16)`` program of each.
        """
        plens = np.array(
            [
                len(getattr(n, "bandwidth_profile", None) or ((0.0, 1.0),))
                for n in self.networks
            ],
            dtype=np.int64,
        )[self.net_idx]
        return [
            _PROFILED_HINT if p > 1 else int(bucket(int(c), PLAN_C_FLOOR))
            for c, p in zip(self.eff_cc, plens)
        ]


# ---------------------------------------------------------------------- #
# plan construction
# ---------------------------------------------------------------------- #


def plan_supported(scenarios: Sequence) -> bool:
    """True when every scenario's algorithm has a columnar ingest."""
    return all(
        sc.algorithm.lower() in PLAN_ALGORITHMS for sc in scenarios
    )


def _effective_chunks(algorithm: str, num_chunks: int) -> int:
    # static/globus/untuned run one merged ALL chunk regardless of the
    # scenario's num_chunks (globus/untuned partition with num_chunks=1
    # and the one-chunk schedulers re-merge; static never partitions)
    return 1 if algorithm in ("static", "globus", "untuned") else num_chunks


def _build_context(
    sc, net_idx: int, network: NetworkSpec, eff_chunks: int,
    size_chunks: List[np.ndarray],
    qsizes_len: int,
) -> Tuple[_Context, int]:
    """Partition one context's file set with array ops and append its
    sizes (chunk-major, file order preserved) to the flat buffer."""
    from ..scenarios import _build_files_cached

    files = _build_files_cached(sc.dataset, sc.dataset_seed)
    fsizes = np.array([f.size for f in files], dtype=np.int64)
    thresholds = np.asarray(
        size_thresholds(network.bandwidth, eff_chunks), dtype=np.float64
    )
    # classify(size, thr) == first i with size <= thr[i]: exactly
    # searchsorted-left over the ascending thresholds
    cls_idx = np.searchsorted(thresholds, fsizes, side="left")
    labels = _CLASS_LABELS[eff_chunks]
    qoff: List[int] = []
    qlen: List[int] = []
    totals: List[int] = []
    ctypes: List[int] = []
    refs: List[_NameRef] = []
    off = qsizes_len
    for ci, label in enumerate(labels):
        members = np.flatnonzero(cls_idx == ci)
        if members.size == 0:
            continue  # empty size classes are dropped (Sec. 4.1)
        csizes = fsizes[members]
        size_chunks.append(csizes.astype(np.float64))
        qoff.append(off)
        qlen.append(int(members.size))
        totals.append(int(csizes.sum()))
        ctypes.append(int(label))
        refs.append(_chunk_ref(label))
        off += int(members.size)
    total_all = int(fsizes.sum())
    ctx = _Context(
        net_idx=net_idx,
        n_chunks=len(qlen),
        chunk_refs=tuple(refs),
        total_bytes=total_all,
        n_files=len(files),
        globus_avg=total_all / len(files) if files else 1.0,
        qoff=np.array(qoff, dtype=np.int64),
        qlen=np.array(qlen, dtype=np.int64),
        chunk_total=np.array(totals, dtype=np.int64),
        ctype=np.array(ctypes, dtype=np.int64),
    )
    return ctx, off


#: shared ChunkType name refs (every context's "SMALL" is the same object)
_CHUNK_REFS: Dict[int, _NameRef] = {}


def _chunk_ref(label: ChunkType) -> _NameRef:
    ref = _CHUNK_REFS.get(int(label))
    if ref is None:
        ref = _CHUNK_REFS[int(label)] = _NameRef(ChunkType(label).name)
    return ref


_SCHED_NAME_OF = {
    _KIND_SC: "SC",
    _KIND_MC: "MC",
    _KIND_PROMC: "ProMC",
}


def build_plan(scenarios: Sequence) -> ScenarioPlan:
    """Vectorized ingest of ``scenarios`` into a :class:`ScenarioPlan`.

    One context build per unique ``(network, dataset, dataset_seed,
    effective_chunks)``; everything per-row is (S,)/(S,K) array math.
    """
    S = len(scenarios)
    ops = numpy_ops()

    networks: List[NetworkSpec] = []
    net_of: Dict[str, int] = {}
    contexts: List[_Context] = []
    ctx_of: Dict[tuple, int] = {}
    size_chunks: List[np.ndarray] = []
    qsizes_len = 0
    sched_ref_of: Dict[str, _NameRef] = {}
    seed_cache: Dict[Tuple[str, int], int] = {}

    ctx_idx = np.zeros(S, dtype=np.int64)
    net_idx = np.zeros(S, dtype=np.int64)
    kind = np.zeros(S, dtype=np.int64)
    max_cc = np.zeros(S, dtype=np.int64)
    eff_cc = np.zeros(S, dtype=np.int64)
    tick_period = np.zeros(S, dtype=np.float64)
    record_timeline = np.zeros(S, dtype=bool)
    sp_pp = np.zeros(S, dtype=np.int64)
    sp_p = np.ones(S, dtype=np.int64)
    sp_cc = np.ones(S, dtype=np.int64)
    names: List[str] = [""] * S
    sched_refs: List[Optional[_NameRef]] = [None] * S
    chunk_refs: List[tuple] = [()] * S
    fabrics: List = [None] * S

    for i, sc in enumerate(scenarios):
        alg = sc.algorithm.lower()
        if alg not in PLAN_ALGORITHMS:
            raise ValueError(
                f"no columnar ingest for algorithm {sc.algorithm!r}; "
                "use the legacy object path"
            )
        nkey = sc.network
        n = net_of.get(nkey)
        if n is None:
            n = net_of[nkey] = len(networks)
            networks.append(testbeds.TESTBEDS[nkey])
        skey = (sc.dataset, sc.seed)
        dseed = seed_cache.get(skey)
        if dseed is None:
            dseed = seed_cache[skey] = sc.dataset_seed
        eff_k = _effective_chunks(alg, sc.num_chunks)
        ckey = (nkey, sc.dataset, dseed, eff_k)
        c = ctx_of.get(ckey)
        if c is None:
            ctx, qsizes_len = _build_context(
                sc, n, networks[n], eff_k, size_chunks, qsizes_len
            )
            c = ctx_of[ckey] = len(contexts)
            contexts.append(ctx)
        ctx_idx[i] = c
        net_idx[i] = n
        kd = _KIND_OF[alg]
        kind[i] = kd
        max_cc[i] = sc.max_cc
        tick_period[i] = sc.tick_period
        record_timeline[i] = sc.record_timeline
        names[i] = sc.name
        chunk_refs[i] = contexts[c].chunk_refs
        fabrics[i] = getattr(sc, "shared_fabric", None)
        if alg == "static":
            pp, p, cc = sc.static_params
            sp_pp[i], sp_p[i], sp_cc[i] = pp, p, cc
            eff_cc[i] = cc
            sname = f"Static(pp={pp},p={p},cc={cc})"
        elif alg == "untuned":
            sp_pp[i], sp_p[i], sp_cc[i] = 0, 1, 1
            eff_cc[i] = sc.max_cc
            sname = "Untuned"
        elif alg == "globus":
            avg = contexts[c].globus_avg
            gi = 0 if avg < 50 * MB else (1 if avg <= 250 * MB else 2)
            sp_pp[i] = _GLOBUS_PP[gi]
            sp_p[i] = _GLOBUS_P[gi]
            sp_cc[i] = _GLOBUS_CC[gi]
            eff_cc[i] = sc.max_cc
            sname = "GlobusOnline"
        else:
            eff_cc[i] = sc.max_cc
            sname = _SCHED_NAME_OF[kd]
        ref = sched_ref_of.get(sname)
        if ref is None:
            ref = sched_ref_of[sname] = _NameRef(sname)
        sched_refs[i] = ref

    qsizes = (
        np.concatenate(size_chunks)
        if size_chunks
        else np.zeros(0, dtype=np.float64)
    )

    # ---- context tables, padded to the shared chunk width K ---------- #
    n_ctx = len(contexts)
    K = bucket(max((c.n_chunks for c in contexts), default=1))
    c_qoff = np.zeros((n_ctx, K), dtype=np.int64)
    c_qlen = np.zeros((n_ctx, K), dtype=np.int64)
    c_total = np.zeros((n_ctx, K), dtype=np.int64)
    c_ctype = np.full((n_ctx, K), _PAD_CTYPE, dtype=np.int64)
    c_nk = np.zeros(n_ctx, dtype=np.int64)
    for j, ctx in enumerate(contexts):
        nk = ctx.n_chunks
        c_qoff[j, :nk] = ctx.qoff
        c_qlen[j, :nk] = ctx.qlen
        c_total[j, :nk] = ctx.chunk_total
        c_ctype[j, :nk] = ctx.ctype
        c_nk[j] = nk
    c_nonempty = np.arange(K)[None, :] < c_nk[:, None]
    # clamped per-chunk average file size (pads hold the neutral 1.0)
    c_avg = np.ones((n_ctx, K), dtype=np.float64)
    real = c_nonempty
    c_avg[real] = np.maximum(
        c_total[real].astype(np.float64) / c_qlen[real].astype(np.float64),
        1.0,
    )
    # SC transfer order over padded ctypes (pads sort last), tail zeroed
    # exactly as the legacy packing loop leaves it
    c_order = sc_chunk_order(ops, c_ctype)
    c_order = np.where(c_nonempty, c_order, 0)
    # MC round-robin rank / ProMC delta weight per chunk
    safe_ct = np.where(c_nonempty, c_ctype, 0)
    c_rank = np.where(c_nonempty, _RR_RANK_BY_CT[safe_ct], _PAD_RANK)
    c_weight = np.where(
        c_nonempty,
        _DELTA_BY_CT[safe_ct].astype(np.float64)
        * c_total.astype(np.float64),
        0.0,
    )

    # ---- gather context columns to rows ------------------------------ #
    qoff = c_qoff[ctx_idx]
    qlen = c_qlen[ctx_idx]
    queue_bytes = c_total[ctx_idx].astype(np.float64)
    avg_fs_k = c_avg[ctx_idx]
    sc_order = c_order[ctx_idx]
    nonempty = c_nonempty[ctx_idx]
    n_chunks = c_nk[ctx_idx]
    rank = c_rank[ctx_idx]
    weight = c_weight[ctx_idx]
    total_bytes = np.array(
        [float(contexts[c].total_bytes) for c in ctx_idx], dtype=np.float64
    )
    n_files = np.array(
        [contexts[c].n_files for c in ctx_idx], dtype=np.int64
    )

    # ---- per-row network scalars ------------------------------------- #
    bdp = np.array([n.bdp for n in networks], dtype=np.float64)[net_idx]
    buf = np.array(
        [n.buffer_size for n in networks], dtype=np.float64
    )[net_idx]
    crtt = np.array(
        [
            n.control_rtt if n.control_rtt is not None else n.rtt
            for n in networks
        ],
        dtype=np.float64,
    )[net_idx]
    unhidden = np.array(
        [n.unhidden_overhead for n in networks], dtype=np.float64
    )[net_idx]
    pfo = np.array(
        [n.disk.per_file_overhead for n in networks], dtype=np.float64
    )[net_idx]
    # per-stream window rate and disk lane, computed per network with the
    # exact scalar expressions (types.NetworkSpec.stream_rate_cap /
    # DiskSpec.channel_lane) so the vectorized caps match bit for bit
    per_stream = np.array(
        [
            n.window_efficiency * n.buffer_size / max(n.rtt, 1e-9)
            for n in networks
        ],
        dtype=np.float64,
    )[net_idx]
    lane = np.array(
        [n.disk.channel_lane for n in networks], dtype=np.float64
    )[net_idx]
    msc = np.array(
        [n.max_streams_per_channel for n in networks], dtype=np.int64
    )[net_idx]
    sco = np.array(
        [n.stream_cpu_overhead for n in networks], dtype=np.float64
    )[net_idx]
    bw = np.array(
        [n.bandwidth for n in networks], dtype=np.float64
    )[net_idx]

    # ---- Algorithm 1 over every (row, chunk) at once ----------------- #
    pp, par, conc = optimal_params(
        ops,
        avg_fs_k,
        bdp[:, None],
        buf[:, None],
        max_cc[:, None].astype(np.float64),
        qlen,
        MAX_PIPELINING,
    )
    # static-parameter family (static candidates, Globus presets,
    # untuned defaults): one merged chunk driven by the row triple
    static_like = kind <= _KIND_STATIC
    pp = np.where(static_like[:, None], sp_pp[:, None], pp)
    par = np.where(static_like[:, None], sp_p[:, None], par)
    conc = np.where(static_like[:, None], sp_cc[:, None], conc)
    # pad slots: born-done chunks keep the legacy constructor's zeros
    pp = np.where(nonempty, pp, 0)
    par = np.where(nonempty, par, 1)
    conc = np.where(nonempty, conc, 0)

    # serial per-file dead time (netmodel.file_start_dead_time, same
    # left-to-right FP order: gap + unhidden + per-file disk overhead)
    gap = crtt[:, None] / (1.0 + pp.astype(np.float64))
    fsdt = np.where(
        nonempty, gap + unhidden[:, None] + pfo[:, None], 0.0
    )
    # channel rate cap (netmodel.channel_rate_cap == min(stream cap,
    # disk lane); stream cap per types.NetworkSpec.stream_rate_cap)
    p_eff = np.maximum(1, np.minimum(par, msc[:, None]))
    stream_eff = 1.0 / (1.0 + sco[:, None] * (p_eff - 1))
    stream_cap = np.minimum(
        p_eff * per_stream[:, None] * stream_eff, bw[:, None]
    )
    cap_k = np.where(nonempty, np.minimum(stream_cap, lane[:, None]), 0.0)

    # ---- initial channel allocation per controller kind -------------- #
    arangeK = np.arange(K)[None, :]
    # SC: one Open at the first chunk of the transfer order
    first = sc_order[:, :1]
    open_sc = np.where(
        arangeK == first, np.take_along_axis(conc, first, axis=1), 0
    )
    # MC: Alg.-2 round-robin split of maxCC over the service order
    open_mc = round_robin_alloc(ops, rank, nonempty, max_cc)
    # MC opens chunk by chunk in service order (rank, index): that order
    # is the channel-column layout contract (kernels.compact_channels)
    key = rank * K + arangeK
    rank_mc = np.sum(key[:, None, :] < key[:, :, None], axis=2)
    # ProMC: Alg.-3 delta-weighted split, opened in ascending chunk index
    open_promc = weighted_alloc(ops, weight, nonempty, max_cc, trim_iters=K)
    # static family: Open(chunk=0, n=cc)
    open_static = np.where(arangeK == 0, conc, 0)

    is_sc = kind == _KIND_SC
    is_mc = kind == _KIND_MC
    is_promc = kind == _KIND_PROMC
    # the legacy constructor populates sc_order only for SC rows (other
    # kinds never read it); keep the zeros for bit-identity
    sc_order = np.where(is_sc[:, None], sc_order, 0)
    open_n = np.where(
        is_sc[:, None],
        open_sc,
        np.where(
            is_mc[:, None],
            open_mc,
            np.where(is_promc[:, None], open_promc, open_static),
        ),
    ).astype(np.int64)
    visit_rank = np.where(
        is_mc[:, None], rank_mc, np.broadcast_to(arangeK, (S, K))
    ).astype(np.int64)

    # ---- closed-form capacity bound (driver._worst_case_channels) ---- #
    conc_real = np.where(nonempty, conc, 0)
    cap_sc = np.maximum(1, conc_real.max(axis=1, initial=0))
    cap_mc = np.maximum(np.maximum(1, max_cc), n_chunks)
    cap_static = np.maximum(1, conc_real.sum(axis=1))
    # coupled SC rows advance on the group horizon, so cursor-advancing
    # completion ties can co-schedule every wave: widen to the full
    # concurrency sum (mirrors driver._worst_case_channels exactly)
    coupled_row = np.array([f is not None for f in fabrics], dtype=bool)
    cap_sc = np.where(coupled_row, cap_static, cap_sc)
    cap_need = np.where(
        is_sc, cap_sc, np.where(is_mc | is_promc, cap_mc, cap_static)
    ).astype(np.int64)

    trivial = np.array([_TRIVIAL_OF[int(k)] for k in kind], dtype=bool)

    return ScenarioPlan(
        K=K,
        networks=networks,
        qsizes=qsizes,
        names=names,
        sched_refs=sched_refs,  # type: ignore[arg-type]
        chunk_refs=chunk_refs,
        fabrics=fabrics,
        net_idx=net_idx,
        kind=kind,
        trivial_tick=trivial[:, 0] if S else np.zeros(0, dtype=bool),
        trivial_complete=trivial[:, 1] if S else np.zeros(0, dtype=bool),
        tick_period=tick_period,
        record_timeline=record_timeline,
        max_cc=max_cc,
        eff_cc=eff_cc,
        total_bytes=total_bytes,
        n_files=n_files,
        n_chunks=n_chunks,
        cap_need=cap_need,
        qoff=qoff,
        qlen=qlen,
        queue_bytes=queue_bytes,
        avg_fs_k=avg_fs_k,
        conc=conc,
        par=par,
        cap_k=cap_k,
        fsdt=fsdt,
        sc_order=sc_order,
        open_n=open_n,
        visit_rank=visit_rank,
    )
