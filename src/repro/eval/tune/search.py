"""Adaptive searchers: cheaper-than-oracle tuning over the fused sweep.

Two budgeted strategies over the same candidate machinery the oracle
uses (:mod:`repro.eval.tune.oracle`), both composing *multiple* batched
sweeps in a host loop — the device evaluates a whole (context x
candidate) plane per rung / iteration, the host only shrinks and
re-batches the candidate axis between sweeps:

* :func:`successive_halving` — evaluate every candidate on a small
  deterministic *subsample* of the dataset, keep the top ``1/eta`` per
  context, re-evaluate the survivors on an ``eta``-times larger
  subsample, and so on until the final rung runs the full dataset. With
  the default schedule (64 candidates, eta=4: 64 @ 1/16 -> 16 @ 1/4 ->
  4 @ full) the *full-fidelity-equivalent* cost is 12 evaluations per
  context — under 1/4 of the oracle's 64 — while the final-rung argmax
  is exact (full dataset) for every survivor.

* :func:`hill_climb` — coordinate descent on the log-spaced axes of
  :class:`repro.eval.tune.space.ParamSpace`: start at the remembered
  per-testbed winner (:mod:`repro.eval.tune.history`) or the
  Algorithm-1 point, evaluate the <= 6 one-step axis neighbors of every
  context's current setting in one batched sweep, move each context to
  its best neighbor, repeat until no context improves. The knob
  responses are unimodal in the model (saturating rate curves, one
  contention sweet spot), which is what makes local search competitive.

Subsampled rungs measure throughput on a deterministic *sketch* of the
fileset — equal-count buckets over the size-sorted files, one synthetic
file per bucket at the bucket's mean size (see ``_Context.subset``),
identical for every candidate within a rung — so rung comparisons are
fair, the dataset's byte shares survive even 1/16-sized samples, and
the cost of a fractional evaluation is proportional to its fraction
(event count scales with file count). ``equivalent_evals`` accounts
rungs at the fraction actually simulated — the budget the acceptance
bar compares against the oracle's full-fidelity evaluation count.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.core import testbeds
from repro.core.runner import build_scheduler
from repro.core.simulator import Simulation
from repro.core.types import FileSpec

from ..runner import DEFAULT_CHUNK_SIZE, cost_estimate, run_built, shape_hint
from ..scenarios import Scenario, build_files
from .oracle import (
    ContextKey,
    ContextTable,
    TuneEntry,
    TuneResult,
    candidate_lists,
    context_key,
    group_contexts,
)
from .space import ParamSpace, algorithm1_params, scenario_space

Triple = Tuple[int, int, int]


def _builder(network, files, triple: Triple, max_cc: int, tick: float):
    """Zero-arg builder of one fresh static-candidate Simulation (via
    the canonical ``build_scheduler("static")`` path, so rung rows carry
    exactly the semantics of the oracle's matrix rows)."""

    def build() -> Simulation:
        sched = build_scheduler(
            "static", files, network, max_cc=max_cc, static_params=triple
        )
        return Simulation(
            sched.chunks, sched.network, sched, tick_period=tick
        )

    return build


class _Context:
    """Host-side search state for one deduplicated transfer context."""

    def __init__(self, key: ContextKey, rep: Scenario):
        self.key = key
        self.rep = rep
        self.network = testbeds.TESTBEDS[rep.network]
        self.files = build_files(rep)
        #: file indices ordered by size: subsets are *size-stratified*
        #: (files at evenly spaced size quantiles), so a rung's sample
        #: keeps the dataset's small/huge mix — a uniform random draw
        #: at 1/16 routinely misses the heavy tail and misranks the
        #: concurrency/parallelism axes
        self.by_size = sorted(
            range(len(self.files)),
            key=lambda i: (self.files[i].size, i),
        )
        #: fraction -> sketch; every candidate of a rung (and the rung's
        #: cost accounting) shares one deterministic sketch, so build it
        #: once instead of once per candidate row
        self._sketch: Dict[float, list] = {}

    def subset(self, fraction: float) -> list:
        """Deterministic ~``fraction``-sized *sketch* of the fileset (the
        whole set at 1.0).

        The size-sorted files are split into ``n = ceil(fraction * m)``
        equal-count buckets and each bucket becomes one synthetic file at
        the bucket's mean size. Equal-count buckets mean every sketch
        file downweights its bucket's bytes by the same factor, so the
        byte *shares* of the size distribution survive — unlike picking
        actual files at size quantiles, which keeps the raw multi-GB tail
        file while dropping most of the bytes around it, leaving a
        one-channel critical path that makes every concurrency setting
        rank equal (the tail file dominates the makespan). Identical for
        every candidate within a rung, so rung comparisons stay fair.
        """
        if fraction >= 1.0 or not self.files:
            return self.files
        cached = self._sketch.get(fraction)
        if cached is not None:
            return cached
        m = len(self.files)
        n = max(1, int(math.ceil(fraction * m)))
        if n >= m:
            return self.files
        out = []
        for b in range(n):
            lo = round(b * m / n)
            hi = max(round((b + 1) * m / n), lo + 1)
            run = [self.files[i].size for i in self.by_size[lo:hi]]
            out.append(
                FileSpec(
                    name=f"sketch{b}",
                    size=int(round(sum(run) / len(run))),
                )
            )
        self._sketch[fraction] = out
        return out


def _evaluate(
    rows: Sequence[Tuple[_Context, Triple, float]],
    backend: str,
    chunk_size: Optional[int],
) -> List[float]:
    """One batched sweep over (context, candidate, fraction) rows ->
    throughputs, input order. Rows carry a capacity shape hint (a static
    candidate holds exactly its own ``cc`` channels) so the jax backend
    can group them into capacity-homogeneous — hence compile-shape-
    homogeneous — chunks."""
    builders, names, costs, hints = [], [], [], []
    for ctx, triple, fraction in rows:
        files = ctx.subset(fraction)
        builders.append(
            _builder(
                ctx.network, files, triple, ctx.rep.max_cc,
                ctx.rep.tick_period,
            )
        )
        names.append(
            "{}|pp{}.p{}.cc{}|f{:g}".format(ctx.rep.name, *triple, fraction)
        )
        costs.append(
            cost_estimate(ctx.network, files, triple[2], ctx.rep.tick_period)
        )
        hints.append(shape_hint(triple[2]))
    results = run_built(
        builders, names, costs, backend=backend, chunk_size=chunk_size,
        hints=hints,
    )
    return [r.throughput for r in results]


def _entries(
    scenarios: Sequence[Scenario],
    tables: Dict[ContextKey, ContextTable],
    n_cands: Dict[ContextKey, int],
) -> List[TuneEntry]:
    return [
        TuneEntry(
            scenario=sc.name,
            context=context_key(sc),
            best_params=tables[context_key(sc)].best_params,
            best_throughput=tables[context_key(sc)].best_throughput,
            n_candidates=n_cands[context_key(sc)],
        )
        for sc in scenarios
    ]


# --------------------------------------------------------------------------
# successive halving
# --------------------------------------------------------------------------


def _diverse_keep(
    by_idx: Dict[int, float],
    cands: Sequence[Triple],
    keep: int,
) -> List[int]:
    """Top-``keep`` selection that never collapses the concurrency axis.

    Subsampled rungs rank pipelining / parallelism reliably (their
    effects are per-file and local) but are systematically biased on
    concurrency: a sketch dataset shifts where the disk-saturation sweet
    spot appears, and a plain top-k then keeps ONE cc value into the
    final rung, deciding the most fidelity-sensitive knob at the lowest
    fidelity. So the keep rule is stratified: first the best candidate
    of each distinct cc value (cc groups ordered by their group best),
    then the remaining slots by plain rank — the full-fidelity rung
    always gets to compare concurrency levels head to head.
    """
    groups: Dict[int, List[int]] = {}
    for i in by_idx:
        groups.setdefault(cands[i][2], []).append(i)
    for cc in groups:
        groups[cc].sort(key=lambda i: -by_idx[i])
    order = sorted(groups, key=lambda cc: -by_idx[groups[cc][0]])
    kept = [groups[cc][0] for cc in order[:keep]]
    taken = set(kept)
    rest = sorted(
        (i for i in by_idx if i not in taken), key=lambda i: -by_idx[i]
    )
    kept += rest[: keep - len(kept)]
    return sorted(kept)


def _sha_schedule(n: int, eta: int) -> Tuple[List[int], List[float]]:
    """Candidate counts per rung and the dataset fraction each rung
    evaluates at (final rung always full fidelity).

    The rung count is ``round(log_eta n)`` — rounding, not flooring, so
    a candidate set a hair over a power of eta (the Algorithm-1 /
    history injections add one or two to a 64-grid) does not grow an
    extra near-zero-fidelity rung that both misranks and halves every
    later rung's budget.
    """
    rungs = max(1, round(math.log(max(n, 1)) / math.log(eta)))
    counts = [max(1, round(n / eta**r)) for r in range(rungs)]
    counts = [min(n, c) for c in counts]
    fractions = [float(eta) ** -(rungs - 1 - r) for r in range(rungs)]
    return counts, fractions


def successive_halving(
    scenarios: Sequence[Scenario],
    *,
    backend: str = "numpy",
    n_candidates: int = 64,
    eta: int = 4,
    space: Optional[Callable[[Scenario], Sequence]] = None,
    history=None,
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
) -> TuneResult:
    """Budgeted grid search: shrink the candidate axis between sweeps."""
    if eta < 2:
        raise ValueError("eta must be >= 2")
    keys, reps, cands = candidate_lists(
        scenarios, n_candidates=n_candidates, space=space, history=history
    )
    contexts = {key: _Context(key, reps[key]) for key in keys}
    survivors: Dict[ContextKey, List[int]] = {
        key: list(range(len(cands[key]))) for key in keys
    }
    schedules = {key: _sha_schedule(len(cands[key]), eta) for key in keys}
    rungs = max(len(s[0]) for s in schedules.values())
    trace: Dict[ContextKey, List[dict]] = {key: [] for key in keys}
    final: Dict[ContextKey, Dict[int, float]] = {key: {} for key in keys}
    evals = 0
    equivalent = 0.0
    for r in range(rungs):
        rows: List[Tuple[_Context, Triple, float]] = []
        row_of: List[Tuple[ContextKey, int]] = []
        actual_frac: Dict[ContextKey, float] = {}
        scores: Dict[ContextKey, Dict[int, float]] = {}
        for key in keys:
            counts, fractions = schedules[key]
            if r >= len(counts):
                continue  # this context's schedule already finished
            fraction = fractions[r]
            ctx = contexts[key]
            # cost accounting uses the fraction actually simulated
            # (ceil() and the 1-file floor round small rungs up)
            actual_frac[key] = (
                len(ctx.subset(fraction)) / len(ctx.files)
                if ctx.files
                else 1.0
            )
            for idx in survivors[key]:
                if idx in final[key]:
                    # already scored at full fidelity in an earlier rung
                    # (tiny filesets: the sketch IS the whole set well
                    # before the nominal schedule reaches 1.0) — reuse,
                    # don't re-simulate an identical row
                    scores.setdefault(key, {})[idx] = final[key][idx]
                    continue
                rows.append((ctx, cands[key][idx], fraction))
                row_of.append((key, idx))
        throughputs = _evaluate(rows, backend, chunk_size)
        evals += len(rows)
        for (key, idx), thr in zip(row_of, throughputs):
            scores.setdefault(key, {})[idx] = thr
            equivalent += actual_frac[key]
            # record by the fraction actually simulated, not the nominal
            # schedule: a sketch covering the whole fileset is already
            # the exact objective
            if actual_frac[key] >= 1.0:
                final[key][idx] = thr
        for key, by_idx in scores.items():
            counts, fractions = schedules[key]
            keep = (
                counts[r + 1] if r + 1 < len(counts) else 1
            )
            survivors[key] = _diverse_keep(by_idx, cands[key], keep)
            trace[key].append(
                {
                    "rung": r,
                    "fraction": fractions[r],
                    "evaluated": sorted(by_idx),
                    "best_throughput": max(by_idx.values()),
                    "kept": list(survivors[key]),
                }
            )
    tables: Dict[ContextKey, ContextTable] = {}
    for key in keys:
        by_idx = final[key]
        assert by_idx, "final rung must evaluate at full fidelity"
        idxs = sorted(by_idx)
        tables[key] = ContextTable(
            candidates=tuple(cands[key][i] for i in idxs),
            throughputs=tuple(by_idx[i] for i in idxs),
        )
        if history is not None:
            history.record(
                reps[key], tables[key].best_params,
                tables[key].best_throughput, method="sha",
            )
    return TuneResult(
        method="sha",
        entries=_entries(
            scenarios, tables, {k: len(cands[k]) for k in keys}
        ),
        tables=tables,
        evals=evals,
        equivalent_evals=equivalent,
        trace=trace,
    )


# --------------------------------------------------------------------------
# hill climbing
# --------------------------------------------------------------------------


def hill_climb(
    scenarios: Sequence[Scenario],
    *,
    backend: str = "numpy",
    n_candidates: int = 64,
    max_iters: int = 12,
    space_builder: Optional[Callable[[Scenario], ParamSpace]] = None,
    history=None,
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
) -> TuneResult:
    """Coordinate descent on the log-spaced knob axes.

    ``n_candidates`` sets the axis *density* of the default space (the
    budget actually spent depends on the walk length). Every iteration
    is one batched sweep over all live contexts' unevaluated neighbor
    settings; a context converges when no axis neighbor beats its
    current point.

    Unlike the flat candidate *sequences* the oracle / successive
    halving accept via ``space``, the climber needs axis structure, so
    its override is named differently: ``space_builder`` maps a
    scenario to a :class:`repro.eval.tune.space.ParamSpace`.
    """
    keys, reps = group_contexts(scenarios)
    spaces: Dict[ContextKey, ParamSpace] = {}
    contexts: Dict[ContextKey, _Context] = {}
    current: Dict[ContextKey, Tuple[int, int, int]] = {}
    cache: Dict[ContextKey, Dict[Tuple[int, int, int], float]] = {}
    trace: Dict[ContextKey, List[dict]] = {}
    for key in keys:
        rep = reps[key]
        spaces[key] = (
            space_builder(rep) if space_builder is not None
            else scenario_space(rep, n_candidates=n_candidates)
        )
        contexts[key] = _Context(key, rep)
        start_params = history.seed(rep) if history is not None else None
        if start_params is None:
            start_params = algorithm1_params(rep)
        current[key] = spaces[key].nearest(start_params)
        cache[key] = {}
        trace[key] = []
    live = set(keys)
    evals = 0
    for it in range(max_iters):
        rows: List[Tuple[_Context, Triple, float]] = []
        row_of: List[Tuple[ContextKey, Tuple[int, int, int]]] = []
        for key in sorted(live, key=keys.index):
            sp = spaces[key]
            frontier = [current[key]] + sp.neighbors(current[key])
            for idx in frontier:
                if idx not in cache[key]:
                    rows.append((contexts[key], _triple_of(sp, idx), 1.0))
                    row_of.append((key, idx))
        if rows:
            throughputs = _evaluate(rows, backend, chunk_size)
            evals += len(rows)
            for (key, idx), thr in zip(row_of, throughputs):
                cache[key][idx] = thr
        next_live = set()
        for key in live:
            sp = spaces[key]
            frontier = [current[key]] + sp.neighbors(current[key])
            best = max(frontier, key=lambda i: cache[key][i])
            trace[key].append(
                {
                    "iter": it,
                    "current": current[key],
                    "throughput": cache[key][current[key]],
                    "best_neighbor": best,
                }
            )
            if cache[key][best] > cache[key][current[key]]:
                current[key] = best
                next_live.add(key)
        live = next_live
        if not live:
            break
    tables: Dict[ContextKey, ContextTable] = {}
    for key in keys:
        sp = spaces[key]
        idxs = sorted(cache[key])
        tables[key] = ContextTable(
            candidates=tuple(_triple_of(sp, i) for i in idxs),
            throughputs=tuple(cache[key][i] for i in idxs),
        )
        if history is not None:
            history.record(
                reps[key], tables[key].best_params,
                tables[key].best_throughput, method="hill",
            )
    return TuneResult(
        method="hill",
        entries=_entries(
            scenarios, tables, {k: len(cache[k]) for k in keys}
        ),
        tables=tables,
        evals=evals,
        equivalent_evals=float(evals),
        trace=trace,
    )


def _triple_of(sp: ParamSpace, idx: Tuple[int, int, int]) -> Triple:
    p = sp.params_at(idx)
    return (p.pipelining, p.parallelism, p.concurrency)
