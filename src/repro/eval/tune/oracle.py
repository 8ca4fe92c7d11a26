"""Exhaustive static-parameter oracle + regret over the fused sweep.

The paper's headline claim — the adaptive heuristics approach the
throughput of the *best static* (pipelining, parallelism, concurrency)
setting without knowing it in advance — needs that optimum computed.
:func:`oracle_search` treats the batched fabric sweep as a vectorized
black-box objective ``f(scenario, pp, p, cc) -> throughput``: the
scenario matrix is expanded along the candidate axis
(:func:`repro.eval.scenarios.expand_candidates`), every (scenario x
candidate) row becomes an ordinary ``static`` scenario, and *one*
:func:`repro.eval.runner.run_matrix` call sweeps the whole plane through
the chosen backend, chunked by the runner's cost-proxy scheduler. There
is no per-candidate Python loop over scenarios — the candidate axis
rides the same (S,)-row batching as everything else.

Scenarios that share a transfer context — same testbed, dataset, seed,
tick period, and maxCC budget — have identical candidate objectives
(the static rows ignore the heuristic-only ``num_chunks`` /
``algorithm`` fields), so the search evaluates each *context* once and
broadcasts the argmax back to every member row. On the full 1116-grid
this deduplication cuts the candidate plane ~4x.

:func:`regret_report` then scores the heuristics:
``regret = heuristic_throughput / oracle_throughput`` per scenario,
aggregated per algorithm. A regret near 1.0 is the paper's claim held
quantitatively; above 1.0 means the adaptive controller *beat* every
static setting (possible — per-chunk parameters and re-allocation are
exactly what a single static setting cannot express).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.simulator import SimResult
from repro.core.types import param_triple

from ..runner import DEFAULT_CHUNK_SIZE, run_matrix
from ..scenarios import Scenario, expand_candidates
from .space import algorithm1_params, scenario_space

#: context key: the scenario fields a static candidate's throughput
#: depends on (``num_chunks`` / ``algorithm`` / ``record_timeline`` are
#: heuristic-row concerns; maxCC stays because it caps the search space)
ContextKey = Tuple[str, str, int, float, int]


def context_key(sc: Scenario) -> ContextKey:
    return (sc.network, sc.dataset, sc.seed, sc.tick_period, sc.max_cc)


def group_contexts(
    scenarios: Sequence[Scenario],
) -> Tuple[List[ContextKey], Dict[ContextKey, Scenario]]:
    """Unique transfer contexts (insertion-ordered) + one representative
    scenario per context."""
    keys: List[ContextKey] = []
    reps: Dict[ContextKey, Scenario] = {}
    for sc in scenarios:
        key = context_key(sc)
        if key not in reps:
            keys.append(key)
            reps[key] = sc
    return keys, reps


@dataclasses.dataclass(frozen=True)
class ContextTable:
    """Per-context candidate evaluations: the searched settings and the
    throughput each achieved (aligned lists, search order)."""

    candidates: Tuple[Tuple[int, int, int], ...]
    throughputs: Tuple[float, ...]

    @property
    def best_index(self) -> int:
        return int(np.argmax(self.throughputs))

    @property
    def best_params(self) -> Tuple[int, int, int]:
        return self.candidates[self.best_index]

    @property
    def best_throughput(self) -> float:
        return float(self.throughputs[self.best_index])


@dataclasses.dataclass(frozen=True)
class TuneEntry:
    """Per-scenario search outcome (broadcast from its context)."""

    scenario: str
    context: ContextKey
    best_params: Tuple[int, int, int]
    best_throughput: float
    n_candidates: int


@dataclasses.dataclass
class TuneResult:
    """Outcome of one search over a scenario matrix.

    ``entries`` aligns with the input scenario order; ``tables`` holds
    the per-context evidence; ``evals`` counts candidate simulations
    actually run and ``equivalent_evals`` their full-fidelity cost (the
    two differ only for successive halving's subsampled rungs).
    """

    method: str
    entries: List[TuneEntry]
    tables: Dict[ContextKey, ContextTable]
    evals: int
    equivalent_evals: float
    #: per-context search trace (successive halving: one dict per rung)
    trace: Optional[Dict[ContextKey, List[dict]]] = None

    def to_json(self) -> dict:
        return {
            "method": self.method,
            "evals": self.evals,
            "equivalent_evals": round(self.equivalent_evals, 3),
            "entries": [
                {
                    "scenario": e.scenario,
                    "best_params": {
                        "pipelining": e.best_params[0],
                        "parallelism": e.best_params[1],
                        "concurrency": e.best_params[2],
                    },
                    "best_throughput": e.best_throughput,
                    "n_candidates": e.n_candidates,
                }
                for e in self.entries
            ],
        }


def _as_triple(params) -> Tuple[int, int, int]:
    return param_triple(params)  # type: ignore[return-value]


def candidate_lists(
    scenarios: Sequence[Scenario],
    *,
    n_candidates: int = 64,
    space: Optional[Callable[[Scenario], Sequence]] = None,
    history=None,
) -> Tuple[List[ContextKey], Dict[ContextKey, Scenario], Dict[ContextKey, List[Tuple[int, int, int]]]]:
    """Deduplicated contexts + their candidate sets.

    ``space`` overrides the default BDP-capped grid
    (:func:`repro.eval.tune.space.scenario_space`); the Algorithm-1
    whole-dataset point always joins the set (the heuristics' own
    operating point must be inside the searched space, or grid
    granularity alone would hand them regret > 1 on one-chunk
    datasets), as does a ``history`` store's remembered winner for the
    context (warm start) when the grid does not already contain it.
    """
    keys, reps = group_contexts(scenarios)
    cands: Dict[ContextKey, List[Tuple[int, int, int]]] = {}
    for key in keys:
        rep = reps[key]
        if space is not None:
            raw = space(rep)
        else:
            raw = scenario_space(rep, n_candidates=n_candidates).grid()
        triples = [_as_triple(p) for p in raw]
        alg1 = _as_triple(algorithm1_params(rep))
        if alg1 not in triples:
            triples.append(alg1)
        if history is not None:
            seed = history.seed(rep)
            if seed is not None and _as_triple(seed) not in triples:
                triples.append(_as_triple(seed))
        if not triples:
            raise ValueError(f"empty candidate set for context {key}")
        cands[key] = triples
    return keys, reps, cands


def oracle_search(
    scenarios: Sequence[Scenario],
    *,
    backend: str = "numpy",
    n_candidates: int = 64,
    space: Optional[Callable[[Scenario], Sequence]] = None,
    history=None,
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
) -> TuneResult:
    """Exhaustive grid search, executed as one batched sweep.

    Per-context argmax over the full candidate grid: ground truth for
    the regret claims and the budget baseline the cheaper searchers
    (:mod:`repro.eval.tune.search`) are measured against.

    The pre-expanded plane is canonical-shape by construction: every row
    is a one-chunk (K=1) static scenario, and :func:`run_matrix` groups
    rows by their capacity shape hint (a static row holds exactly ``cc``
    channels) and cuts power-of-two-aligned chunk spans, so on the jax
    backend the whole 10k+-row plane executes as a handful of compiled
    programs instead of one per chunk (see
    :mod:`repro.eval.fabric.bucketing`).
    """
    keys, reps, cands = candidate_lists(
        scenarios, n_candidates=n_candidates, space=space, history=history
    )
    expanded: List[Scenario] = []
    spans: List[Tuple[ContextKey, int, int]] = []
    for key in keys:
        rows = expand_candidates([reps[key]], cands[key])
        spans.append((key, len(expanded), len(expanded) + len(rows)))
        expanded.extend(rows)
    results = run_matrix(
        expanded, backend=backend, chunk_size=chunk_size,
    )
    tables: Dict[ContextKey, ContextTable] = {}
    for key, lo, hi in spans:
        tables[key] = ContextTable(
            candidates=tuple(cands[key]),
            throughputs=tuple(r.throughput for r in results[lo:hi]),
        )
    if history is not None:
        for key in keys:
            history.record(
                reps[key],
                tables[key].best_params,
                tables[key].best_throughput,
                method="oracle",
            )
    entries = [
        TuneEntry(
            scenario=sc.name,
            context=context_key(sc),
            best_params=tables[context_key(sc)].best_params,
            best_throughput=tables[context_key(sc)].best_throughput,
            n_candidates=len(cands[context_key(sc)]),
        )
        for sc in scenarios
    ]
    return TuneResult(
        method="oracle",
        entries=entries,
        tables=tables,
        evals=len(expanded),
        equivalent_evals=float(len(expanded)),
    )


# --------------------------------------------------------------------------
# regret
# --------------------------------------------------------------------------


@dataclasses.dataclass
class RegretReport:
    """Heuristic-vs-oracle scoring of one matrix run.

    ``per_scenario`` holds ``(name, algorithm, heuristic_throughput,
    oracle_throughput, regret)`` rows; ``per_algorithm`` aggregates
    (median / mean / min / max regret, and the fraction of scenarios
    where the adaptive controller beat every static candidate).
    """

    method: str
    per_scenario: List[dict]
    per_algorithm: Dict[str, dict]

    def to_json(self) -> dict:
        return {
            "method": self.method,
            "per_algorithm": self.per_algorithm,
            "n_scenarios": len(self.per_scenario),
            "per_scenario": [
                dict(row, oracle_params=list(row["oracle_params"]))
                for row in self.per_scenario
            ],
        }

    def format_table(self) -> str:
        lines = [
            f"{'algorithm':<12} {'median':>8} {'mean':>8} {'min':>8} "
            f"{'max':>8} {'beats-oracle':>13} {'n':>5}"
        ]
        for algo, agg in sorted(self.per_algorithm.items()):
            lines.append(
                f"{algo:<12} {agg['median']:>8.3f} {agg['mean']:>8.3f} "
                f"{agg['min']:>8.3f} {agg['max']:>8.3f} "
                f"{agg['frac_above_1']:>12.0%} {agg['n']:>5d}"
            )
        return "\n".join(lines)


def regret_report(
    scenarios: Sequence[Scenario],
    heuristic_results: Sequence[SimResult],
    tune_result: TuneResult,
) -> RegretReport:
    """Score every heuristic scenario against its context's static
    optimum: ``regret = heuristic_throughput / oracle_throughput``."""
    by_context = {e.context: e for e in tune_result.entries}
    rows: List[dict] = []
    buckets: Dict[str, List[float]] = {}
    for sc, res in zip(scenarios, heuristic_results):
        if sc.algorithm == "static":
            continue  # static rows ARE candidates, not contestants
        entry = by_context[context_key(sc)]
        denom = max(entry.best_throughput, 1e-12)
        regret = res.throughput / denom
        rows.append(
            {
                "scenario": sc.name,
                "algorithm": sc.algorithm,
                "heuristic_throughput": res.throughput,
                "oracle_throughput": entry.best_throughput,
                "oracle_params": entry.best_params,
                "regret": regret,
            }
        )
        buckets.setdefault(sc.algorithm, []).append(regret)
    per_algorithm = {
        algo: {
            "median": float(np.median(vals)),
            "mean": float(np.mean(vals)),
            "min": float(np.min(vals)),
            "max": float(np.max(vals)),
            "frac_above_1": float(np.mean(np.asarray(vals) > 1.0)),
            "n": len(vals),
        }
        for algo, vals in buckets.items()
    }
    return RegretReport(
        method=tune_result.method,
        per_scenario=rows,
        per_algorithm=per_algorithm,
    )


def save_report(path: str, report: RegretReport, tune_result: TuneResult) -> None:
    """Serialize a regret report + the search it scored to JSON: the
    per-algorithm aggregates AND the per-scenario regret rows, plus each
    context's full candidate table (what every setting scored)."""
    payload = {
        "regret": report.to_json(),
        "search": tune_result.to_json(),
        "tables": {
            "/".join(str(part) for part in key): {
                "candidates": [list(c) for c in table.candidates],
                "throughputs": list(table.throughputs),
            }
            for key, table in tune_result.tables.items()
        },
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
