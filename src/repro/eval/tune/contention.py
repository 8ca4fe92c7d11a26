"""Fleet contention report: greedy per-tenant tuning vs a coupled oracle.

The paper's heuristics tune each transfer as if it owned the network;
:func:`repro.eval.scenarios.tenant_matrix` breaks that assumption by
coupling tenants through shared backbone links. This module answers the
first fleet question ROADMAP poses: **does greedy per-tenant Algorithm-1
tuning collapse under contention versus the static oracle?**

Two sides are compared per fabric group:

  - **heuristic** — the tenant matrix run as-is: every adaptive tenant
    (SC / MC / ProMC) applies its controller selfishly, blind to the
    other tenants on its links. This *is* greedy per-tenant Algorithm-1
    tuning: each controller's chunk parameters come from Algorithm 1 on
    its own testbed/dataset, contention or not.
  - **oracle** — the best *static* per-tenant settings found with full
    knowledge of the contention: coordinate descent over the tenants of
    a group (sweep one tenant's static candidates while the others hold
    their incumbent settings, accept the argmax of the **group
    aggregate** throughput, move to the next tenant). Initialized at
    each tenant's own Algorithm-1 setting; the candidate set is that
    setting's grid neighborhood (the hill climber's axis moves — the
    interesting contended adjustments are local back-off/grow steps),
    and the incumbent is always a candidate, so each accepted step is
    monotone in the aggregate.

``regret = heuristic_aggregate / oracle_aggregate`` per group — the
contended analogue of :func:`repro.eval.tune.oracle.regret_report`'s
uncontended claim. An **isolated** leg (the same rows with the fabric
stripped) rides along so the report also records how hard contention
binds: ``contention_factor = coupled_aggregate / isolated_aggregate``.

Every candidate evaluation is an ordinary coupled scenario batch: the
trial group is cloned under a renamed fabric group (``g000.p0k2c5``) so
clones never couple with each other or the original, and all clones of
one descent step sweep through ONE :func:`repro.eval.runner.run_matrix`
call — no per-candidate Python loop.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.types import TransferParams, param_triple

from ..runner import DEFAULT_CHUNK_SIZE, run_matrix
from ..scenarios import Scenario, tenant_matrix
from .space import algorithm1_params, scenario_space


def _group_rows(
    scenarios: Sequence[Scenario],
) -> Dict[str, List[Scenario]]:
    """Coupled rows keyed by fabric group (insertion-ordered); uncoupled
    rows are not contention subjects and are skipped."""
    groups: Dict[str, List[Scenario]] = {}
    for sc in scenarios:
        if sc.shared_fabric is not None:
            groups.setdefault(sc.shared_fabric.group, []).append(sc)
    return groups


def _configured_group(
    rows: Sequence[Scenario],
    settings: Sequence[Tuple[int, int, int]],
    tag: str,
) -> List[Scenario]:
    """The group pinned at fixed static settings, cloned under a renamed
    fabric group so the clone never couples with the original (or with
    any sibling clone carrying a different tag)."""
    out: List[Scenario] = []
    for sc, trip in zip(rows, settings):
        fab = dataclasses.replace(
            sc.shared_fabric, group=f"{sc.shared_fabric.group}.{tag}"
        )
        out.append(
            dataclasses.replace(
                sc,
                algorithm="static",
                static_params=tuple(trip),
                record_timeline=False,
                shared_fabric=fab,
            )
        )
    return out


def _candidate_grid(
    sc: Scenario, n_candidates: int
) -> List[Tuple[int, int, int]]:
    """The tenant's candidate set: its Algorithm-1 setting snapped to
    the search grid, plus one step along each axis (the hill climber's
    neighborhood). Deliberately *not* the full grid: degenerate corners
    (``cc=1, pp=0`` on a many-file dataset) make a lockstep coupled
    group crawl at the pace of its slowest member for tens of thousands
    of sweeps, and under contention the interesting moves are exactly
    the local back-off/grow steps around the uncontended optimum."""
    space = scenario_space(sc, n_candidates=max(n_candidates, 8))
    anchor = (
        sc.static_params
        if sc.static_params is not None
        else param_triple(algorithm1_params(sc))
    )
    start = space.nearest(
        TransferParams(
            pipelining=anchor[0],
            parallelism=anchor[1],
            concurrency=anchor[2],
        )
    )
    idxs = [tuple(start)]
    for axis in range(3):
        for d in (-1, 1):
            j = list(start)
            j[axis] += d
            if 0 <= j[axis] < space.shape[axis] and tuple(j) not in idxs:
                idxs.append(tuple(j))
    out: List[Tuple[int, int, int]] = []
    for idx in idxs:
        trip = param_triple(space.params_at(idx))
        if trip not in out:
            out.append(trip)
    return out[:n_candidates]


@dataclasses.dataclass
class ContentionReport:
    """Per-group and aggregate contention outcomes (see module doc)."""

    backend: str
    n_candidates: int
    per_group: List[dict]
    per_algorithm: Dict[str, dict]
    aggregate: dict

    def to_json(self) -> dict:
        return {
            "backend": self.backend,
            "candidates": self.n_candidates,
            "aggregate": self.aggregate,
            "per_algorithm": self.per_algorithm,
            "per_group": self.per_group,
        }

    def summary(self) -> dict:
        """The compact form the bench JSON embeds: aggregate stats plus
        per-algorithm median regret."""
        return {
            "backend": self.backend,
            "candidates": self.n_candidates,
            **self.aggregate,
            "regret_median_by_algorithm": {
                algo: agg["median"]
                for algo, agg in self.per_algorithm.items()
            },
        }


def greedy_static_oracle(
    groups: Dict[str, List[Scenario]],
    *,
    backend: str = "numpy",
    n_candidates: int = 8,
    passes: int = 1,
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
) -> Tuple[Dict[str, List[Tuple[int, int, int]]], int]:
    """Coordinate-descent static oracle under contention.

    Returns ``(settings, evals)``: per-group per-tenant static triples
    and the number of coupled candidate rows simulated. All groups
    advance the same tenant slot together, so each descent step is one
    batched ``run_matrix`` call over every group's candidate clones.
    """
    settings: Dict[str, List[Tuple[int, int, int]]] = {}
    cands: Dict[str, List[List[Tuple[int, int, int]]]] = {}
    for g, rows in groups.items():
        settings[g] = [
            sc.static_params
            if sc.static_params is not None
            else param_triple(algorithm1_params(sc))
            for sc in rows
        ]
        cands[g] = [_candidate_grid(sc, n_candidates) for sc in rows]
    evals = 0
    max_tenants = max((len(rows) for rows in groups.values()), default=0)
    for p in range(passes):
        for k in range(max_tenants):
            batch: List[Scenario] = []
            spans: List[Tuple[str, int, int, int]] = []
            for g, rows in groups.items():
                if k >= len(rows):
                    continue
                # the incumbent is always candidate 0: an accepted step
                # can only improve the aggregate
                options = [settings[g][k]] + [
                    c for c in cands[g][k] if c != settings[g][k]
                ]
                cands[g][k] = options
                for ci, trip in enumerate(options):
                    trial = list(settings[g])
                    trial[k] = trip
                    clone = _configured_group(rows, trial, f"p{p}k{k}c{ci}")
                    spans.append((g, ci, len(batch), len(batch) + len(clone)))
                    batch.extend(clone)
            if not batch:
                continue
            results = run_matrix(
                batch, backend=backend, chunk_size=chunk_size
            )
            evals += len(batch)
            best: Dict[str, Tuple[float, int]] = {}
            for g, ci, lo, hi in spans:
                agg = float(sum(r.throughput for r in results[lo:hi]))
                if g not in best or agg > best[g][0]:
                    best[g] = (agg, ci)
            for g, (_, ci) in best.items():
                settings[g][k] = cands[g][k][ci]
    return settings, evals


def contention_report(
    scenarios: Optional[Sequence[Scenario]] = None,
    *,
    backend: str = "numpy",
    n_candidates: int = 8,
    passes: int = 1,
    chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
) -> ContentionReport:
    """Run all three legs (heuristic coupled, isolated, greedy static
    oracle) over a tenant matrix and score the contended regret."""
    if scenarios is None:
        scenarios = tenant_matrix()
    groups = _group_rows(scenarios)
    if not groups:
        raise ValueError(
            "contention_report needs coupled scenarios (every row had "
            "shared_fabric=None) — build the matrix with tenant_matrix()"
        )

    # legs 1+2 in one sweep: the coupled fleet as-is + fabric-stripped
    # copies (isolated rows are independent, so batching them alongside
    # the coupled groups changes nothing)
    coupled: List[Scenario] = [sc for rows in groups.values() for sc in rows]
    isolated = [
        dataclasses.replace(sc, shared_fabric=None) for sc in coupled
    ]
    res = run_matrix(
        coupled + isolated, backend=backend, chunk_size=chunk_size
    )
    h_res, iso_res = res[: len(coupled)], res[len(coupled):]
    h_of = {sc.name: r for sc, r in zip(coupled, h_res)}
    iso_of = {sc.name: r for sc, r in zip(coupled, iso_res)}

    # leg 3: the contended static oracle + one final evaluation at the
    # chosen settings for per-tenant oracle throughputs
    settings, evals = greedy_static_oracle(
        groups,
        backend=backend,
        n_candidates=n_candidates,
        passes=passes,
        chunk_size=chunk_size,
    )
    final: List[Scenario] = []
    fspans: Dict[str, Tuple[int, int]] = {}
    for g, rows in groups.items():
        clone = _configured_group(rows, settings[g], "opt")
        fspans[g] = (len(final), len(final) + len(clone))
        final.extend(clone)
    fin_res = run_matrix(final, backend=backend, chunk_size=chunk_size)
    evals += len(final)

    per_group: List[dict] = []
    algo_regret: Dict[str, List[float]] = {}
    for g, rows in groups.items():
        lo, hi = fspans[g]
        o_rows = fin_res[lo:hi]
        h_agg = float(sum(h_of[sc.name].throughput for sc in rows))
        iso_agg = float(sum(iso_of[sc.name].throughput for sc in rows))
        o_agg = float(sum(r.throughput for r in o_rows))
        for sc, o in zip(rows, o_rows):
            algo_regret.setdefault(sc.algorithm, []).append(
                h_of[sc.name].throughput / max(o.throughput, 1e-12)
            )
        per_group.append(
            {
                "group": g,
                "tenants": len(rows),
                "links": len(
                    {ln for sc in rows for ln in sc.shared_fabric.links}
                ),
                "algorithms": [sc.algorithm for sc in rows],
                "heuristic_bps": h_agg,
                "oracle_bps": o_agg,
                "isolated_bps": iso_agg,
                "regret": h_agg / max(o_agg, 1e-12),
                "contention_factor": h_agg / max(iso_agg, 1e-12),
                "oracle_params": [list(t) for t in settings[g]],
            }
        )
    regrets = np.asarray([row["regret"] for row in per_group])
    factors = np.asarray([row["contention_factor"] for row in per_group])
    per_algorithm = {
        algo: {
            "median": float(np.median(vals)),
            "mean": float(np.mean(vals)),
            "min": float(np.min(vals)),
            "n": len(vals),
        }
        for algo, vals in algo_regret.items()
    }
    aggregate = {
        "groups": len(per_group),
        "tenants": len(coupled),
        "oracle_evals": evals,
        "regret_median": float(np.median(regrets)),
        "regret_mean": float(np.mean(regrets)),
        "regret_min": float(np.min(regrets)),
        "frac_groups_above_1": float(np.mean(regrets > 1.0)),
        "contention_factor_median": float(np.median(factors)),
    }
    return ContentionReport(
        backend=backend,
        n_candidates=n_candidates,
        per_group=per_group,
        per_algorithm=per_algorithm,
        aggregate=aggregate,
    )


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="numpy")
    ap.add_argument("--candidates", type=int, default=8)
    ap.add_argument("--passes", type=int, default=1)
    ap.add_argument("--groups", type=int, default=None,
                    help="tenant_matrix n_groups (default: full 36)")
    ap.add_argument("--json", action="store_true",
                    help="print the full report, not just the summary")
    args = ap.parse_args(argv)
    matrix = (
        tenant_matrix(n_groups=args.groups)
        if args.groups
        else tenant_matrix()
    )
    report = contention_report(
        matrix,
        backend=args.backend,
        n_candidates=args.candidates,
        passes=args.passes,
    )
    payload = report.to_json() if args.json else report.summary()
    print(json.dumps(payload, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
