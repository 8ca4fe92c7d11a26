"""The comparison that decides ``correct``.

Every answer of every sweep in the window is held to the plain
reference (``bench/reference``) computed on the host after the window:
the numbers compared are the widest relative gap between a row's
throughput and the reference's, and the median gap over every answer.
The traffic file gives their limits (``limits``); ``PERF.md`` gives the
readings each was set from. Beside them, exact counts with the limit 0: answers missing or not
finite on either side, and rows whose files the program resolved
otherwise than the configuration draws them.
"""
from __future__ import annotations

import math
import multiprocessing

import numpy as np


def rel_gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """|got - ref| / |ref| per answer (inf or NaN where ref is 0)."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.abs(got - ref) / np.abs(ref)


def _simulate(batch):
    from bench.reference import transfer

    tasks, num = batch
    return [r["throughput"] for r in transfer.simulate(tasks, num)]


def reference_throughputs(tasks, num=float, workers: int = 1) -> np.ndarray:
    """The reference's throughput of every task, in order. With
    ``workers`` > 1 the rows are dealt over that many spawned processes,
    all ended on return."""
    if workers <= 1:
        return np.array(_simulate((tasks, num)), dtype=np.float64)
    batches = [list(range(i, len(tasks), 4 * workers))
               for i in range(min(len(tasks), 4 * workers))]
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        parts = pool.map(_simulate,
                         [([tasks[i] for i in b], num) for b in batches])
    out = np.empty(len(tasks), dtype=np.float64)
    for b, part in zip(batches, parts):
        out[b] = part
    return out


def compare(outputs, ref, limits, n_answers: int, inputs_off: int) -> dict:
    """The numbers compared, each ``{"value": v, "limit": l}``.

    ``outputs``: one throughput array per sweep, each due ``n_answers``
    answers; ``ref`` the reference's; ``inputs_off`` the rows whose
    inputs the program resolved otherwise. An answer that is missing, or
    not finite on either side, counts in ``answers_missing`` and is left
    out of the gaps; a gap that is not a number counts as infinite."""
    missing, gaps = 0, [np.zeros(0)]
    ref_ok = np.isfinite(ref)
    for out in outputs:
        if len(out) != n_answers:
            missing += abs(n_answers - len(out))
            continue
        ok = np.isfinite(out) & ref_ok
        missing += int((~ok).sum())
        gaps.append(rel_gaps(out[ok], ref[ok]))
    if not outputs:
        missing = n_answers
    gaps = np.concatenate(gaps)
    gaps[np.isnan(gaps)] = math.inf
    worst = float(gaps.max()) if gaps.size else 0.0
    median = float(np.median(gaps)) if gaps.size else 0.0
    return {
        "worst_rel_err": {"value": worst,
                          "limit": limits["worst_rel_err"]},
        "median_rel_err": {"value": median,
                           "limit": limits["median_rel_err"]},
        "answers_missing": {"value": missing, "limit": 0},
        "inputs_off": {"value": inputs_off, "limit": 0},
    }


def widest_rows(outputs, ref, n: int = 3) -> list:
    """(row index, gap) of the ``n`` rows whose gap is widest over the
    whole sweeps in ``outputs``; a gap that is not a number counts as
    infinite."""
    full = [out for out in outputs if len(out) == len(ref)]
    if not full:
        return []
    gaps = np.stack([rel_gaps(out, ref) for out in full]).max(axis=0)
    gaps = np.where(np.isnan(gaps), math.inf, gaps)
    return [(int(i), float(gaps[i])) for i in np.argsort(-gaps)[:n]]


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
