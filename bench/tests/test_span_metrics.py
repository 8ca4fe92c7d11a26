"""The readers of the program's round spans and loop counter, on
hand-built runs and in a traced smoke run on the CPU, and the trace
reduction with those spans in the host plane."""
from __future__ import annotations

import copy

import pytest

from conftest import REPO, run_cell
from test_trace import HAND, OTHER_CHIP

from bench import trace
from bench.run import load_reader

SPAN_METRICS = {
    "loop.device_call_ms": ("device_wall_s", 1e3),
    "loop.upload_ms": ("upload_wall_s", 1e3),
    "loop.download_ms": ("download_wall_s", 1e3),
    "loop.iterations": ("iterations", 1),
}


def _run(sweeps, **sync):
    base = {"rounds": 636, "build_wall_s": 0.004, "compute_wall_s": 80.0,
            "upload_wall_s": 1.5, "device_wall_s": 58.0,
            "download_wall_s": 20.0, "iterations": 1_200_000}
    base.update(sync)
    return {"sweeps": sweeps, "sync": base, "window_compiles": 0,
            "setup_compile_s": 9.0, "trace": None}


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_reader_gives_its_key_per_sweep(name):
    key, scale = SPAN_METRICS[name]
    read = load_reader(REPO, name)
    run = _run(2)
    assert read(run) == pytest.approx(scale * run["sync"][key] / 2)
    assert read(_run(0)) is None


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_reader_finds_nothing_in_a_program_without_the_split(name):
    """A program from before the round spans has ``download_wall_s``
    holding the device's time too, and no other round key."""
    run = _run(2)
    for key in ("upload_wall_s", "device_wall_s", "iterations"):
        del run["sync"][key]
    assert load_reader(REPO, name)(run) is None


def test_round_spans_leave_the_reduction_as_it_was():
    host = copy.deepcopy(HAND[0])
    # the caller's line: a run holding upload, device call and download
    host[1][0][1].extend([
        ("fabric.run", 2, 98), ("fabric.upload", 3, 9),
        ("fabric.device", 9, 31), ("fabric.download", 32, 48),
        ("fabric.upload", 49, 50), ("fabric.device", 50, 61),
    ])
    # the prep thread's build
    host[1][1][1].append(("fabric.build", 61, 99))
    spanned = [host] + HAND[1:] + [OTHER_CHIP]
    assert trace.reduce(spanned, [0]) == trace.reduce(HAND + [OTHER_CHIP],
                                                      [0])


def test_trace_run_reports_the_round_span_metrics(smoke_root):
    rc, res = run_cell(smoke_root, "smoke_wan.heuristics", trace="1")
    assert rc == 0 and res["correct"]
    # the program's round spans and loop counter are all read
    assert set(SPAN_METRICS) <= set(res["metrics"])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["loop.iterations"] >= m["loop.device_rounds"] > 0
    rounds_ms = sum(m[k] for k in ("loop.device_call_ms",
                                   "loop.upload_ms", "loop.download_ms"))
    assert 0 < rounds_ms <= m["executor.compute_ms"]
