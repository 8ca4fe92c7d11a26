"""The trace reduction on a hand-built event list whose answer is known,
laid out as a TPU v5e trace is (ops named by their HLO text, a while op
spanning its body, Python-tracer frames on the host)."""
from __future__ import annotations

import pytest

from bench import trace

# window [0, 100) ns; device ops cover [10, 30) and [50, 60): busy 30
HAND = [
    ("/host:CPU", [
        ("python3", [
            ("bench.slice", 0, 100),
            ("$run.py:1 main", 0, 100),
            ("$runner.py:5 run_matrix", 1, 99),
            ("$jax_backend.py:9 _download", 32, 48),
        ]),
        ("python3", [
            ("$threading.py:1 _bootstrap", 0, 100),
            ("$executor.py:2 prep", 0, 100),
            ("$plan.py:3 take", 61, 99),
        ]),
        ("python3", [
            ("$executor.py:133 _warm_loop", 0, 100),
            ("$queue.py:154 get", 0, 100),
        ]),
        # a thread whose outer frames began before the trace
        ("python3", [("$array.py:631 _value", 20, 55)]),
    ]),
    ("/device:TPU:0", [
        ("XLA Modules", [("jit__device_rounds_fn(1)", 10, 30),
                         ("jit_other(2)", 50, 60)]),
        ("XLA Ops", [("%while.3 = (f32[8]) while(...)", 10, 30),
                     ("%fusion.1 = f32[8] fusion(...)", 10, 20),
                     ("%sort.2 = f32[8] sort(...)", 15, 30),
                     ("%fusion.1 = f32[8] fusion(...)", 50, 60)]),
    ]),
]


# a chip the cell does not use, busy all through the window
OTHER_CHIP = ("/device:TPU:1", [
    ("XLA Ops", [("%fusion.9 = f32[8] fusion(...)", 0, 100)]),
])


def test_hand_built_trace_reduces_to_known_numbers():
    red = trace.reduce(HAND + [OTHER_CHIP], [0])
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(30e-9)
    # ops by HLO name; the while op, which spans its body, is not listed
    assert red["device_ops"] == [["%fusion.1", pytest.approx(20e-9)],
                                 ["%sort.2", pytest.approx(15e-9)]]
    gaps = red["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([40e-9, 20e-9, 10e-9])
    # [60, 100): the prep thread builds a plan, the caller waits in run_matrix
    assert gaps[0][0] == (
        "fabric-prep: plan.py:3 take; fabric-warm: queue.py:154 get; "
        "main: runner.py:5 run_matrix"
    )
    # [30, 50): the caller downloads; an older thread waits on an array
    assert "main: jax_backend.py:9 _download" in gaps[1][0]
    assert "thread: array.py:631 _value" in gaps[1][0]


def test_only_the_cells_chips_are_read():
    assert [p[0] for p in trace.device_planes(HAND + [OTHER_CHIP], [1])] \
        == ["/device:TPU:1"]
    assert trace.device_planes(HAND, [1]) == []
    red = trace.reduce(HAND + [OTHER_CHIP], [0, 1])
    assert red["busy_s"] == pytest.approx((30e-9 + 100e-9) / 2)


def test_union_merges_overlaps_and_clips_to_window():
    busy, gaps = trace.busy_and_gaps(
        [("a", -5, 5), ("b", 3, 8), ("c", 20, 40)], 0, 30
    )
    assert busy == pytest.approx(18e-9)
    assert gaps == [(8, 20)]
