"""The plain reference: independent of the program, and equal to the
program's own event simulator at smoke size on the CPU."""
from __future__ import annotations

import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO, smoke_config

BLOCKED_IMPORT = r"""
import importlib.abc, sys

class Block(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path=None, target=None):
        if name == "repro" or name.startswith("repro.") or name == "jax":
            raise ImportError(f"blocked: {name}")
        return None

sys.meta_path.insert(0, Block())
sys.path.insert(0, sys.argv[1])
sys.path.insert(0, sys.argv[1] + "/src")
from bench.reference import transfer
net = {"bandwidth": 1.25e9, "rtt": 0.05, "buffer_size": 32 << 20,
       "unhidden_overhead": 0.01, "channel_setup_cost": 0.1,
       "stream_cpu_overhead": 0.002, "window_efficiency": 0.55,
       "max_streams_per_channel": 64, "control_rtt": None,
       "bandwidth_profile": None,
       "disk": {"streaming_rate": 1e9, "per_file_overhead": 0.005,
                "saturation_cc": 8, "contention": 0.02,
                "per_channel_rate": None}}
task = {"network": net, "files": [10 << 20] * 20 + [2 << 30] * 2,
        "algorithm": "promc", "max_cc": 8, "num_chunks": 4,
        "tick_period": 5.0}
print(transfer.simulate([task])[0]["throughput"])
assert not any(m == "repro" or m.startswith("repro.") for m in sys.modules)
"""


def test_reference_imports_nothing_of_the_program():
    out = subprocess.run(
        [sys.executable, "-c", BLOCKED_IMPORT, REPO],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert float(out.stdout.strip()) > 0


def _smoke_sweep(seed):
    from bench.run import load_json
    from bench.sweep import Sweep

    traffic = load_json(f"{REPO}/bench/traffic/heuristics.json")
    return Sweep("smoke_wan", smoke_config(), traffic, seed)


@pytest.mark.parametrize("seed", [0, 7, 2147483659])
def test_reference_matches_event_simulator(seed):
    from bench.check import reference_throughputs, rel_gaps
    from repro.eval.runner import run_matrix

    sweep = _smoke_sweep(seed)
    assert sweep.inputs_off() == 0
    event = np.array([r.throughput
                      for r in run_matrix(sweep.scenarios, "event")])
    ref = reference_throughputs(sweep.tasks())
    assert np.max(rel_gaps(event, ref)) <= 1e-12


def test_seed_draws_the_data_and_keeps_its_shape():
    a, b = _smoke_sweep(1), _smoke_sweep(2)
    for ds in a.files:
        assert len(a.files[ds]) == len(b.files[ds])
        assert not np.array_equal(a.files[ds], b.files[ds])
    assert np.array_equal(a.files["des"], _smoke_sweep(1).files["des"])


def test_reference_pool_gives_the_same_answers():
    from bench.check import reference_throughputs

    tasks = _smoke_sweep(3).tasks()
    one = reference_throughputs(tasks)
    assert np.array_equal(reference_throughputs(tasks, workers=2), one)
