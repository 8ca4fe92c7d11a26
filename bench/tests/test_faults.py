"""A run with the timed path broken underneath comes out not correct.

The harness runs on the CPU (its look for a TPU skipped) at smoke size,
with one fault planted in the jax backend's driver: a device step that
returns its state unchanged, half of each batch left out with the mean of
the rest in its place, and one answer altered where it is produced. The
cells run on one chip, so there is no exchange between chips to leave
out."""
from __future__ import annotations

import dataclasses

import pytest

from conftest import SMOKE_CELLS, run_cell


def _state_unchanged(cls, monkeypatch):
    monkeypatch.setattr(
        cls, "_device_call", lambda self, mut, const, q: (mut, 0)
    )


def _half_batch(cls, monkeypatch):
    run = cls.run

    def half(self):
        res = run(self)
        keep = res[: (len(res) + 1) // 2]
        mean = sum(r.throughput for r in keep) / len(keep)
        return keep + [dataclasses.replace(r, throughput=mean)
                       for r in res[len(keep):]]

    monkeypatch.setattr(cls, "run", half)


def _answer_altered(cls, monkeypatch):
    run = cls.run

    def altered(self):
        res = run(self)
        res[0] = dataclasses.replace(
            res[0], throughput=res[0].throughput * (1 + 1e-2)
        )
        return res

    monkeypatch.setattr(cls, "run", altered)


@pytest.mark.parametrize(
    "fault", [_state_unchanged, _half_batch, _answer_altered],
    ids=["state_unchanged", "half_batch", "answer_altered"],
)
@pytest.mark.parametrize("workload", [c[0] for c in SMOKE_CELLS])
def test_fault_makes_run_incorrect(smoke_root, monkeypatch, workload, fault):
    from repro.eval.fabric.jax_backend import JaxFabricSimulation

    fault(JaxFabricSimulation, monkeypatch)
    rc, res = run_cell(smoke_root, workload)
    assert rc == 0 and res is not None
    assert res["correct"] is False, res["checks"]
