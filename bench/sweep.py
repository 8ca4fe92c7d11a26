"""The general generator: one cell's sweep, built from its configuration
and traffic files.

A configuration is a deployment: its testbeds (every number of each
path and its disks) and its datasets (file count and size classes). A
traffic file is the sweep a user submits over it: the schedulers and
their limits, crossed with every testbed and dataset. The file sizes are
drawn from ``--seed``; every seed gives the same file counts and size
classes, and so the same compiled shapes.

The program gets the deployment through its own registries (a testbed
by name in ``testbeds.TESTBEDS``, a dataset builder by name in
``scenarios.DATASET_BUILDERS``), under names of the form
``<configuration>.<name>``; each row carries the seed, which the program
keys its dataset caches on. The plain reference gets the same numbers as
plain data (:meth:`Sweep.tasks`), straight from here.
"""
from __future__ import annotations

import itertools

import numpy as np

MB = 1024 * 1024


def draw_sizes(spec: dict, rng: np.random.Generator) -> np.ndarray:
    """File sizes in bytes of one dataset: each size class takes its
    ``share`` of the files (rounded down), its ``count``, or the ``rest``;
    a class with ``byte_share_of_rest`` is rescaled to that share of the
    other classes' bytes and clipped to ``clip_mb``; ``total_mb`` rescales
    the whole set."""
    n = spec["files"]
    counts = []
    for c in spec["classes"]:
        counts.append(int(c["share"] * n) if "share" in c
                      else c.get("count", 0))
    for i, c in enumerate(spec["classes"]):
        if c.get("rest"):
            counts[i] = max(1, n - sum(counts))
    parts = []
    for c, k in zip(spec["classes"], counts):
        lo, hi = c["lo_mb"] * MB, c["hi_mb"] * MB
        if c["draw"] == "uniform":
            parts.append(rng.uniform(lo, hi, size=k))
        else:
            parts.append(np.exp(rng.uniform(np.log(lo), np.log(hi), size=k)))
    for i, c in enumerate(spec["classes"]):
        if "byte_share_of_rest" in c:
            rest = sum(p.sum() for j, p in enumerate(parts) if j != i)
            parts[i] *= c["byte_share_of_rest"] * rest / parts[i].sum()
            parts[i] = np.clip(parts[i], *(v * MB for v in c["clip_mb"]))
    sizes = np.concatenate(parts)
    if "total_mb" in spec:
        sizes *= spec["total_mb"] * MB / sizes.sum()
    if spec.get("shuffle"):
        rng.shuffle(sizes)
    return np.maximum(1, sizes.astype(np.int64))


def rows(traffic: dict, config: dict) -> list:
    """(network, dataset, algorithm, max_cc, num_chunks) of every row, in
    the order the sweep submits them."""
    return list(itertools.product(
        config["networks"], config["datasets"], traffic["algorithms"],
        traffic["max_cc"], traffic["num_chunks"],
    ))


class Sweep:
    """The cell's sweep: :meth:`run` is the call the window repeats; it
    returns one throughput per row, in the order of :meth:`tasks`."""

    def __init__(self, name: str, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.rows = rows(traffic, config)
        self.seed = seed
        root = np.random.SeedSequence(seed % 2**64)
        self.files = {
            ds: draw_sizes(spec, np.random.default_rng(child))
            for (ds, spec), child in zip(config["datasets"].items(),
                                         root.spawn(len(config["datasets"])))
        }
        self.scenarios = self._register(name)

    def _register(self, name: str) -> list:
        """Hand the deployment to the program's registries; the program's
        scenarios of every row."""
        from repro.core import testbeds
        from repro.core.types import DiskSpec, FileSpec, NetworkSpec
        from repro.eval import scenarios

        nets, dsets = {}, {}
        for net, spec in self.config["networks"].items():
            key = f"{name}.{net}"
            fields = dict(spec, disk=DiskSpec(**spec["disk"]))
            testbeds.TESTBEDS[key] = NetworkSpec(name=key, **fields)
            nets[net] = key
        for ds, sizes in self.files.items():
            key = f"{name}.{ds}"
            specs = [FileSpec(name=f"{ds}/{i:06d}", size=int(s))
                     for i, s in enumerate(sizes)]
            scenarios.DATASET_BUILDERS[key] = lambda _seed, s=specs: list(s)
            dsets[ds] = key
        tick = self.traffic["tick_period_s"]
        return [
            scenarios.Scenario(network=nets[net], dataset=dsets[ds],
                               algorithm=algo, max_cc=cc, num_chunks=k,
                               tick_period=tick, seed=self.seed)
            for net, ds, algo, cc, k in self.rows
        ]

    def run(self) -> np.ndarray:
        from repro.eval.runner import run_matrix

        res = run_matrix(self.scenarios, backend="jax")
        return np.array([r.throughput for r in res], dtype=np.float64)

    def inputs_off(self) -> int:
        """Rows whose files, as the program resolves them, differ from the
        configuration's."""
        from repro.eval.scenarios import build_files

        off = 0
        for sc, (_, ds, *_) in zip(self.scenarios, self.rows):
            got = np.array([f.size for f in build_files(sc)], np.int64)
            off += not np.array_equal(got, self.files[ds])
        return off

    def tasks(self) -> list:
        """Reference inputs of every row, in the order of :meth:`run`."""
        tick = self.traffic["tick_period_s"]
        return [
            {"network": self.config["networks"][net],
             "files": self.files[ds].tolist(),
             "algorithm": algo, "max_cc": cc, "num_chunks": k,
             "tick_period": tick}
            for net, ds, algo, cc, k in self.rows
        ]
