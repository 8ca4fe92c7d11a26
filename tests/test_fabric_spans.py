"""The fabric's telemetry spans: each ``stats.span`` adds to its
``SYNC_STATS`` wall key and, once jax is imported, opens a
``jax.profiler.TraceAnnotation`` of its name. A jax device round is split
into ``fabric.upload``, ``fabric.device`` and ``fabric.download`` inside
the executor's ``fabric.run``, and ``iterations`` counts the device loop's
iterations."""
import functools
import os
import re
import subprocess
import sys

import jax
import pytest

from repro.core import testbeds
from repro.eval import Scenario
from repro.eval.fabric import jax_backend, stats
from repro.eval.fabric.bucketing import COMPACT_FLOOR
from repro.eval.fabric.executor import execute_chunks
from repro.eval.fabric.jax_backend import JaxFabricSimulation
from repro.eval.fabric.jaxenv import x64
from repro.eval.scenarios import build_simulation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUND_SPANS = ("fabric.upload", "fabric.device", "fabric.download")
#: (rows, C, K, P, B, T, Q): a small loop signature, cheap to compile
SMALL_SIG = (16, 8, 4, 16, 1, 1, 1024)
#: its profiled twin: bandwidth-profile width 16 on the same channel
#: floor, the shape the cross-traffic cell runs
PROFILED_SIG = (16, 8, 4, 16, 16, 1, 1024)
LOOP_SCOPES = (
    jax_backend.SCOPE_ADVANCE, jax_backend.SCOPE_COMPLETE,
    jax_backend.SCOPE_TICK, jax_backend.SCOPE_MOVE,
)


def _batch():
    nets = (testbeds.XSEDE.name, testbeds.LONI.name)
    return [
        Scenario(network=nets[i % 2], dataset="mixed",
                 algorithm=("sc", "mc", "promc")[i % 3], max_cc=4, seed=i)
        for i in range(3)
    ]


def _run_one_chunk():
    """One chunk through the executor's pipeline, as ``run_matrix``
    runs it."""
    batch = _batch()
    results = [None] * len(batch)
    execute_chunks(
        JaxFabricSimulation, [list(range(len(batch)))],
        [lambda sc=sc: build_simulation(sc) for sc in batch],
        [sc.name for sc in batch], results,
    )
    assert all(r is not None for r in results)


def test_jax_round_spans_and_iterations_are_filled():
    stats.reset_sync_stats()
    _run_one_chunk()
    s = dict(stats.SYNC_STATS)
    for key in ("upload_wall_s", "device_wall_s", "download_wall_s",
                "iterations"):
        assert s[key] > 0, (key, s)
    assert s["iterations"] >= s["rounds"] > 0
    # a single chunk: the round spans lie inside its fabric.run
    rounds = s["upload_wall_s"] + s["device_wall_s"] + s["download_wall_s"]
    assert rounds <= s["compute_wall_s"]


def test_wall_keys_are_exactly_the_span_keys():
    walls = {k for k in stats.SYNC_STATS if k.endswith("_wall_s")}
    assert stats.WALL_KEYS == walls
    assert "iterations" not in stats.WALL_KEYS
    stats.reset_sync_stats()
    assert isinstance(stats.SYNC_STATS["iterations"], int)
    assert all(isinstance(stats.SYNC_STATS[k], float) for k in walls)


def test_span_adds_to_its_key_or_to_a_private_accumulator():
    stats.reset_sync_stats()
    with stats.span("fabric.build", "build_wall_s"):
        pass
    assert stats.SYNC_STATS["build_wall_s"] > 0
    local = {"device_wall_s": 0}
    with stats.span("fabric.device", "device_wall_s", local):
        pass
    assert local["device_wall_s"] > 0
    assert stats.SYNC_STATS["device_wall_s"] == 0.0
    # the time is recorded when the block raises, too
    with pytest.raises(RuntimeError):
        with stats.span("fabric.run", "compute_wall_s", local):
            local["compute_wall_s"] = 0
            raise RuntimeError("boom")
    assert local["compute_wall_s"] > 0


def test_stats_imports_and_times_without_jax():
    code = (
        "import sys\n"
        "from repro.eval.fabric import stats\n"
        "with stats.span('fabric.build', 'build_wall_s'):\n"
        "    pass\n"
        "assert stats.SYNC_STATS['build_wall_s'] > 0\n"
        "assert 'jax' not in sys.modules, 'stats imported jax'\n"
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr


def test_profiler_trace_nests_round_spans_in_run(tmp_path):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from bench import trace

    _run_one_chunk()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run_one_chunk()
    finally:
        jax.profiler.stop_trace()
    host = [lines for name, lines in trace.load(str(tmp_path))
            if name == "/host:CPU"]
    assert host, "no host plane in the trace"
    found = {n: 0 for n in ROUND_SPANS}
    for line_name, events in host[0]:
        runs = [(s, e) for n, s, e in events if n == "fabric.run"]
        for n, s, e in events:
            if n in found:
                # on the line of the thread that opened the run
                assert any(rs <= s and e <= re for rs, re in runs), n
                found[n] += 1
    assert all(found.values()), found


@functools.lru_cache(maxsize=None)
def _compiled_loop(coupled: bool, sig=SMALL_SIG):
    dev = jax.devices()[0]
    with x64():
        mut, const, qsizes = jax_backend.signature_shapes(sig, dev)
        if not coupled:
            return jax_backend._device_rounds.lower(
                mut, const, qsizes, COMPACT_FLOOR
            ).compile()
        rows, links, groups = sig[0], 4, 2
        i64, f64 = jax.numpy.int64, jax.numpy.float64
        fab = {
            "gid": jax.ShapeDtypeStruct((rows,), i64),
            "member": jax.ShapeDtypeStruct((links, rows), jax.numpy.bool_),
            "link_cap": jax.ShapeDtypeStruct((links,), f64),
            "gslot": jax.ShapeDtypeStruct((groups,), f64),
        }
        return jax_backend._device_rounds_coupled.lower(
            mut, const, qsizes, fab, COMPACT_FLOOR
        ).compile()


@pytest.mark.parametrize("coupled", [False, True], ids=["grid", "coupled"])
def test_loop_phases_reach_the_compiled_op_names(coupled):
    """Each named phase of the loop body is a component of some op's
    ``op_name`` in the compiled HLO: that metadata, not the device
    trace's op events, ties a fusion to its phase."""
    parts = _op_name_parts(_compiled_loop(coupled))
    scopes = LOOP_SCOPES + ((jax_backend.SCOPE_WATERFILL,) if coupled
                            else ())
    assert set(scopes) <= parts, sorted(set(scopes) - parts)
    if not coupled:
        assert jax_backend.SCOPE_WATERFILL not in parts


def _op_name_parts(compiled) -> set:
    """The components of every ``op_name``; a scope opened inside a
    vmapped function reads ``vmap(<scope>)`` there, and counts as
    ``<scope>``."""
    parts = set()
    for op_name in re.findall(r'op_name="([^"]*)"', compiled.as_text()):
        parts.update(re.sub(r"^vmap\((.*)\)$", r"\1", part)
                     for part in op_name.split("/"))
    return parts


@pytest.mark.parametrize("coupled", [False, True], ids=["grid", "coupled"])
@pytest.mark.parametrize("profiled", [False, True],
                         ids=["static", "profiled"])
def test_profile_scope_only_in_profiled_programs(coupled, profiled):
    """The profiled rows' capacity lookup and step horizon are the
    ``profile_capacity`` scope of the compiled HLO; the static (B == 1)
    programs have no such ops."""
    sig = PROFILED_SIG if profiled else SMALL_SIG
    parts = _op_name_parts(_compiled_loop(coupled, sig))
    assert (jax_backend.SCOPE_PROFILE in parts) == profiled
    assert jax_backend.SCOPE_ADVANCE in parts
