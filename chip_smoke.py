"""Run the fabric sweep's main path on a TPU and check it against the
event reference.

    python chip_smoke.py               # one chip: full grid + tenant fleet
    python chip_smoke.py --four-chips  # oracle plane on 4 chips vs 1

One chip (the default): the 1116-row ``full_matrix`` grid and the
~206-row coupled ``tenant_matrix`` fleet, each through
``run_matrix(..., backend="jax")`` — a cold pass (compiles: set-up) and
a warm repeat in the same process (steady) — and each held to the event
reference at the difftest's 2% bar with zero parked-row replays.

``--four-chips``: the 64-candidate oracle plane over the full grid
(``tune.oracle_search``, ~16.7k rows) through the async executor on
``jax.devices()[:4]`` and on ``jax.devices()[:1]`` in the same process;
per-row throughputs must be identical, and every one of the four chips
must have run chunks.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}`` and appears only when every phase
passed. The script exits nonzero, printing no such line, when JAX finds
no TPU, when the repository's sources are not beside it, or when any
phase fails. Everything runs in this one process: it holds the chip.
The persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback

_HERE = os.path.dirname(os.path.abspath(__file__))
RTOL = 0.02


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


class CompileMeter:
    """Backend-compile seconds (persistent-cache reads included) and
    cache hits/writes, from JAX's monitoring events. Seconds are summed
    over threads: the executor's warm threads compile beside the compute
    threads."""

    def __init__(self):
        import jax

        self.lock = threading.Lock()
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def reset(self) -> None:
        self.compile_s, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        self.by_program = {}

    def _dur(self, event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self.lock:
                self.compile_s += duration
                self.compiles += 1
                n, s = self.by_program.get(fun_name, (0, 0.0))
                self.by_program[fun_name] = (n + 1, s + duration)

    def _evt(self, event, **_):
        with self.lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> dict:
        with self.lock:
            top = sorted(self.by_program.items(), key=lambda kv: -kv[1][1])
            return {
                "compile_s": self.compile_s,
                "backend_compiles": self.compiles,
                "cache_hits": self.hits,
                "cache_writes": self.misses,
                # [program, compiles, seconds] for the costliest five
                "top_programs": [[k, n, s] for k, (n, s) in top[:5]],
            }


def matrix_phase(name, scenarios, meter, device_kind, cache_dir) -> dict:
    """Cold and warm jax runs of one matrix, checked against the event
    reference at the 2% bar with zero parked-row replays."""
    from repro.eval import run_matrix
    from repro.eval.difftest import pair_results
    from repro.eval.fabric import jax_backend

    t0 = time.perf_counter()
    ref = run_matrix(scenarios, backend="event")
    ref_wall = time.perf_counter() - t0

    jax_backend.reset_sync_stats()
    meter.reset()
    t0 = time.perf_counter()
    cold = run_matrix(scenarios, backend="jax")
    cold_wall = time.perf_counter() - t0
    cold_compile = meter.snapshot()
    programs = jax_backend.compiled_program_count()

    meter.reset()
    t0 = time.perf_counter()
    warm = run_matrix(scenarios, backend="jax")
    warm_wall = time.perf_counter() - t0
    warm_compile = meter.snapshot()
    stats = dict(jax_backend.SYNC_STATS)

    reports = pair_results(scenarios, ref, cold, "event", "jax") + (
        pair_results(scenarios, ref, warm, "event", "jax")
    )
    worst = max(r.rel_err for r in reports)
    deviations = sum(not r.ok(RTOL) for r in reports)
    ok = deviations == 0 and stats["replay_rounds"] == 0 and (
        stats["post_row_replays"] == 0
    )
    row = {
        "phase": name,
        "rows": len(scenarios),
        "wall_cold_s": cold_wall,
        "wall_warm_s": warm_wall,
        "rows_per_s_warm": len(scenarios) / warm_wall,
        "event_reference_wall_s": ref_wall,
        "cold": cold_compile,
        "warm": warm_compile,
        "compiled_programs": programs,
        "worst_rel_err": worst,
        "deviations": deviations,
        "rtol": RTOL,
        "replay_rounds": stats["replay_rounds"],
        "post_row_replays": stats["post_row_replays"],
        "device_rounds": stats["rounds"],
        "device_kind": device_kind,
        "cache_dir": cache_dir,
        "ok": ok,
    }
    if not ok:
        bad = sorted(reports, key=lambda r: -r.rel_err)[:5]
        row["worst_rows"] = [
            [r.scenario, r.event_throughput, r.batch_throughput]
            for r in bad
        ]
    return row


def one_chip(meter, device_kind, cache_dir) -> bool:
    import jax

    from repro.eval.fabric.executor import pinned_devices
    from repro.eval.scenarios import full_matrix, tenant_matrix

    with pinned_devices(jax.devices()[:1]):
        for name, scenarios in (
            ("grid", full_matrix()), ("fleet", tenant_matrix())
        ):
            row = matrix_phase(
                name, scenarios, meter, device_kind, cache_dir
            )
            emit(row)
            if not row["ok"]:
                return False
    return True


def four_chips(meter, device_kind, cache_dir) -> bool:
    import jax

    from repro.eval.fabric.executor import pinned_devices
    from repro.eval.fabric.jax_backend import JaxFabricSimulation
    from repro.eval.scenarios import full_matrix
    from repro.eval.tune import oracle_search

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {devices}")
    devices = devices[:4]
    # which devices the uploads of each device round really landed on
    landed, lock = {}, threading.Lock()
    call = JaxFabricSimulation._device_call

    def recording_call(self, mut, const, qsizes):
        with lock:
            for d in mut["t"].devices():
                landed[str(d)] = landed.get(str(d), 0) + 1
        return call(self, mut, const, qsizes)

    JaxFabricSimulation._device_call = recording_call
    scenarios = full_matrix()
    walls, tables = {}, {}
    try:
        for label, devs in (
            # four first: its per-device warm threads compile in
            # parallel, and the one-chip pass then reads device 0's
            # programs from the persistent cache
            ("4chip_cold", devices), ("1chip_cold", devices[:1]),
            ("4chip_warm", devices), ("1chip_warm", devices[:1]),
        ):
            landed.clear()
            meter.reset()
            with pinned_devices(devs):
                t0 = time.perf_counter()
                res = oracle_search(
                    scenarios, backend="jax", n_candidates=64
                )
                walls[label] = time.perf_counter() - t0
            tables[label] = {
                k: t.throughputs for k, t in res.tables.items()
            }
            emit({
                "phase": f"oracle_plane_{label}",
                "rows": res.evals,
                "wall_s": walls[label],
                "rows_per_s": res.evals / walls[label],
                "device_rounds_by_device": dict(landed),
                **meter.snapshot(),
                "device_kind": device_kind,
                "cache_dir": cache_dir,
            })
            if label.startswith("4chip") and len(landed) != 4:
                emit({"phase": "check", "ok": False,
                      "error": f"chunks landed on {sorted(landed)}"})
                return False
    finally:
        JaxFabricSimulation._device_call = call
    base = tables["4chip_cold"]
    same = all(t == base for t in tables.values())
    n_rows = sum(len(v) for v in base.values())
    emit({
        "phase": "four_chip_compare",
        "rows": n_rows,
        "identical_per_row": same,
        "wall_1chip_warm_s": walls["1chip_warm"],
        "wall_4chip_warm_s": walls["4chip_warm"],
        "speedup_4chip_warm": walls["1chip_warm"] / walls["4chip_warm"],
        "ok": same,
    })
    return same


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the multi-chip oracle-plane comparison",
    )
    args = ap.parse_args(argv)

    src = os.path.join(_HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repository sources at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
            f"({dev.device_kind})",
            file=sys.stderr,
        )
        return 1

    from repro.eval.fabric.jaxenv import arm_compile_cache

    cache_dir = arm_compile_cache()
    meter = CompileMeter()
    emit({
        "phase": "setup",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "jax_version": jax.__version__,
        "cache_dir": cache_dir,
    })
    try:
        if args.four_chips:
            ok = four_chips(meter, dev.device_kind, cache_dir)
        else:
            ok = one_chip(meter, dev.device_kind, cache_dir)
    except Exception:
        traceback.print_exc()
        return 1
    if not ok:
        return 1
    emit({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    })
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
