"""Run one benchmark cell of the fabric sweep on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. ``--seed`` draws the file sizes of the
configuration's datasets (``bench/sweep.py``). The cell
(``BENCHMARK.json``) names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); per-layer metrics are read by
``bench/metrics/<name>.py``. Adding a cell, a mix or a metric adds files
and edits none.

One run: set-up (imports, drawing the data, arming the compile cache
at ``<checkout>/.jax_cache``, one warm sweep that loads every program the
window uses); then a closed loop with one client that submits the whole
sweep, waits for its answers on the host and submits it again, until the
first sweep that ends at or after ``--seconds``; then every answer of
every sweep is compared with the plain reference, computed on the host.
With ``--trace 1`` one more sweep runs, the profiler on for a slice in
its middle, and the metrics are the per-layer ones. The last line of
standard output is one JSON object; the numbers compared, each beside
its limit, end standard error.

Exits nonzero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the program's sources are missing.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import resource
import shutil
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(items: list, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    if not trace:
        return [
            m for m in bench["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])
        ]
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    return [
        m for m in bench["per_layer"]
        if m["moves"] in e2e
        and cell["name"] in m.get("workloads", [cell["name"]])
    ]


def load_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def host_peak_mb() -> float:
    """Peak resident memory of this process, in MB: the kernel's
    ``ru_maxrss`` (KiB on Linux; some kernels leave ``VmHWM`` out of
    ``/proc/self/status``)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e6


def device_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_sweeps(sweep, seconds: float):
    """The measured window: whole sweeps until one ends at or after
    ``seconds``. Returns (outputs, elapsed, error)."""
    outputs = []
    t0 = time.perf_counter()
    try:
        while True:
            outputs.append(sweep.run())
            if time.perf_counter() - t0 >= seconds:
                break
    except Exception:
        traceback.print_exc()
        return outputs, time.perf_counter() - t0, True
    return outputs, time.perf_counter() - t0, False


def traced_sweep(sweep, log_dir: str, sweep_s: float, devices):
    """One more sweep, with the profiler on for a slice of
    ``trace.SLICE_S`` seconds in its middle (``sweep_s`` is the window's
    mean sweep). Returns its answers, and the slice reduced to device
    numbers over the ``devices`` the cell uses (None where the trace
    holds no plane of theirs, as on the CPU). A v5e trace records every op of the device loop, about 8
    million events per second of device time, so a whole sweep's trace
    would not fit a run."""
    import jax

    from bench import trace

    shutil.rmtree(log_dir, ignore_errors=True)
    done = []

    def work():
        try:
            done.append(sweep.run())
        except Exception as exc:
            done.append(exc)

    worker = threading.Thread(target=work, name="bench-sweep")
    worker.start()
    time.sleep(max(0.0, (sweep_s - trace.SLICE_S) / 2))
    jax.profiler.start_trace(log_dir)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            time.sleep(trace.SLICE_S)
    finally:
        jax.profiler.stop_trace()
        worker.join()
    if isinstance(done[0], Exception):
        raise done[0]
    try:
        planes = trace.load(log_dir)
        ids = [d.id for d in devices]
        if not trace.device_planes(planes, ids):
            return done[0], None
        return done[0], trace.reduce(planes, ids)
    except Exception:
        # an unreadable trace loses the device numbers, not the run
        traceback.print_exc()
        return done[0], None
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None, require_tpu: bool = True, root: str = ROOT) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = find(bench["workloads"], args.workload, "workload")
    config = load_json(
        os.path.join(root, "bench", "configs", cell["config"] + ".json")
    )
    traffic = load_json(
        os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")
    )
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no program sources at {src}", file=sys.stderr)
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    # the compile cache lives in the checkout, whatever the environment
    # says, so that two checkouts on one machine share nothing
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache

    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"bench: cell needs {cell['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    devices = devices[: cell["chips"]]

    from bench import check
    from bench.meter import CompileMeter
    from bench.sweep import Sweep
    from repro.eval.fabric import jax_backend
    from repro.eval.fabric.executor import pinned_devices
    from repro.eval.fabric.jaxenv import arm_compile_cache

    arm_compile_cache()
    meter = CompileMeter()
    sweep = Sweep(cell["config"], config, traffic, args.seed)
    outputs, traced, tr, error = [], [], None, False
    with pinned_devices(devices):
        try:
            sweep.run()  # warm: loads every program the window uses
        except Exception:
            traceback.print_exc()
            error = True
        setup_s = time.perf_counter() - t_start
        setup = meter.snapshot()
        if not error:
            jax_backend.reset_sync_stats()
            outputs, window_s, error = run_sweeps(sweep, args.seconds)
            sync = dict(jax_backend.SYNC_STATS)
            window_compiles = meter.snapshot()["compiles"] - setup["compiles"]
            peak_mb = host_peak_mb()
            peak_dev = device_peak_bytes(devices)
            if args.trace and not error:
                try:
                    out, tr = traced_sweep(sweep, os.path.join(
                        root, ".bench_trace", cell["name"]),
                        window_s / len(outputs), devices)
                    traced = [out]
                except Exception:
                    traceback.print_exc()
                    error = True

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    n_ans = len(sweep.rows)
    if error or not outputs:
        # the program raised: the traceback is on stderr
        result = {"correct": False, "attempted": n_ans * max(1, len(outputs)),
                  "failed": n_ans, "metrics": {}, "device": device}
        return emit(result, {"program_error": {"value": 1, "limit": 0}})

    # the reference runs after the window, the device's peak read
    ref = check.reference_throughputs(
        sweep.tasks(), workers=traffic.get("reference_workers", 1)
    )
    checks = check.compare(outputs + traced, ref, traffic["limits"],
                           n_ans, sweep.inputs_off())
    failed = checks["answers_missing"]["value"]
    for i, gap in check.widest_rows(outputs + traced, ref):
        print(f"widest gap {gap!r}: row {i} "
              + " ".join(map(str, sweep.rows[i])), file=sys.stderr)

    n_sweeps = len(outputs)
    if args.trace:
        run = {"sweeps": n_sweeps, "sync": sync,
               "window_compiles": window_compiles,
               "setup_compile_s": setup["compile_s"], "trace": tr}
        metrics = {}
        for m in cell_metrics(bench, cell, True):
            value = load_reader(root, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
    else:
        values = {
            "rows_per_s": n_ans * n_sweeps / window_s,
            "host_peak_mb": peak_mb,
            "setup_s": setup_s,
        }
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, cell, False)
        }
    device["memory_peak_bytes"] = peak_dev
    result = {
        "correct": check.passed(checks),
        "attempted": n_ans * n_sweeps,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if tr is not None:
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    return emit(result, checks)


def emit(result: dict, checks: dict) -> int:
    """The result line last on stdout, the compared numbers last on
    stderr. Exit 0: the run reached its verdict, whatever it is."""
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT  # bench/ itself must not shadow standard modules
    raise SystemExit(main())
