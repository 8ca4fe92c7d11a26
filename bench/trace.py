"""Reduction of a profiler trace to the benchmark's device numbers.

The trace is read with ``jax.profiler.ProfileData`` into plain tuples::

    [(plane_name, [(line_name, [(event_name, start_ns, end_ns), ...]), ...]), ...]

so that every function below also runs on a hand-built event list.
Device planes are those named ``/device:<kind>:<id>``, and only those of
the devices a cell uses are read: a host may expose more chips than the
cell takes. On them the op line (``XLA Ops``) gives the busy intervals,
or, where a trace has no op line, the module line (``XLA Modules``). On a
TPU v5e each op
event is named by its whole HLO text, and the loop's ``%while`` op spans
every op of its body. The window is the host span the harness opens
around the traced slice of a sweep (``bench.slice``).
Python-tracer events on the host (names starting with ``$``; one line
per thread) say what the host was doing in each idle gap.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.slice"
#: seconds of a sweep the profiler records
SLICE_S = 0.2


def load(log_dir: str) -> list:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain tuples."""
    from jax.profiler import ProfileData

    paths = glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [
        (plane.name, [
            (line.name, [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line.events
            ])
            for line in plane.lines
        ])
        for plane in pd.planes
    ]


def device_planes(planes, ids) -> list:
    """The planes of the devices numbered ``ids``."""
    want = {str(i) for i in ids}
    return [p for p in planes if p[0].startswith("/device:")
            and p[0].rsplit(":", 1)[-1] in want]


def window(planes, span: str = WINDOW_SPAN):
    """(start, end) of the first host event named ``span``."""
    for name, lines in planes:
        if name.startswith("/device:"):
            continue
        for _, events in lines:
            for ev, s, e in events:
                if ev == span:
                    return s, e
    raise ValueError(f"no {span!r} span in the trace")


def _line_events(plane, line_name):
    return [ev for name, evs in plane[1] if name == line_name for ev in evs]


def op_events(plane) -> list:
    """The device's op executions: the op line where the trace has one,
    else the module executions."""
    return _line_events(plane, OPS_LINE) or _line_events(plane, MODULES_LINE)


def op_name(event_name: str) -> str:
    """An op's HLO instruction name: the TPU trace names each op event by
    its whole HLO text (``%fusion.6 = pred[] fusion(...)``)."""
    return event_name.split(" = ", 1)[0]


# control flow: a while op spans every op of its body
CONTAINERS = ("%while", "%conditional", "%call")


def clip(events, lo, hi) -> list:
    return [
        (n, max(s, lo), min(e, hi)) for n, s, e in events
        if e > lo and s < hi
    ]


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_and_gaps(events, lo, hi):
    """Busy seconds (length of the union of ``events`` in [lo, hi)) and
    the idle gaps between them, each ``(start, end)`` in ns."""
    merged = union((s, e) for _, s, e in clip(events, lo, hi))
    busy = sum(e - s for s, e in merged)
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return busy / 1e9, gaps


def time_by_name(events, lo, hi) -> dict:
    """Seconds per event name inside [lo, hi)."""
    out = {}
    for n, s, e in clip(events, lo, hi):
        out[n] = out.get(n, 0.0) + (e - s) / 1e9
    return out


def _frame(name: str) -> str:
    return name[1:] if name.startswith("$") else name


#: the executor's worker functions (``fabric/executor.py``) by thread
EXECUTOR_THREADS = {"prep": "fabric-prep", "compute": "fabric-dev",
                    "_warm_loop": "fabric-warm"}


def _thread_tag(stack) -> str:
    """Which thread a Python-tracer line is, from the frames it runs:
    the executor's prep, compute and warm-up workers, or the caller.
    The tracer records only calls made after the trace began, so a
    thread whose outer frames are older than the slice is ``thread``."""
    for name in stack:
        f = _frame(name)
        if "executor.py" in f:
            tag = EXECUTOR_THREADS.get(f.rsplit(" ", 1)[-1])
            if tag:
                return tag
    return "main" if any("run.py" in _frame(n) for n in stack) else "thread"


def host_doing(planes, t: int) -> str:
    """What each Python thread was running at time ``t``: its thread
    tag and innermost frame, joined by '; '."""
    parts = []
    for name, lines in planes:
        if name.startswith("/device:"):
            continue
        for _, events in lines:
            stack = [ev for ev in events
                     if ev[1] <= t < ev[2] and ev[0].startswith("$")]
            if not stack:
                continue
            stack.sort(key=lambda ev: (ev[1], -ev[2]))
            names = [ev[0] for ev in stack]
            inner = _frame(names[-1])
            parts.append(f"{_thread_tag(names)}: {inner}")
    return "; ".join(sorted(parts)) or "no host activity traced"


def reduce(planes, ids, top: int = 10) -> dict:
    """Busy and window seconds averaged over the planes of the devices
    numbered ``ids``, and the breakdown: the ``top`` device ops and the
    ``top`` longest idle gaps, each labelled with what the host was
    doing at its middle."""
    lo, hi = window(planes)
    devs = device_planes(planes, ids)
    if not devs:
        raise ValueError(f"no plane of devices {ids} in the trace")
    busy, ops, gaps = 0.0, {}, []
    for plane in devs:
        b, g = busy_and_gaps(op_events(plane), lo, hi)
        busy += b
        gaps.extend(g)
        for n, s in time_by_name(op_events(plane), lo, hi).items():
            n = op_name(n)
            if not n.startswith(CONTAINERS):
                ops[n] = ops.get(n, 0.0) + s
    n_dev = len(devs)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy / n_dev,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [
            [n, s / n_dev]
            for n, s in sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [host_doing(planes, (s + e) // 2), (e - s) / 1e9]
            for s, e in gaps[:top]
        ],
    }
