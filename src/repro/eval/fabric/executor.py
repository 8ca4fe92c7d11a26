"""Overlap-pipelined, multi-device chunk executor for the batched sweeps.

``runner.run_built`` and ``runner.run_plan`` hand their chunks to
:func:`execute_chunks`, which runs them through a small pipeline:

  * **prep threads** walk the chunks in order, build each chunk's
    driver, submit its canonical-signature ladder to its device's
    background AOT warm thread (jax), and hand the ready driver to a
    bounded per-device queue — explicit double-buffered staging: while
    device ``d`` computes chunk ``j``, chunk ``j + n_devices`` is already
    built and waiting in ``d``'s queue, and the chunk after that is being
    built;
  * one **compute worker per device** drains its queue, runs the driver
    (pinned to that device via ``device=`` for drivers that advertise
    ``supports_device_placement``), and writes results straight into the
    shared results list at the chunk's original indices — results are
    always in input order, independent of interleaving;
  * chunks round-robin across ``jax.devices()``, so the oracle/tuner
    planes scale with device count (validated on CPU hosts via
    ``--xla_force_host_platform_device_count=N``, as ``launch/dryrun.py``
    does).

The queue bound is the backpressure: at most :data:`DEFAULT_QUEUE_DEPTH`
staged chunks per device plus the one being built, so peak host memory
stays a small constant multiple of one chunk — not the whole sweep.

Any worker/prep exception cancels the pipeline (remaining chunks are
discarded) and re-raises in the caller.
"""
from __future__ import annotations

import contextlib
import os
import queue
import threading
from typing import Callable, List, Optional, Sequence

from .stats import span

#: staged (built-but-not-running) chunks per device: 1 is classic double
#: buffering — one chunk in flight, one staged, one being built
DEFAULT_QUEUE_DEPTH = 1


#: device list set by :func:`pinned_devices` (None: all of jax.devices())
_PINNED: Optional[list] = None


@contextlib.contextmanager
def pinned_devices(devices: Sequence):
    """Shard the executor's sweeps over ``devices`` only, for the scope
    of the ``with`` block (process-wide). One device pins a run to one
    chip of a multi-chip host; the multi-chip comparison runs the same
    plane under ``jax.devices()[:1]`` and ``[:4]``."""
    global _PINNED
    saved, _PINNED = _PINNED, list(devices)
    try:
        yield
    finally:
        _PINNED = saved


def backend_devices(cls) -> list:
    """The device list the executor shards over: ``jax.devices()`` (or
    the :func:`pinned_devices` list) for drivers that support placement,
    else a single anonymous slot (the NumPy driver still gets prep/
    compute overlap from the pipeline).

    XLA *host* devices are virtual — N forced CPU devices timeslice the
    same physical cores — so on the cpu platform the list is capped at
    ``os.cpu_count()``: round-robining four device loops onto one core
    benchmarked at 0.3x the single-device rate (threadpool contention
    plus N copies of every compiled program), while real accelerator
    platforms keep the full device list."""
    if getattr(cls, "supports_device_placement", False):
        import jax

        devices = list(jax.devices() if _PINNED is None else _PINNED)
        if devices and devices[0].platform == "cpu":
            devices = devices[: max(1, os.cpu_count() or 1)]
        return devices
    return [None]


def _warm_chunk(driver) -> None:
    """AOT-compile the chunk's signature ladder (initial shape + every
    compaction rung) so the compute worker finds ready executables."""
    from . import jax_backend
    from .bucketing import canonical_signature, signature_ladder

    floor = driver.compact_floor()
    for rung in signature_ladder(canonical_signature(driver), floor):
        jax_backend.warm_signature(rung, device=driver.device, floor=floor)


def _warm_loop(warm_q: "queue.Queue", stop: Optional[threading.Event],
               warm: Optional[Callable] = None,
               fail: Optional[Callable] = None) -> None:
    """Drain ``warm_q`` (driver -> AOT warm; ``None`` sentinel exits).

    Warm work is pure prefetch, so on pipeline failure (``stop`` set)
    pending drivers are discarded instead of compiled — errors surface
    as soon as the workers join, not after a stray multi-second XLA
    compile of a chunk nobody will run. A warm that raises fails the
    pipeline through ``fail``: the compiler refusing a program is a
    fault, not a reason to compile it again on the critical path."""
    warm = warm or _warm_chunk
    while True:
        driver = warm_q.get()
        if driver is None:
            return
        if stop is not None and stop.is_set():
            continue  # fail-fast: drop pending warms, keep draining
        try:
            warm(driver)
        except Exception as exc:
            if fail is None:
                raise
            fail(exc)


def execute_chunks(
    cls,
    parts: Sequence[Sequence[int]],
    builders: Optional[Sequence[Callable]],
    names: Optional[Sequence[str]],
    results: List,
    *,
    make_chunk: Optional[Callable] = None,
    prep_workers: Optional[int] = None,
) -> List:
    """Execute ``parts`` (lists of row indices) through driver class
    ``cls``, writing each row's result to ``results[i]``.

    Chunk construction is pluggable: ``make_chunk(part, device)`` must
    return a ready driver for the rows in ``part`` (the columnar plan
    path slices a ``ScenarioPlan``); the default builds ``Simulation``
    objects through ``builders``/``names`` — the legacy object path.

    The pipeline overlaps host prep, device compute, and AOT warming,
    sharding chunks across devices round-robin. ``prep_workers``
    (default 1) parallelizes chunk prep — only raise it when
    ``make_chunk`` is thread-safe, as plan slicing is and the legacy
    builder chain (shared file-cache hits aside) generally is not
    guaranteed to be.

    Chunk build and driver-run wall time accumulate into the shared
    ``stats.SYNC_STATS`` wall keys (spans ``fabric.build`` and
    ``fabric.run``), so the prep-vs-compute breakdown (``runner
    --verbose``) measures the host build tax.
    """
    parts = [list(p) for p in parts]
    placed = getattr(cls, "supports_device_placement", False)

    if make_chunk is None:
        if builders is None or names is None:
            raise ValueError("make_chunk or builders+names required")

        def make_chunk(part, dev):
            sims = [builders[i]() for i in part]
            kwargs = {"device": dev} if placed else {}
            return cls(sims, names=[names[i] for i in part], **kwargs)

    if not parts:
        return results

    devices = backend_devices(cls)
    # with one device there is no sharding win from pinning, and leaving
    # device=None keeps the AOT/jit cache key shared with direct
    # (non-executor) runs of the same shapes
    if len(devices) == 1:
        devices = [None]
    queues: List[queue.Queue] = [
        queue.Queue(maxsize=DEFAULT_QUEUE_DEPTH) for _ in devices
    ]
    stop = threading.Event()
    errors: List[BaseException] = []
    err_lock = threading.Lock()

    def fail(exc: BaseException) -> None:
        with err_lock:
            errors.append(exc)
        stop.set()

    def put(q: queue.Queue, item) -> None:
        # bounded-queue put that aborts on pipeline failure; sentinels
        # (None) always go through — workers drain until they see one
        while True:
            if stop.is_set() and item is not None:
                return
            try:
                q.put(item, timeout=0.05)
                return
            except queue.Full:
                continue

    # chunk prep fans out over a small worker pool: workers claim chunk
    # indices from a shared cursor, so chunk j still lands on device
    # j % n_devices (the round-robin sharding contract) regardless of
    # which worker built it; per-device queue order may interleave, but
    # results are written by original row index so output order is fixed
    next_j = [0]
    j_lock = threading.Lock()

    def prep() -> None:
        try:
            while not stop.is_set():
                with j_lock:
                    j = next_j[0]
                    if j >= len(parts):
                        return
                    next_j[0] = j + 1
                dev = devices[j % len(devices)]
                with span("fabric.build", "build_wall_s"):
                    driver = make_chunk(parts[j], dev)
                if placed:
                    warm_qs[j % len(devices)].put(driver)
                put(queues[j % len(devices)], (parts[j], driver))
        except BaseException as exc:  # chunk builds can raise anything
            fail(exc)

    def compute(d: int) -> None:
        q = queues[d]
        while True:
            item = q.get()
            if item is None:
                return
            if stop.is_set():
                continue  # keep draining so prep's puts can't wedge
            part, driver = item
            try:
                with span("fabric.run", "compute_wall_s"):
                    out = driver.run()
                # distinct indices per chunk: concurrent writes are safe
                for i, res in zip(part, out):
                    results[i] = res
            except BaseException as exc:
                fail(exc)

    # one warm thread per device: AOT compiles happen off the critical
    # path, one at a time per device. A single-device program is
    # compiled for its device, so an N-device sweep compiles each
    # signature N times; the per-device threads run those in parallel
    warm_qs: List["queue.Queue"] = [queue.Queue() for _ in devices]

    n_prep = max(1, min(prep_workers or 1, max(1, len(parts))))
    prep_threads = [
        threading.Thread(target=prep, name=f"fabric-prep{p}")
        for p in range(n_prep)
    ]
    compute_threads = [
        threading.Thread(target=compute, args=(d,), name=f"fabric-dev{d}")
        for d in range(len(devices))
    ]
    warm_threads = [
        threading.Thread(
            target=_warm_loop, args=(warm_qs[d], stop, None, fail),
            name=f"fabric-warm{d}", daemon=True,
        )
        for d in range(len(devices) if placed else 0)
    ]
    for t in warm_threads:
        t.start()
    for t in prep_threads + compute_threads:
        t.start()
    # sentinels flow only after every prep worker is finished
    for t in prep_threads:
        t.join()
    for q in queues:
        put(q, None)
    for t in compute_threads:
        t.join()
    # leftover warm work is pure prefetch — drop it, then join: an
    # abandoned thread still inside an XLA compile when the interpreter
    # exits aborts the whole process (std::terminate), and the join waits
    # out at most the one in-flight compile per thread
    for warm_q, t in zip(warm_qs, warm_threads):
        try:
            while True:
                warm_q.get_nowait()
        except queue.Empty:
            pass
        warm_q.put(None)
        t.join()
    if errors:
        raise errors[0]
    return results
