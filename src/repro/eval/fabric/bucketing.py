"""Canonical shape bucketing: make every sweep hit a small, fixed set of
compiled programs.

The JAX driver's fused loop (:func:`repro.eval.fabric.jax_backend.
_device_rounds`) is one ``jax.jit`` whose compile cache is keyed on the
shape of *every* array in the carried state: scenario rows S, channel
axis C, chunk axis K, resume-stack depth P, bandwidth-profile width B,
timeline width T, and the flat file-size buffer Q. Left raw, each of
those takes whatever value a batch happens to produce — Q in particular
is the total file count of a chunk's scenarios, different for every
chunk — so a full-matrix run pays a fresh ~5-10 s XLA compile per chunk
and the tuner's candidate planes paid hundreds of them (the ~14 min
"jax oracle" of PR 5, vs 34 s on NumPy).

Every shape that reaches the jit signature is therefore *bucketed* to a
canonical pad ladder — the next power of two at or above a per-axis
floor — shared by the matrix runner's chunking, the tuner's candidate
planes, and the fuzz harness:

  * S: padded rows, floor :data:`MIN_ROW_PAD` (the jax driver pads rows
    itself; runner chunk spans are cut power-of-two-aligned so live rows
    fill the padded shape — see :func:`chunk_spans`);
  * C / P: pre-sized by doubling from 4, already on the ladder;
  * K: chunk axis, bucketed in the driver (padding chunks are born done);
  * B: bandwidth-profile width (1 for all-static batches, else the
    ladder from :data:`PROFILE_PAD_FLOOR`);
  * T: timeline width (1 when no row records, else the budget — keep
    budgets powers of two);
  * Q: the flat file-size buffer, zero-padded at device upload to the
    quarter-step ladder of :func:`qsizes_pad`.

With the persistent compilation cache (armed by the entry points, see
:mod:`repro.eval.fabric.jaxenv`) the surviving handful of signatures
compiles once per cache directory, not once per process.
"""
from __future__ import annotations

from typing import Tuple

#: floor on the padded device row count (the jax driver's straggler-tail
#: economics set it; see ``jax_backend._MIN_PAD`` which aliases this)
MIN_ROW_PAD = 8

#: floor on the bucketed flat file-size buffer: a 1024-slot f64 pad costs
#: 8 KB of upload, while every distinct raw length below it would be one
#: more compiled program
QSIZES_FLOOR = 1024

#: the smallest padded device shape compaction will descend to (the jax
#: driver's deterministic quarter-step rung policy bottoms out here);
#: shared with :func:`signature_ladder` so the executor's AOT warm-start
#: pre-builds exactly the rungs a running batch can reach. Straggler
#: tails run thousands of narrow sweeps whose cost is linear in the pad
#: width, so the heterogeneous full grid needs the 64 rung; each extra
#: rung is one more program per (C, B) family, which the jax.export
#: trace cache keeps to ~0.3 s/program in warm processes
COMPACT_FLOOR = 64

#: chunk remainders below this are not split further into power-of-two
#: spans but padded as one chunk — a 32-row padded tail beats three
#: extra device batches with their own fixed dispatch cost
MIN_SPAN = 64

#: floor on the bucketed bandwidth-profile width of any batch that has a
#: profiled row at all (all-static batches keep the width-1 fast path).
#: Testbed profiles run 2-16 steps; letting a chunk's max width pick the
#: bucket minted separate B=4 programs for chunks that happened to hold
#: only short-profile networks — one more trace per (C, Q) family for a
#: few columns of (inf, last-multiplier) pad the gather never selects
PROFILE_PAD_FLOOR = 16


def bucket(n: int, floor: int = 1) -> int:
    """Next power of two at or above ``max(n, floor)`` (``floor`` for 0)."""
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()


def qsizes_pad(n: int) -> int:
    """Bucketed length of the flat file-size buffer: the *quarter-step*
    ladder ``1024, 4096, 16384, 65536, ...``.

    Q is the noisiest signature axis — a chunk's raw length is the total
    file count of whatever 1024 rows the cost sort dealt it, so the pow2
    ladder still minted five Q rungs across the tuner's candidate plane.
    4x steps cut that to three for the price of at most 3x dead f64
    slots (8 B each, upload-only)."""
    q = QSIZES_FLOOR
    n = int(n)
    while q < n:
        q *= 4
    return q


def chunk_spans(
    n: int, size: int, pad_aligned: bool = False
) -> Tuple[Tuple[int, int], ...]:
    """Split ``n`` rows into execution-chunk ``(lo, hi)`` spans.

    ``pad_aligned=False`` is the plain uniform split (the NumPy driver
    has no padded shapes to fill). ``pad_aligned=True`` cuts spans whose
    sizes are powers of two wherever that matters: full ``size``-row
    chunks first, then the remainder decomposed into descending
    power-of-two spans down to :data:`MIN_SPAN`, with the final scraps
    as one padded chunk. Live rows then fill the padded device shape —
    a 276-row grid becomes 256 + 20(pad 32) instead of one 276(pad 512)
    batch sweeping 46% dead rows — and every span lands on the
    canonical ladder.
    """
    spans = []
    lo = 0
    if pad_aligned:
        # keep `size` itself on the ladder so full chunks are exact
        size = bucket(size)
    while n - lo >= size:
        spans.append((lo, lo + size))
        lo += size
    rest = n - lo
    while pad_aligned and rest >= MIN_SPAN:
        take = 1 << (rest.bit_length() - 1)  # largest pow2 <= rest
        if take < MIN_SPAN:
            break
        spans.append((lo, lo + take))
        lo += take
        rest = n - lo
    if rest > 0:
        spans.append((lo, n))
    return tuple(spans)


def canonical_signature(sim) -> Tuple[int, ...]:
    """The bucketed jit-cache signature a :class:`FabricSimulation`'s
    batch will occupy on the jax driver: ``(rows, C, K, P, B, T, Q)``.

    ``rows`` is the initial padded row count; compaction walks it down
    the same ladder (each rung at most once per ``(C, K, P, B, T, Q)``
    combination). C / P reflect the closed-form capacity pre-sizing the
    jax driver applies before its first sweep, so the signature can be
    computed without running — the pad-ladder canary test plans the full
    grid's shapes this way.
    """
    need_c, need_p = sim.capacity_need()
    return (
        bucket(max(sim.S, getattr(sim, "_pad_floor", 0)), MIN_ROW_PAD),
        bucket(need_c, sim.C),
        sim.K,
        bucket(need_p, sim.P),
        sim.prof_t.shape[1],
        sim.tl_t.shape[1],
        qsizes_pad(sim.qsizes.shape[0]),
    )


def signature_ladder(
    sig: Tuple[int, ...], floor: int = COMPACT_FLOOR
) -> Tuple[Tuple[int, ...], ...]:
    """Every signature a batch starting at ``sig`` can occupy over its
    lifetime: the initial shape plus the deterministic quarter-step
    compaction rungs of the rows axis (``R, R//4, ..., floor`` — only
    the rows axis moves; compaction never reshapes C/K/P/B/T/Q).
    ``floor`` is the batch's compaction floor (:data:`COMPACT_FLOOR`
    for the heterogeneous grid, ``plan.PLAN_COMPACT_FLOOR`` for
    all-static candidate planes). The executor AOT-warms exactly this
    set per chunk, so mid-run compaction re-entry hits a pre-built
    executable too."""
    rows = int(sig[0])
    floor = int(floor)
    rest = tuple(sig[1:])
    out = [(rows,) + rest]
    while rows > floor:
        rows = max(rows // 4, floor)
        out.append((rows,) + rest)
    return tuple(out)
