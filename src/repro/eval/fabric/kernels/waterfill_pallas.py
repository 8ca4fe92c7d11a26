"""Optional Pallas water-fill kernel behind the ``(caps, pool) -> rates``
signature of :func:`repro.eval.fabric.kernels.waterfill`.

Instead of the sort-based closed form, the kernel bisects the water level
``lam`` solving ``sum_i min(cap_i, lam) = min(pool, sum_i cap_i)`` — pure
element-wise math plus row reductions, which maps onto the TPU VPU (and
Triton on GPU) without needing an in-kernel sort. 80 halvings from
``max(caps)`` pin ``lam`` to f64 resolution, so allocations agree with
the closed form to ~1e-12 relative.

Pallas has real lowerings on TPU (Mosaic) and GPU (Triton); only plain
CPU lacks one, so interpreter mode is the fallback *there alone* — a GPU
host gets the genuinely compiled kernel, not the silent interpreted
crawl it used to. CI and the equivalence test in
``tests/test_fabric_kernels.py`` exercise the interpreter path. Opt in
on the NumPy driver with ``FabricSimulation(..., waterfill_impl=
"pallas")`` or ``REPRO_FABRIC_WATERFILL=pallas``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BISECT_ITERS = 80

#: backends with a real Pallas lowering (TPU: Mosaic; GPU: Triton).
#: Everything else (cpu, plugins without kernel support) interprets.
_COMPILED_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def supports_compiled_pallas() -> bool:
    """True when the default JAX backend can lower ``pallas_call``
    natively instead of interpreting it."""
    return jax.default_backend() in _COMPILED_BACKENDS


def require_f64_kernels(option: str) -> None:
    """Refuse the float64 Pallas kernels (this water-fill and the fused
    step) where they cannot compile: TPU's kernel compiler (Mosaic) has
    no 64-bit element types and rejects them with ``UNIMPLEMENTED``.
    Called when ``option`` selects them, so the run fails at selection
    instead of mid-sweep."""
    backend = jax.default_backend()
    if backend == "tpu":
        raise ValueError(
            f"{option}=pallas selects a float64 Pallas kernel, which the "
            f"{backend} kernel compiler refuses (no 64-bit types); use "
            "the default closed-form path on this backend"
        )


def _waterfill_kernel(caps_ref, pool_ref, out_ref):
    caps = caps_ref[...]
    pool = pool_ref[...]  # (S, 1)
    total = jnp.sum(caps, axis=1, keepdims=True)
    pool_eff = jnp.clip(jnp.minimum(pool, total), 0.0, None)
    hi = jnp.max(caps, axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        filled = jnp.sum(jnp.minimum(caps, mid), axis=1, keepdims=True)
        low = filled < pool_eff
        return jnp.where(low, mid, lo), jnp.where(low, hi, mid)

    # invariant: sum(min(caps, hi)) >= pool_eff >= sum(min(caps, lo))
    lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
    out_ref[...] = jnp.minimum(caps, hi)


@functools.lru_cache(maxsize=64)
def _build_call(S: int, C: int, dtype: str, interpret: bool):
    """One ``pallas_call`` per (shape, dtype, mode): kernel construction
    re-walks the grid/block specs every time, so rebuilding it per sweep
    put Python dispatch on the hot path of every water-fill. Shapes are
    bucketed upstream (:mod:`repro.eval.fabric.bucketing`), so the cache
    stays a handful of entries."""
    return pl.pallas_call(
        _waterfill_kernel,
        out_shape=jax.ShapeDtypeStruct((S, C), dtype),
        interpret=interpret,
    )


def waterfill_pallas(caps, pool, interpret=None):
    """Max-min fair allocation of ``pool`` across ``caps`` rows via Pallas.

    ``caps``: (S, C) per-entity ceilings (idle entries 0); ``pool``: (S,).
    ``interpret=None`` auto-selects: compiled wherever the backend has a
    Pallas lowering (TPU/GPU), interpreter mode only on CPU.
    """
    caps = jnp.asarray(caps)
    pool = jnp.asarray(pool)
    S, C = caps.shape
    if S == 0 or C == 0:
        return jnp.zeros_like(caps)
    if interpret is None:
        interpret = not supports_compiled_pallas()
    pool2 = pool.reshape(S, 1).astype(caps.dtype)
    call = _build_call(S, C, jnp.dtype(caps.dtype).name, bool(interpret))
    return call(caps, pool2)


def waterfill_pallas_f64(caps, pool):
    """float64 wrapper for the NumPy driver: runs the kernel under the
    scoped x64 context and hands back a NumPy array."""
    import numpy as np
    from ..jaxenv import x64

    with x64():
        out = waterfill_pallas(
            jnp.asarray(np.asarray(caps, dtype=np.float64)),
            jnp.asarray(np.asarray(pool, dtype=np.float64)),
        )
        return np.asarray(out)
