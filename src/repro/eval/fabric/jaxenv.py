"""JAX settings shared by the fabric backend and its entry points.

Two things live here:

  * :func:`x64` — the one scoped float64 switch. The device loop and the
    Pallas kernels trace in float64; the rest of the repo traces in
    float32, so the switch is a context manager (``jax.enable_x64``),
    never the global flag. It is *thread-local*: every thread that
    traces or uploads f64 state (the executor's AOT warm thread
    included) enters it itself.
  * :func:`arm_compile_cache` — JAX's persistent compilation cache for
    the command-line entry points (runner, difftest, ``chip_smoke.py``,
    ``benchmarks.run``). Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
    directory is the cache; otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    never moves). Library calls — the tests among them — arm nothing.
"""
from __future__ import annotations

import os

#: ``<checkout>/.jax_cache``: this file sits at
#: ``<checkout>/src/repro/eval/fabric/jaxenv.py``
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(
            os.path.dirname(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            )
        )
    ),
    ".jax_cache",
)


def x64():
    """Scoped (thread-local) float64 tracing: ``with x64(): ...``."""
    import jax

    return jax.enable_x64(True)


def arm_compile_cache() -> str:
    """Turn on the persistent compilation cache for an entry point and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX
    has already read it), else :data:`CHECKOUT_CACHE_DIR`."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = CHECKOUT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: besides the device loop a sweep compiles a few
    # dozen small upload/gather helpers, and on a TPU each of those takes
    # a few hundred milliseconds to compile
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
