"""Backend registry: name -> batched fabric driver class.

``numpy`` (alias ``batch``, the historical name) is the eager NumPy
driver; ``jax`` the jit/vmap device loop. The event-driven reference is
not a fabric backend — ``eval.runner`` special-cases it — but the names
here are the ``--backend`` axis surfaced by ``eval.runner`` and
``eval.difftest``.
"""
from __future__ import annotations

from typing import Type

from .driver import FabricSimulation

#: public backend names (excluding the event-driven reference)
BACKENDS = ("numpy", "jax")


def get_backend(name: str) -> Type[FabricSimulation]:
    """Resolve a fabric backend name to its driver class."""
    if name in ("numpy", "batch"):
        return FabricSimulation
    if name == "jax":
        from .jax_backend import JaxFabricSimulation

        return JaxFabricSimulation
    raise ValueError(
        f"unknown fabric backend {name!r}; options: {BACKENDS}"
    )
