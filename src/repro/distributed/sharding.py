"""Logical-axis sharding (MaxText-style rules, divisibility-aware).

Model code annotates activations with *logical* axis names via ``shard(x,
"batch", "seq", None)``. A ``ShardingCtx`` (installed by the launcher with
``use_sharding``) maps logical names to physical mesh axes; outside any
context the annotations are no-ops, so the same model code runs on a laptop
CPU and on a 512-chip mesh.

A logical rule only applies when the dimension size is divisible by the
mapped mesh-axis product — e.g. gemma3-1b's 4 query heads cannot shard over a
16-way model axis, so "heads" silently falls back to replicated while "mlp"
(6912 % 16 == 0) still shards. This is what makes one rule set serve ten
architectures.

Parameter PartitionSpecs are derived from leaf names by ``param_pspecs`` —
every weight name in the model zoo is covered explicitly; 1-D scales/biases
shard over the FSDP axis when divisible.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisSpec = Union[None, str, Tuple[str, ...]]


def abstract_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str]):
    """Version-portable ``AbstractMesh`` constructor.

    Newer jax takes ``AbstractMesh(axis_sizes, axis_names)``; 0.4.x takes a
    single ``shape_tuple`` of ``(name, size)`` pairs (passing sizes-only there
    fails with ``TypeError: 'int' object is not iterable``). Rule resolution
    only needs axis names/sizes, never devices, so an abstract mesh is the
    right object for tests and planning code on any version.
    """
    sizes = tuple(int(s) for s in axis_sizes)
    names = tuple(axis_names)
    if len(sizes) != len(names):
        raise ValueError(f"{len(sizes)} sizes for {len(names)} axis names")
    try:
        return jax.sharding.AbstractMesh(sizes, names)
    except TypeError:
        return jax.sharding.AbstractMesh(tuple(zip(names, sizes)))

#: default logical -> physical rules for the production meshes.
DEFAULT_RULES: Dict[str, AxisSpec] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,  # long-context runs remap this to ("pod", "data")
    "heads": "model",
    "kv": "model",
    "mlp": "model",
    "moe_mlp": None,
    "vocab": "model",
    "expert": "model",
    "embed": None,
    "cap": None,  # MoE capacity dim
    # params
    "p_embed": "data",  # FSDP axis for weight matrices' d_model dim
    "p_vocab": "model",
    "p_heads": "model",
    "p_mlp": "model",
    "p_expert": "model",
    "p_lru": "model",
    "p_scale": "data",
    "layer": None,
}


@dataclasses.dataclass
class ShardingCtx:
    mesh: Mesh
    rules: Dict[str, AxisSpec]
    #: axes handled manually by an enclosing shard_map (e.g. {"pod"} in the
    #: multi-pod train step) — stripped from every resolved spec because
    #: with_sharding_constraint may only reference auto axes there.
    manual_axes: frozenset = frozenset()

    def axis_size(self, spec: AxisSpec) -> int:
        if spec is None:
            return 1
        axes = (spec,) if isinstance(spec, str) else spec
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    def resolve(self, names: Sequence[Optional[str]], shape) -> P:
        """Logical names -> PartitionSpec, dropping non-divisible entries."""
        out = []
        used: set = set()
        for dim, name in zip(shape, names):
            spec = self.rules.get(name) if name else None
            if spec is not None:
                axes = (spec,) if isinstance(spec, str) else tuple(spec)
                # drop axes that are shard_map-manual or absent from the mesh
                # (e.g. no "pod" axis on the single-pod mesh)
                axes = tuple(
                    a for a in axes
                    if a not in self.manual_axes and a in self.mesh.shape
                )
                if (
                    not axes
                    or any(a in used for a in axes)
                    or dim % self.axis_size(axes) != 0
                ):
                    spec = None
                else:
                    used.update(axes)
                    spec = axes if len(axes) > 1 else axes[0]
            out.append(spec)
        return P(*out)


_local = threading.local()


def current_ctx() -> Optional[ShardingCtx]:
    return getattr(_local, "ctx", None)


@contextlib.contextmanager
def use_sharding(
    mesh: Mesh,
    rules: Optional[Dict[str, AxisSpec]] = None,
    manual_axes: frozenset = frozenset(),
):
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    prev = current_ctx()
    _local.ctx = ShardingCtx(mesh=mesh, rules=merged, manual_axes=manual_axes)
    try:
        yield _local.ctx
    finally:
        _local.ctx = prev


def mark_varying(x, axes):
    """Mark ``x`` varying over manual ``axes`` (jax's varying-manual-axes
    type)."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    return jax.lax.pcast(x, axes, to="varying")


def shard_map_compat(f, mesh, in_specs, out_specs, manual_axes):
    """shard_map manual over ``manual_axes`` only (the other mesh axes
    stay automatic)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(manual_axes),
    )


def vary_for_manual(x):
    """Mark ``x`` varying over any active manual axes (scan-carry inits that
    will accumulate manual-axis-varying values need matching vma types)."""
    ctx = current_ctx()
    if ctx is None or not ctx.manual_axes:
        return x
    axes = tuple(ctx.manual_axes)
    return jax.tree.map(lambda a: mark_varying(a, axes), x)


def shard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (no-op w/o context).

    Emits bare-PartitionSpec constraints (resolved against the ambient mesh
    set by the launcher via ``jax.set_mesh``) so the same annotation works in
    plain pjit programs and inside partially-manual shard_map regions.
    """
    ctx = current_ctx()
    if ctx is None:
        return x
    if len(names) != x.ndim:
        raise ValueError(f"shard(): {len(names)} names for rank-{x.ndim} array")
    if ctx.manual_axes and not hasattr(jax, "shard_map"):
        # legacy (0.4.x) partial-manual shard_map: XLA CHECK-fails on sharding
        # constraints inside the auto sub-region (hlo_sharding_util
        # IsManualSubgroup). Drop the hint; in_specs still seed propagation.
        return x
    pspec = ctx.resolve(names, x.shape)
    return jax.lax.with_sharding_constraint(x, pspec)


# ------------------------------------------------------------------ #
# parameter PartitionSpecs (by leaf name)
# ------------------------------------------------------------------ #

#: leaf-name -> logical names per trailing dims (leading 'layer' dims are
#: padded with None automatically).
_PARAM_TABLE: Dict[str, Tuple[Optional[str], ...]] = {
    # attention
    "wq": ("p_embed", "p_heads"),
    "wk": ("p_embed", "p_heads"),
    "wv": ("p_embed", "p_heads"),
    "wo": ("p_heads", "p_embed"),
    # dense ffn
    "w_up": ("p_embed", "p_mlp"),
    "w_gate": ("p_embed", "p_mlp"),
    "w_down": ("p_mlp", "p_embed"),
    # moe
    "router": ("p_embed", None),
    "we_up": ("p_expert", "p_embed", None),
    "we_gate": ("p_expert", "p_embed", None),
    "we_down": ("p_expert", None, "p_embed"),
    # embeddings / head
    "tok": ("p_vocab", "p_embed"),
    "head": ("p_vocab", "p_embed"),
    "pos": (None, "p_embed"),
    # rwkv6
    "w_r": ("p_embed", "p_heads"),
    "w_k": ("p_embed", "p_heads"),
    "w_v": ("p_embed", "p_heads"),
    "w_g": ("p_embed", "p_heads"),
    "w_o": ("p_heads", "p_embed"),
    "decay_a": ("p_embed", None),
    "decay_b": (None, "p_heads"),
    "mix_a": ("p_embed", None),
    "mix_b": (None, None, "p_embed"),
    "cm_k": ("p_embed", "p_mlp"),
    "cm_v": ("p_mlp", "p_embed"),
    # rg-lru recurrent block
    "w_in": ("p_embed", "p_lru"),
    "w_gate_br": ("p_embed", "p_lru"),
    "w_a": ("p_lru", None),
    "w_x": ("p_lru", None),
    "w_out": ("p_lru", "p_embed"),
    "conv_w": (None, "p_lru"),
}

_SCALE_NAMES = {
    "attn_norm", "ffn_norm", "final_norm", "q_norm", "k_norm", "ln_x",
    "mix_base", "u", "decay_base", "cm_mix", "lam", "conv_b", "gate_b",
    "enc_norm", "cross_norm", "mix_w",
}


def _leaf_pspec(name: str, shape, ctx: ShardingCtx) -> P:
    if name in _PARAM_TABLE:
        logical = _PARAM_TABLE[name]
        pad = len(shape) - len(logical)
        names = ("layer",) * pad + logical
        return ctx.resolve(names, shape)
    # scales / biases / mixing vectors: shard trailing dim over FSDP axis
    names = (None,) * (len(shape) - 1) + ("p_scale",)
    if len(shape) == 0:
        return P()
    return ctx.resolve(names, shape)


def param_pspecs(shape_tree, ctx: Optional[ShardingCtx] = None):
    """PartitionSpec tree for a parameter (shape) tree, by leaf names."""
    ctx = ctx or current_ctx()
    if ctx is None:
        raise RuntimeError("param_pspecs requires an active sharding context")

    def walk(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else str(path[-1])
        return _leaf_pspec(name, leaf.shape, ctx)

    return jax.tree_util.tree_map_with_path(walk, shape_tree)


def param_shardings(shape_tree, ctx: Optional[ShardingCtx] = None):
    ctx = ctx or current_ctx()
    specs = param_pspecs(shape_tree, ctx)
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), specs)
