"""Logical-axis sharding rules (GSPMD annotation layer).

Parameters are annotated with *logical* axis names ("embed", "mlp", "heads",
"vocab", …); a rule table maps logical → mesh axes. This replaces the
reference's approach of delegating sharding to DeepSpeed/FSDP config dicts
(reference: python/ray/train/lightning/_lightning_utils.py:83-126) with
first-class, introspectable sharding that XLA compiles into collectives.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicated)
LogicalAxisRules = Dict[str, Union[None, str, Tuple[str, ...]]]

# The standard rule table for transformer LMs. fsdp shards the embed dim of
# every weight (ZeRO-3); tensor shards heads/mlp (megatron); batch rides
# (data, fsdp) together so the global batch divides evenly when fsdp > 1.
DEFAULT_RULES: LogicalAxisRules = {
    "batch": ("data", "fsdp"),
    "seq": "seq",
    "embed": "fsdp",
    "heads": "tensor",
    "kv_heads": "tensor",
    "head_dim": None,
    "mlp": "tensor",
    "vocab": "tensor",
    "expert": "expert",
    "norm": None,
}


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[LogicalAxisRules] = None,
) -> P:
    """('embed','mlp') -> PartitionSpec('fsdp','tensor') under DEFAULT_RULES."""
    rules = rules or DEFAULT_RULES
    spec = []
    used: set = set()
    for ax in logical_axes:
        mesh_ax = rules.get(ax) if ax is not None else None
        # A mesh axis may appear only once per spec; later duplicates replicate.
        if mesh_ax is None:
            spec.append(None)
        elif isinstance(mesh_ax, tuple):
            fresh = tuple(m for m in mesh_ax if m not in used)
            used.update(fresh)
            spec.append(fresh if fresh else None)
        elif mesh_ax in used:
            spec.append(None)
        else:
            used.add(mesh_ax)
            spec.append(mesh_ax)
    return P(*spec)


def param_shardings(
    logical_tree: Any,
    mesh: Mesh,
    rules: Optional[LogicalAxisRules] = None,
) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def shard_pytree(tree: Any, shardings: Any) -> Any:
    """Device-put a pytree onto its shardings (host → sharded device arrays)."""
    return jax.tree.map(jax.device_put, tree, shardings)


def ambient_mesh():
    """The mesh ``jax.set_mesh`` put in scope, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.shape_tuple else None


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]],
              rules: Optional[LogicalAxisRules] = None) -> jax.Array:
    """with_sharding_constraint by logical axes. No-op when no mesh is in
    scope (plain eager/single-chip code); real annotation errors propagate."""
    if ambient_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, logical_to_spec(logical_axes, rules))
