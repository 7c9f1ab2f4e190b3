"""Shared model utilities: parameter-spec trees (single source of truth for
abstract dry-run specs AND materialized init), dtype helpers, and the
sharding-constraint hook used by layers."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from jax._src.mesh import thread_resources as _thread_resources


@dataclass(frozen=True)
class Spec:
    """Declarative parameter leaf: shape + dtype + init scheme."""

    shape: Tuple[int, ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"  # normal | zeros | ones | small
    scale: float = 1.0


def tree_specs(template) -> Dict:
    """Spec tree -> ShapeDtypeStruct tree (for .lower() dry-runs)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        template,
        is_leaf=lambda x: isinstance(x, Spec),
    )


def tree_init(template, key) -> Dict:
    """Spec tree -> materialized params (deterministic per-leaf keys)."""
    leaves, treedef = jax.tree.flatten(template, is_leaf=lambda x: isinstance(x, Spec))
    out = []
    for i, s in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if s.init == "zeros":
            arr = jnp.zeros(s.shape, s.dtype)
        elif s.init == "ones":
            arr = jnp.ones(s.shape, s.dtype)
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else max(1, s.shape[-1])
            std = s.scale / np.sqrt(fan_in)
            if s.init == "small":
                std *= 0.1
            arr = (jax.random.normal(k, s.shape, jnp.float32) * std).astype(s.dtype)
        out.append(arr)
    return jax.tree.unflatten(treedef, out)


def count_params(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


# ----------------------------------------------------------------- sharding
def current_mesh():
    m = _thread_resources.env.physical_mesh
    return None if m.empty else m


def constrain(x, *axes):
    """with_sharding_constraint that degrades to identity when no mesh is
    active and silently drops axis names the active mesh doesn't have —
    models stay mesh-agnostic."""
    mesh = current_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def fix(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in names)
            return kept if kept else None
        return e if e in names else None

    spec = P(*(fix(e) for e in axes))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# batch is sharded over (pod, data); model-parallel dims over model
BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"
