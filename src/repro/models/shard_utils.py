"""Mesh-aware sharding constraints that degrade to no-ops off-mesh."""
from __future__ import annotations

from typing import Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# the batch/token-parallel axes in priority order
DATA_AXES = ("pod", "data")


def _mesh_axes() -> dict:
    """Axis sizes of the abstract mesh in context ({} outside any mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def resolve_spec(shape: Tuple[int, ...], *axes: Axis) -> tuple:
    """Per-dim spec entries naming only the axes of ``axes`` that exist in
    the current mesh AND divide that dimension (None elsewhere)."""
    sizes = _mesh_axes()

    def resolve(a, dim):
        if a is None:
            return None
        cand = (a,) if isinstance(a, str) else tuple(a)
        kept = tuple(t for t in cand if t in sizes)
        total = 1
        for t in kept:
            total *= sizes[t]
        if not kept or dim % total != 0:
            return None
        return kept if len(kept) > 1 else kept[0]

    return tuple(resolve(a, d) for a, d in zip(axes, shape))


def constrain(x: jax.Array, *axes: Axis) -> jax.Array:
    """with_sharding_constraint that only names axes present in the current
    mesh AND dividing the dimension; a no-op outside any mesh (CPU tests,
    live engine, or e.g. batch=1 decode where batch can't shard)."""
    if not _mesh_axes():
        return x
    spec = resolve_spec(x.shape, *axes)
    if not any(s for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def data_axis() -> Axis:
    names = _mesh_axes()
    kept = tuple(a for a in DATA_AXES if a in names)
    return kept if kept else None


def model_axis_size() -> int:
    return _mesh_axes().get("model", 1)


def constrain_full(x: jax.Array, *axes: Axis) -> jax.Array:
    """Like constrain, but an all-None spec still APPLIES (= replicate).

    Used to pin FSDP-stored weights to their TP-only spec at the use site:
    GSPMD then all-gathers the (small) weight shard over 'data' instead of
    gathering the (large) activations — the classic FSDP weight-gather.
    """
    if not _mesh_axes():
        return x
    return jax.lax.with_sharding_constraint(x, P(*resolve_spec(x.shape, *axes)))


def attention_head_policy(num_heads: int, num_kv_heads: int) -> str:
    """Trace-time mirror of launch.sharding.attention_policy (same ladder)."""
    n = model_axis_size()
    if num_kv_heads and num_kv_heads % n == 0:
        return "kv"
    if num_heads and num_heads % n == 0:
        return "q"
    return "none"
