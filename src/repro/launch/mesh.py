"""Production mesh construction.

A function (not a module constant) so importing this module never touches
jax device state. The dry-run sets XLA_FLAGS host-device-count=512 BEFORE
any jax import; tests and benches see the real single CPU device.

Every mesh is built with ``Auto`` axis types, so GSPMD propagates the
shardings the code pins with ``with_sharding_constraint``. A process that
owns serving end to end also makes its mesh global with
``jax.sharding.set_mesh``, which lets trace-time constraints
(``models.shard_utils``) see the abstract mesh.
"""
from __future__ import annotations

import jax


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with ``Auto`` axis types on every axis."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_from_spec(spec: str):
    """Build a mesh from a ``"model=K,data=D"`` CLI spec (axis order is
    normalized to the repo's ``("pod", "data", "model")`` convention, so
    ``model=2,data=4`` and ``data=4,model=2`` are the same mesh). Axis
    sizes must multiply to a divisor of the visible device count —
    ``jax.make_mesh`` enforces that; off-accelerator runs force devices
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    sizes = {}
    for part in spec.split(","):
        name, _, val = part.partition("=")
        name = name.strip()
        if name not in ("pod", "data", "model") or not val.strip().isdigit():
            raise ValueError(
                f"bad mesh spec {spec!r}: expected 'model=K,data=D' with "
                "axes from pod/data/model and integer sizes"
            )
        sizes[name] = int(val)
    axes = tuple(a for a in ("pod", "data", "model") if a in sizes)
    if not axes:
        raise ValueError(f"bad mesh spec {spec!r}: no axes given")
    return make_mesh(tuple(sizes[a] for a in axes), axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The (possibly compound) batch-parallel axes of a mesh."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def make_host_mesh(model: int = 1, data: int = 1):
    """Tiny mesh over real local devices (CPU tests)."""
    return make_mesh((data, model), ("data", "model"))
