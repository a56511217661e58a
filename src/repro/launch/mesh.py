"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax device query.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "require_devices",
           "parse_mesh_shape"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod 16x16 ('data','model') or 2-pod 2x16x16
    ('pod','data','model')."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (elastic restarts pass the recomputed shape)."""
    need = 1
    for size in shape:
        need *= int(size)
    require_devices(need, shape=shape, axes=axes)
    # Auto axes: the sharding rules place arrays with
    # with_sharding_constraint / NamedSharding, which Explicit axes refuse
    return jax.make_mesh(tuple(int(s) for s in shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def parse_mesh_shape(text: str) -> Tuple[int, ...]:
    """Parse a CLI mesh-shape literal like ``'4x2'`` into ``(4, 2)``.

    Axis order is the mesh-construction order: ``data x model`` for the
    2-axis meshes the sharded GEMM path uses.
    """
    try:
        shape = tuple(int(part) for part in text.lower().split("x"))
    except ValueError:
        raise ValueError(f"mesh shape {text!r} is not of the form "
                         f"'DxM' (e.g. '4x2')") from None
    if not shape or any(s < 1 for s in shape):
        raise ValueError(f"mesh shape {text!r} needs positive axis sizes")
    return shape


def require_devices(n: int, *, shape: Optional[Tuple[int, ...]] = None,
                    axes: Optional[Tuple[str, ...]] = None) -> None:
    """Fail fast when the requested mesh cannot be built.

    ``n`` is the device count the caller needs.  When ``shape``/``axes``
    are given, also check that the shape's product matches ``n`` and —
    if the host is short on devices — name the first axis whose size the
    remaining device pool cannot factor, instead of only the total.
    """
    have = len(jax.devices())
    if shape is not None:
        need = 1
        for size in shape:
            need *= int(size)
        if need != n:
            raise ValueError(
                f"mesh shape {tuple(shape)} has {need} devices but "
                f"{n} were requested — the axis product must match")
        if need > have:
            names = tuple(axes) if axes is not None else \
                tuple(f"axis{i}" for i in range(len(shape)))
            remaining = have
            for name, size in zip(names, shape):
                if size > remaining or remaining % size:
                    raise RuntimeError(
                        f"mesh axis {name!r} (size {size}) does not fit: "
                        f"{remaining} of {have} present devices remain for "
                        f"it (mesh shape {tuple(shape)} needs {need}). For "
                        f"CPU testing set XLA_FLAGS="
                        f"--xla_force_host_platform_device_count={need} "
                        f"BEFORE importing jax (launch/dryrun.py does "
                        f"this).")
                remaining //= size
    if have < n:
        raise RuntimeError(
            f"mesh needs {n} devices but only {have} present. For the "
            f"dry-run set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} BEFORE importing jax (launch/dryrun.py does this).")
