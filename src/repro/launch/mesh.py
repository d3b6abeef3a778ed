"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis carries
only gradient all-reduce (or pipeline stages) — DCN-friendly.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto-typed (GSPMD propagation).
    Everything in-repo that builds a mesh goes through here."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for unit tests (requires >= prod(shape) devices)."""
    return make_mesh(shape, axes)


def parse_mesh(spec: str):
    """``"4x2:data,model"`` -> Mesh (or None for ``""``).

    The one --mesh grammar every launcher shares: shape "4x2" cross axis
    names "data,model". Validated on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    if not spec:
        return None
    try:
        shape_s, axes_s = spec.split(":")
        shape = tuple(int(x) for x in shape_s.split("x"))
        axes = tuple(a for a in axes_s.split(",") if a)
    except ValueError as e:
        raise ValueError(f"bad --mesh {spec!r}; want e.g. 4x2:data,model") \
            from e
    if len(shape) != len(axes):
        raise ValueError(f"--mesh {spec!r}: {len(shape)} dims for "
                         f"{len(axes)} axis names")
    return make_mesh(shape, axes)
