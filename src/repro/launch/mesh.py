"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the pod axis
composes with data for pure DP across pods (the gradient all-reduce crosses
the inter-pod links once per step).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax


def _make_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (tests use small ones, e.g. (2, 4) on 8 host devices)."""
    return _make_mesh(shape, axes)


def activate_mesh(mesh):
    """Context manager making `mesh` the ambient mesh (`jax.set_mesh`,
    which also feeds `get_abstract_mesh`)."""
    return jax.set_mesh(mesh)


def dp_axes(mesh) -> tuple:
    """Axes that shard the batch (pod folds into data-parallel)."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def tp_size(mesh) -> int:
    return mesh.shape["model"]
