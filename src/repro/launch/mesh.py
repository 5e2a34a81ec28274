"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is a
second data-parallel axis whose collectives cross the slow inter-pod links
-- exactly the hop IDEALEM gradient compression targets (DESIGN.md Sec. 2).

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).  The axes are
``Auto``: the model code places activations with
``with_sharding_constraint``, which asserts instead of resharding on the
``Explicit`` axes that ``jax.make_mesh`` defaults to.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "batch_axes", "make_debug_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(multi_pod: bool = False):
    return ("pod", "data") if multi_pod else ("data",)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU multi-device tests (subprocess with forced device
    count)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
