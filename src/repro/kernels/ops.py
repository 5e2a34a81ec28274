"""Jit'd public wrappers around the Pallas ``dict_match`` kernel.

``dict_match``     -- (ks, mm) for arbitrary D (pads to TILE_D multiple).
                      This is the encoder matcher signature: pass it as
                      ``matcher=`` to ``repro.core.encoder.encode_decisions``
                      so the kernel's fused min/max gate is consumed directly
                      instead of being recomputed outside the kernel.
``dict_match_ks``  -- legacy KS-only view (gate discarded); kept for the
                      kernel test suite and external callers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .dict_match import TILE_D, dict_match_pallas
from .ref import dict_match_ref

__all__ = ["dict_match", "dict_match_ks", "dict_match_reference"]


@functools.partial(jax.jit, static_argnames=("rel_tol", "tile_d"))
def dict_match(xs_sorted, dict_blocks, dmin, dmax, rel_tol: float = 0.1,
               tile_d: int = TILE_D):
    """Pad-to-tile wrapper; returns (ks (D,), mm (D,))."""
    num_d, n = dict_blocks.shape
    pad = (-num_d) % tile_d
    if pad:
        dict_blocks = jnp.pad(dict_blocks, ((0, pad), (0, 0)))
        dmin = jnp.pad(dmin, (0, pad))
        dmax = jnp.pad(dmax, (0, pad))
    ks, mm = dict_match_pallas(xs_sorted, dict_blocks, dmin, dmax, rel_tol,
                               tile_d=tile_d)
    return ks[:num_d], mm[:num_d]


def dict_match_ks(xs_sorted, dict_sorted, rel_tol: float = 0.5):
    """Raw KS distances from the kernel, min/max gate discarded.

    The streaming encoder no longer uses this: it passes ``dict_match`` as
    its fused matcher and consumes (ks, mm) together.
    """
    dmin = dict_sorted[:, 0]
    dmax = dict_sorted[:, -1]
    ks, _ = dict_match(xs_sorted, dict_sorted, dmin, dmax, rel_tol)
    return ks


def dict_match_reference(xs_sorted, dict_blocks, dmin, dmax, rel_tol: float = 0.1):
    """Pure-jnp oracle with the public signature."""
    return dict_match_ref(xs_sorted, dict_blocks, dmin, dmax, rel_tol)
