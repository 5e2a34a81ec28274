"""Pallas kernel: sequential in-block cumulative sum (delta-mode decode).

Delta-mode reconstruction (paper Sec. V-B2) rebuilds each block as
``base + cumsum(deltas)``.  The host decoder uses ``np.cumsum``, which
accumulates strictly left-to-right; XLA's ``cumsum`` lowers to an
associative scan whose f64 rounding differs in the last bit for long
blocks (measured, see tests/test_decode_backends.py).  Byte-identity
between the host and device decode paths therefore needs a cumsum that
accumulates in the SAME sequential order -- this kernel.

One program per tile of TILE_R rows; within the tile a static column walk
carries the running sum, exactly like ``np.add.accumulate`` (Mosaic has no
cumsum lowering and no dynamic lane slicing, so the walk is unrolled).
Column 0 is stored as-is (``acc = x[:, 0]``, not ``0 + x[:, 0]``) so a
leading ``-0.0`` survives bit-for-bit.  P is small (block_size - 1 <= 254)
so the serialized column walk costs nothing against the gather around it.

Like every kernel here it is compiled for the platform it is lowered for
and interpreted only on the CPU (``kernels.platform``).  The TPU has no
f64, so ``repro.core.decode`` routes f64 plans to the host: that is a
routing rule counted in ``decode_stats()``, not a fallback on error.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

TILE_R = 8

__all__ = ["seq_cumsum_pallas", "seq_cumsum", "TILE_R"]


def _seq_cumsum_kernel(x_ref, o_ref):
    acc = x_ref[:, 0:1]
    o_ref[:, 0:1] = acc
    for j in range(1, x_ref.shape[1]):
        acc = acc + x_ref[:, j:j + 1]
        o_ref[:, j:j + 1] = acc


@jax.jit
def seq_cumsum_pallas(x):
    """x (R, P) -> row-wise cumulative sum, accumulated strictly
    left-to-right (bit-identical to ``np.cumsum(x, axis=1)``).  R must be
    a multiple of TILE_R (use ``seq_cumsum`` for arbitrary R)."""
    R, P = x.shape
    if R % TILE_R:
        raise ValueError(f"R={R} is not a multiple of TILE_R={TILE_R}; "
                         f"use seq_cumsum, which pads")
    return pallas_call(
        _seq_cumsum_kernel,
        name="seq_cumsum",
        grid=(R // TILE_R,),
        in_specs=[pl.BlockSpec((TILE_R, P), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE_R, P), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, P), x.dtype),
    )(x)


@jax.jit
def seq_cumsum(x):
    """Pad-to-tile wrapper for arbitrary row counts."""
    R = x.shape[0]
    pad = (-R) % TILE_R
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return seq_cumsum_pallas(x)[:R]
