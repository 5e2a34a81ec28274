"""Pallas TPU kernel: fused min/max gate + two-sample KS distance vs dictionary.

This is IDEALEM's encode hot spot (paper Fig. 15): for each incoming block the
encoder must test exchangeability against up to D=255 stored source
distributions.  The kernel keeps the sorted candidate resident in VMEM and
streams dictionary tiles through, computing for every entry:

  mm[d] = min/max gate, eq. (3)
  ks[d] = sup_x |F_cand(x) - F_dict_d(x)|   (two-sample KS statistic, eq. 1)

ECDF counting is done with dense broadcast comparisons: the candidate is
sorted, so F_cand(xs_i) = (i+1)/n, and F_dict(xs_i) = #{dict <= xs_i}/n needs
no sorted dictionary at all -- counting is order-free.  This is O(n^2) per
entry but branch-free, layout-friendly VPU work (n <= 256 => a (TILE_D, n, n)
bool intermediate of ~0.5 MB in VMEM), in contrast to the CPU early-exit
merge walk which serializes.

Grid: one program per tile of TILE_D dictionary entries.  The wrapper in
``ops.py`` pads D up to a tile multiple and slices the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import pallas_call

TILE_D = 8  # default dictionary-tile height; sweepable via ``tile_d=``

__all__ = ["KernelShapeError", "dict_match_pallas", "TILE_D"]


# Historical import path: the class now lives in the unified hierarchy
# (repro.errors) under the ReproError root; same object either way.
from repro.errors import KernelShapeError  # noqa: E402,F401


def check_tile_divisible(num_d: int, tile_d: int, kernel: str) -> None:
    """D must be a tile multiple; the wrappers in ``ops.py`` (and the fused
    encoder's pad-at-scan-entry) guarantee it -- anything else is a caller
    bug worth a precise message."""
    if tile_d < 1:
        raise KernelShapeError(f"{kernel}: tile_d={tile_d} must be >= 1")
    if num_d % tile_d:
        pad = (-num_d) % tile_d
        raise KernelShapeError(
            f"{kernel}: D={num_d} is not a multiple of tile_d={tile_d}; "
            f"pad the dictionary with {pad} more row(s) to "
            f"{num_d + pad} (ops.dict_match pads automatically)")


def _dict_match_kernel(rtol_ref, xs_ref, dict_ref, dmin_ref, dmax_ref,
                       ks_ref, mm_ref):
    # tile height comes from the BlockSpec: dict_ref is (tile_d, n)
    n = xs_ref.shape[1]
    xs = xs_ref[...]                     # (1, n) sorted candidate
    ds = dict_ref[...]                   # (TILE_D, n) dictionary tile
    inv_n = 1.0 / n

    # --- KS distance: evaluate |F_x - F_d| at both samples' jump points ---
    # counts of dict values <= each candidate point: (TILE_D, n_x)
    cmp_d_le_x = (ds[:, :, None] <= xs[None, :, :]).astype(jnp.float32)
    cnt_d = jnp.sum(cmp_d_le_x, axis=1)                        # (TILE_D, n)
    # F_x at its own points: the count of candidate values <= each one
    # (j + 1 at position j unless values tie: a tied run takes the count
    # at its end, as the ECDF does)
    cnt_xx = jnp.sum((xs[:, None, :] <= xs[:, :, None]).astype(jnp.float32),
                     axis=2)                                   # (1, n)
    f_x_at_x = cnt_xx * inv_n                                  # (1, n)
    d1 = jnp.max(jnp.abs(f_x_at_x - cnt_d * inv_n), axis=1, keepdims=True)

    # counts of candidate values <= each dict point: (TILE_D, n_d)
    cmp_x_le_d = (xs[None, :, :] <= ds[:, :, None]).astype(jnp.float32)
    cnt_x = jnp.sum(cmp_x_le_d, axis=2)                        # (TILE_D, n)
    # F_d at its own (unsorted) points: rank of each point within its row.
    rank_d = jnp.sum((ds[:, None, :] <= ds[:, :, None]).astype(jnp.float32),
                     axis=2)                                   # (TILE_D, n)
    d2 = jnp.max(jnp.abs(cnt_x * inv_n - rank_d * inv_n), axis=1,
                 keepdims=True)

    ks_ref[...] = jnp.maximum(d1, d2)

    # --- min/max gate (eq. 3) ---
    r = rtol_ref[0, 0]
    # xs is sorted: its max is xs[n - 1] (a lane slice past the first 128
    # lanes cannot be broadcast by Mosaic, a reduction can)
    xmin, xmax = xs[:, 0:1], jnp.max(xs, axis=1, keepdims=True)
    dmin, dmax = dmin_ref[...], dmax_ref[...]                  # (TILE_D, 1)
    t = (dmax - dmin) * r
    mm = ((xmin >= dmin - t) & (xmin <= dmin + t)
          & (xmax >= dmax - t) & (xmax <= dmax + t))
    mm_ref[...] = mm.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("tile_d",))
def dict_match_pallas(xs_sorted, dict_blocks, dmin, dmax, rel_tol,
                      tile_d: int = TILE_D):
    """xs_sorted (n,), dict_blocks (D, n) [any order], dmin/dmax (D,),
    rel_tol scalar -> (ks (D,) f32, mm (D,) bool).  D must be a multiple of
    ``tile_d`` (use ops.dict_match for arbitrary D); ``tile_d`` trades VMEM
    footprint of the (tile_d, n, n) comparison against grid length, and is
    swept by the encode autotuner.  On the TPU it must be a multiple of 8:
    dmin, dmax and the outputs travel as (D, 1) columns tiled (tile_d, 1),
    and ``rel_tol`` sits in SMEM."""
    num_d, n = dict_blocks.shape
    check_tile_divisible(num_d, tile_d, "dict_match_pallas")
    grid = (num_d // tile_d,)
    rtol_arr = jnp.asarray([[rel_tol]], dtype=jnp.float32)
    col = pl.BlockSpec((tile_d, 1), lambda i: (i, 0))
    ks, mm = pallas_call(
        _dict_match_kernel,
        name="dict_match",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # rel_tol
            pl.BlockSpec((1, n), lambda i: (0, 0)),       # candidate: reused
            pl.BlockSpec((tile_d, n), lambda i: (i, 0)),  # dict tile
            col,
            col,
        ],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((num_d, 1), jnp.float32),
            jax.ShapeDtypeStruct((num_d, 1), jnp.int32),
        ],
    )(
        rtol_arr,
        xs_sorted.astype(jnp.float32).reshape(1, n),
        dict_blocks.astype(jnp.float32),
        dmin.astype(jnp.float32).reshape(num_d, 1),
        dmax.astype(jnp.float32).reshape(num_d, 1),
    )
    return ks.reshape(num_d), mm.reshape(num_d) != 0
