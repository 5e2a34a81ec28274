"""Pure-jnp oracle for the ``dict_match`` Pallas kernel.

Same math, no pallas: broadcast-count ECDF distances + eq. (3) gate.
Cross-checked in tests against both the kernel (interpret mode) and the
independent searchsorted implementation in ``repro.core.ks``.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["dict_match_ref"]


def dict_match_ref(xs_sorted, dict_blocks, dmin, dmax, rel_tol):
    """xs_sorted (n,), dict_blocks (D, n) -> (ks (D,) f32, mm (D,) bool)."""
    xs = xs_sorted.astype(jnp.float32)
    ds = dict_blocks.astype(jnp.float32)
    n = xs.shape[0]
    inv_n = 1.0 / n

    cnt_d = jnp.sum(ds[:, :, None] <= xs[None, None, :], axis=1)  # (D, n)
    # F_x at its own points: count of candidate values <= each (ties)
    cnt_xx = jnp.sum(xs[None, :] <= xs[:, None], axis=1)         # (n,)
    f_x_at_x = cnt_xx.astype(jnp.float32) * inv_n
    d1 = jnp.max(jnp.abs(f_x_at_x[None, :] - cnt_d * inv_n), axis=1)

    cnt_x = jnp.sum(xs[None, None, :] <= ds[:, :, None], axis=2)  # (D, n)
    rank_d = jnp.sum(ds[:, None, :] <= ds[:, :, None], axis=2)    # (D, n)
    d2 = jnp.max(jnp.abs(cnt_x * inv_n - rank_d * inv_n), axis=1)

    ks = jnp.maximum(d1, d2)

    xmin, xmax = xs[0], xs[n - 1]
    dmin = dmin.astype(jnp.float32)
    dmax = dmax.astype(jnp.float32)
    t = (dmax - dmin) * jnp.float32(rel_tol)
    mm = ((xmin >= dmin - t) & (xmin <= dmin + t)
          & (xmax >= dmax - t) & (xmax <= dmax + t))
    return ks, mm


def flash_decode_ref(q, k_cache, v_cache, valid):
    """Pure-jnp oracle for the flash_decode kernel.

    q (B,H,hd) pre-scaled; k/v (B,C,Hkv,hd); valid (B,C) -> (B,H,hd) f32."""
    B, H, hd = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bckd->bkgc", qg, k_cache.astype(jnp.float32))
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    import jax
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgc,bckd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, H, hd)
