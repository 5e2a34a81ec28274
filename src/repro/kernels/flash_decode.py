"""Pallas TPU kernel: single-token flash-decode attention over a KV cache.

Serving's hot spot: one query token per sequence against a (C, Hkv, hd)
ring cache.  The kernel tiles the cache into VMEM-sized chunks along C and
keeps the online-softmax state (m, l, acc) in VMEM scratch across grid
steps, so the (H, C) score row never round-trips HBM.  GQA is handled
in-kernel by grouping query heads over each kv head (no materialized
repeat_kv).  Grid: (batch, C/chunk), cache-chunk minor so scratch carries
across the chunk sweep; the last chunk step finalizes o = acc / l.

Validated against ``ref.flash_decode_ref`` (interpreted on the CPU; see
``kernels.platform``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import pallas_call

CHUNK_C = 512

__all__ = ["flash_decode_pallas", "CHUNK_C"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr, acc_scr):
    nc = pl.num_programs(1)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0]          # (Hkv, G, hd) grouped query heads
    k = k_ref[0]          # (chunk, Hkv, hd)
    v = v_ref[0]          # (chunk, Hkv, hd)
    valid = valid_ref[0]  # (chunk,) bool

    s = jnp.einsum("kgd,ckd->kgc", q.astype(jnp.float32),
                   k.astype(jnp.float32))  # (Hkv, G, chunk)
    s = jnp.where(valid[None, None, :], s, _NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, :, None])
    alpha = jnp.exp(m_prev - m_new)
    pv = jnp.einsum("kgc,ckd->kgd", p, v.astype(jnp.float32))
    acc_scr[...] = acc_scr[...] * alpha[:, :, None] + pv
    m_scr[...] = m_new
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1)

    @pl.when(j == nc - 1)
    def _final():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, :, None]
                    ).astype(o_ref.dtype)


@jax.jit
def flash_decode_pallas(q, k_cache, v_cache, valid):
    """q: (B, H, hd) pre-scaled query; k/v_cache: (B, C, Hkv, hd);
    valid: (B, C) bool (ring-position validity incl. window masking).
    Returns (B, H, hd) f32."""
    B, H, hd = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    chunk = min(CHUNK_C, C)
    assert C % chunk == 0, "cache length must be a multiple of the chunk"
    qg = q.reshape(B, Hkv, G, hd)
    out = pallas_call(
        _kernel,
        name="flash_decode",
        grid=(B, C // chunk),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, hd), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, chunk, Hkv, hd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, chunk, Hkv, hd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, chunk), lambda b, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, hd), lambda b, j: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),      # running max
            pltpu.VMEM((Hkv, G), jnp.float32),      # running denominator
            pltpu.VMEM((Hkv, G, hd), jnp.float32),  # output accumulator
        ],
    )(qg, k_cache, v_cache, valid)
    return out.reshape(B, H, hd)
