"""Fused single-dispatch encode step (kernels/encode_step.py): decision
parity vs the reference matcher and the composed pallas path, edge sizes,
resumable state, masked padding, tile_d sweeps, the typed kernel-shape
error, and the encode-side measured autotuner (DESIGN.md Sec. 10)."""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.encoder import (encode_decisions, encode_decisions_batched,
                                init_state, matcher_reference)
from repro.kernels.dict_match import TILE_D, KernelShapeError

# TILE_D+-1 straddles the tile boundary; 1 and 255 are the codec's D range
EDGE_D = [1, TILE_D - 1, TILE_D + 1, 255]


def _mixture_blocks(nb, n, dtype=np.float32, seed=0):
    """Hits, misses and FIFO overwrites all occur on this traffic."""
    rng = np.random.default_rng(seed)
    parts = [rng.normal(m, s, size=(nb // 3, n))
             for m, s in [(0, 1), (5, 0.5), (0, 1)]]
    parts.append(rng.normal(0, 1, size=(nb - 3 * (nb // 3), n)))
    return np.concatenate(parts).astype(dtype)


def _assert_same_decisions(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------ decision-parity ring
@pytest.mark.parametrize("num_dict", EDGE_D)
@pytest.mark.parametrize("n", [TILE_D - 1, 24, 256])
def test_fused_matches_reference(num_dict, n):
    # d_crit between KS jump points (multiples of 1/n) so ulp-level
    # arithmetic differences between matchers cannot flip a decision
    d_crit = (int(0.4 * n) + 0.5) / n
    blocks = jnp.asarray(_mixture_blocks(45, n))
    kw = dict(num_dict=num_dict, d_crit=d_crit, rel_tol=0.5)
    ref = encode_decisions(blocks, **kw)
    _assert_same_decisions(ref, encode_decisions(blocks, matcher="fused", **kw))


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_fused_dtype_ring(dtype):
    n = 24
    blocks = jnp.asarray(_mixture_blocks(36, n, dtype=dtype))
    kw = dict(num_dict=7, d_crit=(int(0.4 * n) + 0.5) / n, rel_tol=0.5)
    ref = encode_decisions(blocks, **kw)
    fused = encode_decisions(blocks, matcher="fused", **kw)
    _assert_same_decisions(ref, fused)


@pytest.mark.parametrize("matcher", [None, "ops", "fused"])
def test_constant_and_tied_blocks_decide_as_the_host_oracle(matcher):
    """Blocks with tied values -- runs of zeros, a constant level,
    quantized samples -- make the host walk's decisions on every device
    matcher (a constant block hits an equal stored one)."""
    from repro.core.npref import encode_decisions_np

    rng = np.random.default_rng(4)
    n = 15
    blocks = np.concatenate([
        np.zeros((6, n)), np.full((3, n), 2.0),
        np.round(rng.normal(size=(12, n))),
        np.r_[np.zeros(8), rng.uniform(0.1, 1.0, 7)][None].repeat(4, 0),
    ]).astype(np.float32)
    kw = dict(num_dict=9, d_crit=(int(0.4 * n) + 0.5) / n, rel_tol=0.5)
    want = encode_decisions_np(blocks, **kw)
    got = encode_decisions(jnp.asarray(blocks), matcher=matcher, **kw)
    _assert_same_decisions(want, got)
    assert np.asarray(got[0])[:6].sum() == 5     # zeros hit the first zero


def test_fused_matches_ops():
    """Fused and composed pallas paths share the same kernel arithmetic.

    KS values are multiples of 1/n, so any threshold strictly between two
    jump points decides identically (XLA fusion differences -- e.g. FMA
    contraction -- can move a KS value by one ulp, which only matters for
    a d_crit placed exactly on k/n; ``critical_distance`` never is)."""
    n = 24
    blocks = jnp.asarray(_mixture_blocks(60, n, seed=3))
    for k in (6, 8, 10):  # thresholds mid-gap between k/n and (k+1)/n
        kw = dict(num_dict=9, d_crit=(k + 0.5) / n, rel_tol=0.5)
        _assert_same_decisions(
            encode_decisions(blocks, matcher="ops", **kw),
            encode_decisions(blocks, matcher="fused", **kw))


@pytest.mark.parametrize("use_minmax,use_ks", [(False, True), (True, False)])
def test_fused_ablation_parity(use_minmax, use_ks):
    blocks = jnp.asarray(_mixture_blocks(40, 16, seed=5))
    kw = dict(num_dict=7, d_crit=0.4, rel_tol=0.5,
              use_minmax=use_minmax, use_ks=use_ks)
    _assert_same_decisions(encode_decisions(blocks, **kw),
                           encode_decisions(blocks, matcher="fused", **kw))


def test_minmax_gate_boundary():
    """Exactly-on-threshold extremes (eq. 3 is inclusive) decide the same
    through reference, ops and fused -- boundary values chosen exactly
    representable so all paths see the identical comparison."""
    n = 16
    base = np.linspace(0.0, 1.0, n, dtype=np.float32)  # dmin=0, dmax=1
    on = base.copy()
    on[0], on[-1] = -0.5, 1.5       # exactly dmin - t and dmax + t (r=0.5)
    off = base.copy()
    off[0] = np.nextafter(np.float32(-0.5), np.float32(-1.0))  # just outside
    blocks = jnp.asarray(np.stack([base, on, off]))
    kw = dict(num_dict=3, d_crit=2.0, rel_tol=0.5)  # KS always passes
    ref = encode_decisions(blocks, **kw)
    _assert_same_decisions(ref, encode_decisions(blocks, matcher="fused", **kw))
    _assert_same_decisions(ref, encode_decisions(blocks, matcher="ops", **kw))
    hits = np.asarray(ref[0])
    assert hits[1] and not hits[2]  # inclusive on, exclusive just-outside


def test_minmax_gate_rounds_in_float32():
    """Eq. 3's bound dmin + t rounds to float32 on every path, the numpy
    oracle included: a candidate whose minimum sits on the rounded bound,
    half an ulp above the exact one, hits everywhere.  (A float64 bound in
    the oracle once turned this block into a miss -- micro-PMU voltages
    near 7.2 kV, B=32 -- and the streams diverged from the device's.)"""
    from repro.core.npref import encode_decisions_np

    n = 32
    lo, hi = np.float32(7204.3564), np.float32(7216.563)
    t = (hi - lo) * np.float32(0.5)
    bound = lo + t
    assert float(bound) > float(lo) + float(t)  # rounded up in float32
    entry = np.linspace(lo, hi, n, dtype=np.float32)
    cand = np.linspace(bound, hi, n, dtype=np.float32)
    assert entry[0] == lo and entry[-1] == hi and cand[0] == bound
    blocks = np.stack([entry, cand])
    kw = dict(num_dict=3, d_crit=2.0, rel_tol=0.5)  # KS always passes
    want = encode_decisions_np(blocks, **kw)
    assert want[0][1]
    for matcher in ("reference", "ops", "fused"):
        _assert_same_decisions(
            want, encode_decisions(jnp.asarray(blocks), matcher=matcher, **kw))


# ------------------------------------------------- streaming / masked cases
def test_fused_resumable_state():
    blocks = jnp.asarray(_mixture_blocks(90, 24, seed=7))
    kw = dict(num_dict=7, d_crit=0.4, rel_tol=0.5)
    ref = encode_decisions(blocks, **kw)
    state = init_state(7, 24)
    parts = []
    for lo in range(0, 90, 17):
        out, state = encode_decisions(blocks[lo:lo + 17], matcher="fused",
                                      state=state, **kw)
        parts.append(out)
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(ref[i]),
            np.concatenate([np.asarray(p[i]) for p in parts]))
    # the carry itself matches a reference-matcher scan (same dictionary)
    _, ref_state = encode_decisions(blocks, state=init_state(7, 24), **kw)
    np.testing.assert_array_equal(np.asarray(state.valid),
                                  np.asarray(ref_state.valid))
    np.testing.assert_array_equal(np.asarray(state.sorted_blocks),
                                  np.asarray(ref_state.sorted_blocks))
    assert int(state.count) == int(ref_state.count)


def test_fused_masked_padding_is_noop():
    rng = np.random.default_rng(0)
    blocks = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    kw = dict(num_dict=5, d_crit=0.45, rel_tol=0.5)
    ref = encode_decisions(blocks, matcher="fused", **kw)
    blk2 = jnp.zeros((100, 16), jnp.float32).at[::2].set(blocks)
    valid = np.zeros(100, dtype=bool)
    valid[::2] = True
    out = encode_decisions(blk2, matcher="fused", valid=jnp.asarray(valid),
                           **kw)
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(ref[i]),
                                      np.asarray(out[i])[::2])
        assert not np.any(np.asarray(out[i])[1::2])


def test_fused_batched_channels():
    rng = np.random.default_rng(1)
    bc = jnp.asarray(rng.normal(size=(3, 40, 16)), jnp.float32)
    kw = dict(num_dict=5, d_crit=0.45, rel_tol=0.5)
    _assert_same_decisions(
        sum((list(t) for t in encode_decisions_batched(bc, **kw)), []),
        sum((list(t) for t in encode_decisions_batched(
            bc, matcher="fused", **kw)), []))


def test_codec_pallas_backend_byte_identity():
    """End-to-end: the pallas backend (fused matcher default) emits byte-
    identical streams to the numpy early-exit reference, per mode."""
    from repro.core import IdealemCodec

    rng = np.random.default_rng(4)
    x = np.concatenate([rng.normal(m, s, size=500)
                        for m, s in [(0, 1), (5, 0.5), (0, 1)]])
    for mode, vr in [("std", None), ("residual", (0.0, 360.0)),
                     ("delta", None)]:
        xs = np.mod(np.abs(x) * 40.0, 360.0) if vr else x
        kw = dict(mode=mode, block_size=16, num_dict=31, alpha=0.05,
                  rel_tol=0.5, value_range=vr)
        blob_np = IdealemCodec(backend="numpy", **kw).encode(xs)
        blob_pl = IdealemCodec(backend="pallas", **kw).encode(xs)
        assert blob_np == blob_pl


# ------------------------------------------------------- tile_d parameter
@pytest.mark.parametrize("tile_d", [4, TILE_D, 64])
def test_fused_tile_d_sweep_identical(tile_d):
    blocks = jnp.asarray(_mixture_blocks(45, 24, seed=9))
    kw = dict(num_dict=13, d_crit=0.4, rel_tol=0.5)
    ref = encode_decisions(blocks, matcher="fused", **kw)
    _assert_same_decisions(
        ref, encode_decisions(blocks, matcher=("fused", tile_d), **kw))


def test_dict_match_tile_d_param():
    from repro.kernels.ops import dict_match

    rng = np.random.default_rng(2)
    xs = jnp.sort(jnp.asarray(rng.normal(size=16), jnp.float32))
    dic = jnp.asarray(rng.normal(size=(10, 16)), jnp.float32)
    dmin, dmax = dic.min(axis=1), dic.max(axis=1)
    ks8, mm8 = dict_match(xs, dic, dmin, dmax, rel_tol=0.5)
    ks4, mm4 = dict_match(xs, dic, dmin, dmax, rel_tol=0.5, tile_d=4)
    np.testing.assert_array_equal(np.asarray(ks8), np.asarray(ks4))
    np.testing.assert_array_equal(np.asarray(mm8), np.asarray(mm4))


def test_kernel_shape_error_is_typed():
    from repro.kernels.dict_match import dict_match_pallas
    from repro.kernels.encode_step import encode_step_pallas

    rng = np.random.default_rng(0)
    xs = jnp.sort(jnp.asarray(rng.normal(size=8), jnp.float32))
    dic = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    mn = mx = jnp.zeros(5, jnp.float32)
    with pytest.raises(KernelShapeError) as ei:
        dict_match_pallas(xs, dic, mn, mx, 0.5)
    assert "D=5" in str(ei.value) and "3 more row" in str(ei.value)
    assert isinstance(ei.value, ValueError)  # typed, but still a ValueError
    with pytest.raises(KernelShapeError):
        encode_step_pallas(xs, dic, mn, mx, jnp.zeros(5, bool),
                           jnp.int32(0), jnp.asarray(True), d_crit=0.4,
                           rel_tol=0.5, num_dict=5)


# ------------------------------------------------------ measured autotuner
def test_encode_autotune_lifecycle(tmp_path, monkeypatch):
    from repro.core import encoder as enc
    from repro.core.tuning import AutotuneCacheError

    path = str(tmp_path / "encode_autotune.json")
    monkeypatch.setenv("REPRO_ENCODE_AUTOTUNE", path)
    enc.reset_encode_autotune()
    blocks = jnp.asarray(_mixture_blocks(12, 16, seed=1))
    kw = dict(num_dict=5, d_crit=0.4, rel_tol=0.5)
    assert not enc.encode_autotune_cached(5, 16, np.float32)
    ref = encode_decisions(blocks, **kw)
    out = encode_decisions(blocks, matcher="auto", **kw)
    _assert_same_decisions(ref, out)  # whatever won, decisions agree
    assert enc.encode_autotune_cached(5, 16, np.float32)
    assert os.path.exists(path)
    doc = json.load(open(path))
    assert doc["version"] == enc.ENCODE_AUTOTUNE_VERSION
    (key, ent), = doc["entries"].items()
    assert ent["matcher"] in enc.MATCHERS and "times_us" in ent

    # persisted choice survives a reset + reload; a second resolve is a hit
    enc.reset_encode_autotune()
    assert enc.load_encode_autotune(path) == 1
    assert enc.encode_autotune_choices()[key] == ent["matcher"]
    probes_before = enc._TUNER.stats["probes"]
    encode_decisions(blocks, matcher="auto", **kw)
    assert enc._TUNER.stats["probes"] == probes_before  # served from cache

    # stale version: strict load raises, non-strict discards and re-probes
    doc["version"] = 999
    with open(path, "w") as f:
        json.dump(doc, f)
    enc.reset_encode_autotune()
    with pytest.raises(AutotuneCacheError):
        enc.load_encode_autotune(path)
    enc.reset_encode_autotune()
    assert enc.load_encode_autotune(path, strict=False) == 0
    enc.reset_encode_autotune()


def test_unknown_matcher_rejected():
    blocks = jnp.asarray(_mixture_blocks(6, 16))
    with pytest.raises(ValueError, match="unknown matcher"):
        encode_decisions(blocks, num_dict=3, d_crit=0.4, matcher="warp")
    from repro.core import IdealemCodec
    with pytest.raises(ValueError, match="matcher"):
        IdealemCodec(matcher="warp")
