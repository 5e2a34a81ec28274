"""The error bound as a runtime operand of the encode scan, and the
demotion counter it feeds (DESIGN.md Sec. 11).

Residual, error-bounded streams of a small field, fed in ragged chunks
through an in-process ``ServeFrontend`` under three different bounds:

* the bytes equal a stream assembled from the host reference walk
  (``npref.encode_decisions_np`` with the bound, via the numpy backend);
* every decoded sample is within the bound, plus the float32 rounding
  the stream format adds (stated with ``_slop``);
* the direct scan traces once per feed shape, whatever the bound;
* ``repro_encode_bound_demotions_total`` equals a numpy count of blocks
  whose min/max-passing entries the bound excluded, every one.
"""
import asyncio

import numpy as np
import pytest

from repro import api, obs
from repro.core import IdealemCodec
from repro.serve import FrontendClient, ServeFrontend

LEVELS, NY, NX = 3, 16, 64          # a few levels of a 16 x 64 field
SNAPSHOTS = 2
BOUNDS = (0.02, 0.1, 0.5)
# an alpha no other test uses, so that the jit cache holds no program of
# this codec before the first stream here is fed
CODEC = dict(mode="residual", block_size=16, num_dict=16, alpha=0.037,
             rel_tol=0.5)


def _field(seed: int) -> np.ndarray:
    """(SNAPSHOTS, LEVELS, NY * NX) float32: a smooth random field with a
    zero patch in every level (sparse hydrometeor-like rows hit there)."""
    rng = np.random.default_rng(seed)
    x = np.cumsum(np.cumsum(rng.normal(0, 0.05, (SNAPSHOTS, LEVELS, NY, NX)),
                            axis=-1), axis=-2)
    x[..., NY // 2:, : NX // 2] = 0.0
    return x.reshape(SNAPSHOTS, LEVELS, NY * NX).astype(np.float32)


def _chunks(n: int, seed: int):
    """Ragged feed sizes covering ``n`` samples."""
    rng = np.random.default_rng(seed)
    cuts, i = [], 0
    while i < n:
        step = int(rng.choice([200, 333, 512]))
        cuts.append((i, min(i + step, n)))
        i += step
    return cuts


def _counter(name: str, **labels) -> float:
    for v in obs.registry().snapshot().get(name, {}).get("values", []):
        if v["labels"] == labels:
            return float(v["value"])
    return 0.0


def _slop(y: np.ndarray, x: np.ndarray, bound: float) -> np.ndarray:
    """What the stream format adds to the bound, per sample: the decoded
    value is the f32 sum base + residual (half an ulp of it), the stored
    residual is the f32 difference x - base (half an ulp of it), and the
    gate compares against the bound rounded to f32 (one ulp of it)."""
    base = np.repeat(x.reshape(-1, CODEC["block_size"])[:, :1],
                     CODEC["block_size"], axis=1).reshape(-1)
    res = (x - base).astype(np.float32)
    return (0.5 * np.spacing(np.abs(y)) + 0.5 * np.spacing(np.abs(res))
            + np.spacing(np.float32(bound))).astype(np.float64)


def _demotions(series, cuts, bound: float) -> int:
    """Numpy count of the demotion definition over one stream: a block
    (fed in ``cuts``) some valid entry of which passed the f32 min/max gate
    while the f32 bound excluded every such entry.  The dictionary is
    replayed from the reference walk's own decisions."""
    from repro.core.npref import encode_decisions_np, np_init_state

    codec = IdealemCodec(**CODEC, error_bound=bound)
    B, D = codec.block_size, codec.num_dict
    st = np_init_state(D)
    rows = [None] * D
    r32, b32 = np.float32(codec.rel_tol), np.float32(bound)
    tail, count = np.zeros(0, np.float32), 0
    for lo, hi in cuts:
        joined = np.concatenate([tail, series[lo:hi]])
        nb = len(joined) // B
        tail = joined[nb * B:]
        if nb == 0:
            continue
        p, _ = codec._transform(joined[:nb * B].reshape(nb, B))
        (hit, slot, _o), st = encode_decisions_np(
            p, num_dict=D, d_crit=codec.d_crit, rel_tol=codec.rel_tol,
            error_bound=bound, state=st)
        for i in range(nb):
            kept = [r for r in rows if r is not None]
            if kept:
                R = np.stack(kept)
                lo_, hi_ = R.min(axis=1), R.max(axis=1)
                t = (hi_ - lo_) * r32
                mn, mx = p[i].min(), p[i].max()
                mm = ((lo_ - t <= mn) & (mn <= lo_ + t)
                      & (hi_ - t <= mx) & (mx <= hi_ + t))
                ok = np.max(np.abs(p[i][None, :] - R), axis=1) <= b32
                count += bool(mm.any() and not (mm & ok).any())
            if not hit[i]:
                rows[slot[i]] = p[i].copy()
    return count


@pytest.fixture(scope="module")
def served():
    """Every stream served once: per bound, the bytes of each level's
    stream, the counters' growth, and the chunking."""
    field = _field(seed=15)
    series = field.transpose(1, 0, 2).reshape(LEVELS, -1)
    cuts = _chunks(series.shape[1], seed=16)

    async def main():
        out = {}
        async with ServeFrontend(run_control=False) as fe:
            async with FrontendClient(fe.host, fe.port, "isabel") as c:
                for bound in BOUNDS:
                    cfg = api.CodecConfig(**CODEC, error_bound=bound,
                                          backend="pallas", matcher="fused")
                    t0 = _counter("repro_encode_scan_traces_total",
                                  path="direct")
                    d0 = _counter("repro_encode_bound_demotions_total",
                                  path="direct")
                    segs = {z: [] for z in range(LEVELS)}
                    for z in range(LEVELS):
                        await c.open(f"l{z}", cfg, dtype="float32")
                    for lo, hi in cuts:
                        for z in range(LEVELS):
                            res = await c.feed(f"l{z}", series[z, lo:hi])
                            segs[z].append(res.segment)
                    for z in range(LEVELS):
                        segs[z].append((await c.close_stream(f"l{z}")).segment)
                    out[bound] = {
                        "streams": [b"".join(segs[z]) for z in range(LEVELS)],
                        "traces": _counter("repro_encode_scan_traces_total",
                                           path="direct") - t0,
                        "demotions": _counter(
                            "repro_encode_bound_demotions_total",
                            path="direct") - d0}
        return out

    return series, cuts, asyncio.run(main())


@pytest.mark.parametrize("bound", BOUNDS)
def test_bytes_equal_the_reference_walk(served, bound):
    series, cuts, out = served
    ref = IdealemCodec(**CODEC, error_bound=bound, backend="numpy")
    for z in range(LEVELS):
        s = ref.session(dtype=np.float32)
        want = b"".join([s.feed(series[z, lo:hi]) for lo, hi in cuts]
                        + [s.finish()])
        assert out[bound]["streams"][z] == want, (bound, z)


@pytest.mark.parametrize("bound", BOUNDS)
def test_decoded_samples_within_the_bound(served, bound):
    series, _cuts, out = served
    codec = IdealemCodec(**CODEC, error_bound=bound)
    for z in range(LEVELS):
        x = series[z]
        y = codec.decode(out[bound]["streams"][z])
        assert y.dtype == np.float32 and len(y) == len(x)
        err = np.abs(y.astype(np.float64) - x.astype(np.float64))
        assert np.all(err <= bound + _slop(y, x, bound)), (bound, z)


def test_one_trace_per_feed_shape_whatever_the_bound(served):
    series, cuts, out = served
    B = CODEC["block_size"]
    shapes, tail = set(), 0
    for lo, hi in cuts:
        nb = (tail + hi - lo) // B
        tail = (tail + hi - lo) % B
        if nb:
            shapes.add(nb)
    first, *others = BOUNDS
    assert out[first]["traces"] == len(shapes) > 1
    assert all(out[b]["traces"] == 0 for b in others)


@pytest.mark.parametrize("bound", BOUNDS)
def test_demotion_counter_equals_a_numpy_count(served, bound):
    series, cuts, out = served
    want = sum(_demotions(series[z], cuts, bound) for z in range(LEVELS))
    assert out[bound]["demotions"] == want
    if bound == BOUNDS[0]:
        assert want > 0          # the tight bound does demote
