"""Streaming architecture tests: resumable encoder state, chunked session
equivalence to one-shot encoding, batched multi-channel sessions, and the
serve-layer CompressionService."""
import numpy as np
import pytest

from repro.core import IdealemCodec
from repro.core.npref import encode_decisions_np, np_init_state
from repro.core.stream import decode_stream, parse_stream

CHUNKINGS = [
    [1_000_000],                 # everything at once
    [7, 16, 100, 1_000_000],     # sub-block then large
    [256] * 100,                 # uniform
    [1, 31, 32, 33, 999, 1_000_000],
]


def _mixed(n, seed=0):
    rng = np.random.default_rng(seed)
    # mixture of sources => hits, misses and overwrites all occur
    parts = [rng.normal(m, s, size=n // 3) for m, s in [(0, 1), (5, 0.5), (0, 1)]]
    return np.concatenate(parts + [rng.normal(0, 1, size=n - 3 * (n // 3))])


def _take(x, sizes):
    out, lo = [], 0
    for s in sizes:
        out.append(x[lo:lo + s])
        lo += s
        if lo >= len(x):
            break
    return out


# -------------------------------------------------- resumable encoder state
@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_chunked_decisions_match_one_shot(backend):
    """Threading the dictionary carry across chunks must reproduce the
    decisions of a single scan over the concatenated blocks."""
    rng = np.random.default_rng(7)
    blocks = np.concatenate([
        rng.normal(m, s, size=(30, 24)) for m, s in [(0, 1), (5, 0.5), (0, 1)]
    ]).astype(np.float32)
    kw = dict(num_dict=7, d_crit=0.4, rel_tol=0.5)

    if backend == "numpy":
        ref = encode_decisions_np(blocks, **kw)
        state = np_init_state(kw["num_dict"])
        parts = [encode_decisions_np(blocks[lo:lo + 17], state=state, **kw)[0]
                 for lo in range(0, len(blocks), 17)]
    else:
        import jax.numpy as jnp
        from repro.core.encoder import encode_decisions, init_state
        matcher = None
        if backend == "pallas":
            from repro.kernels.ops import dict_match
            matcher = dict_match
        jb = jnp.asarray(blocks)
        ref = encode_decisions(jb, matcher=matcher, **kw)
        state = init_state(kw["num_dict"], blocks.shape[-1])
        parts = []
        for lo in range(0, len(blocks), 17):
            out, state = encode_decisions(jb[lo:lo + 17], matcher=matcher,
                                          state=state, **kw)
            parts.append(out)
    for i in range(3):
        got = np.concatenate([np.asarray(p[i]) for p in parts])
        np.testing.assert_array_equal(np.asarray(ref[i]), got)


def test_batched_state_matches_per_channel():
    """(C, nb, n) blocks with per-channel DictState == C independent scans."""
    import jax.numpy as jnp
    from repro.core.encoder import (encode_decisions,
                                    encode_decisions_batched, init_state)
    rng = np.random.default_rng(1)
    blocks = jnp.asarray(rng.normal(size=(3, 40, 16)), jnp.float32)
    kw = dict(num_dict=5, d_crit=0.45, rel_tol=0.5)
    state = init_state(5, 16, channels=3)
    (h, s, o), state2 = encode_decisions_batched(blocks, state=state, **kw)
    assert h.shape == (3, 40) and state2.sorted_blocks.shape == (3, 5, 16)
    for ci in range(3):
        hc, sc, oc = encode_decisions(blocks[ci], **kw)
        np.testing.assert_array_equal(np.asarray(h[ci]), np.asarray(hc))
        np.testing.assert_array_equal(np.asarray(s[ci]), np.asarray(sc))
        np.testing.assert_array_equal(np.asarray(o[ci]), np.asarray(oc))


@pytest.mark.parametrize("matcher", ["reference", "fused"])
@pytest.mark.parametrize("chunks", [[40], [13, 7, 20]])
def test_batched_chunks_match_chained_channels(matcher, chunks):
    """Chunked batched calls over C=3 channels with a ragged ``valid`` mask
    give the decisions and carry of chaining each channel's own
    ``encode_decisions`` calls over the same chunks and mask (the batched
    scan is one jitted vmap of the same per-channel body)."""
    import jax.numpy as jnp
    from repro.core.encoder import (encode_decisions,
                                    encode_decisions_batched, init_state)
    C, nb, n = 3, sum(chunks), 16
    blocks = jnp.asarray(np.stack([_mixed(nb * n, seed=ci).reshape(nb, n)
                                   for ci in range(C)]), jnp.float32)
    # ragged: channel ci's real blocks stop short of each chunk's end
    valid = np.ones((C, nb), bool)
    lo = 0
    for size in chunks:
        for ci in range(C):
            valid[ci, lo + max(size - 2 * ci, 1):lo + size] = False
        lo += size
    valid = jnp.asarray(valid)
    kw = dict(num_dict=5, d_crit=0.45, rel_tol=0.5, matcher=matcher)
    state = init_state(5, n, channels=C)
    per = [init_state(5, n) for _ in range(C)]
    got, want = [], [[] for _ in range(C)]
    lo = 0
    for size in chunks:
        sl = slice(lo, lo + size)
        out, state = encode_decisions_batched(
            blocks[:, sl], state=state, valid=valid[:, sl], **kw)
        got.append(out)
        for ci in range(C):
            o, per[ci] = encode_decisions(blocks[ci, sl], state=per[ci],
                                          valid=valid[ci, sl], **kw)
            want[ci].append(o)
        lo += size
    for i in range(3):
        g = np.concatenate([np.asarray(o[i]) for o in got], axis=1)
        for ci in range(C):
            np.testing.assert_array_equal(
                g[ci], np.concatenate([np.asarray(o[i]) for o in want[ci]]))
    for field in state._fields:
        for ci in range(C):
            np.testing.assert_array_equal(
                np.asarray(getattr(state, field))[ci],
                np.asarray(getattr(per[ci], field)))


# ----------------------------------------------- session chunked == one-shot
@pytest.mark.parametrize("mode,num_dict", [
    ("std", 255), ("std", 3), ("std", 1),
    ("residual", 255), ("residual", 1),
    ("delta", 3), ("delta", 1),
])
@pytest.mark.parametrize("chunking", CHUNKINGS)
def test_session_chunked_decodes_like_one_shot(mode, num_dict, chunking):
    """Acceptance: any chunk split through feed()/finish() decodes to exactly
    the bytes one-shot encode decodes to, with dictionary state preserved."""
    vr = (0.0, 360.0) if mode != "std" else None
    x = _mixed(16 * 150 + 9, seed=2)
    if vr:
        x = np.mod(np.abs(x) * 40.0, 360.0)
    c = IdealemCodec(mode=mode, block_size=16, num_dict=num_dict, alpha=0.05,
                     rel_tol=0.5, value_range=vr, backend="numpy")
    one_shot = c.encode(x)
    y_ref = c.decode(one_shot)

    s = c.session()
    segs = [s.feed(ch) for ch in _take(x, chunking)]
    segs.append(s.finish())
    blob = b"".join(segs)
    y = c.decode(blob)
    np.testing.assert_array_equal(y_ref, y)

    # dictionary state (and therefore hit rate) is preserved across chunks
    _, ev_ref = parse_stream(one_shot)
    _, ev = parse_stream(blob)
    kinds_ref = [(e["kind"], e["slot"]) for e in ev_ref]
    kinds = [(e["kind"], e["slot"]) for e in ev]
    assert kinds_ref == kinds


def test_session_single_feed_bytes_equal_one_shot():
    """A one-feed buffered session is the one-shot path: byte-equal output."""
    x = _mixed(32 * 80 + 3, seed=5)
    c = IdealemCodec(mode="std", block_size=32, num_dict=31, alpha=0.05,
                     rel_tol=0.5, backend="numpy")
    s = c.session(emit_segments=False)
    s.feed(x)
    assert s.finish() == c.encode(x)


def test_session_hit_rate_preserved_vs_naive_chunking():
    """The whole point of the carry: chunked sessions keep the one-shot hit
    rate while naive per-chunk encodes rebuild the dictionary and lose it."""
    x = _mixed(32 * 400, seed=9)
    c = IdealemCodec(mode="std", block_size=32, num_dict=255, alpha=0.05,
                     rel_tol=0.5, backend="numpy")
    one = c.encode_stats(x)

    s = c.session()
    for lo in range(0, len(x), 640):
        s.feed(x[lo:lo + 640])
    s.finish()
    assert s.stats.blocks == one["blocks"]
    assert s.stats.hits == one["hits"]  # identical decisions => identical hits

    naive_hits = sum(c.encode_stats(x[lo:lo + 640])["hits"]
                     for lo in range(0, len(x), 640))
    assert naive_hits < one["hits"]  # the naive path must lose hits


def test_session_multi_channel_segments():
    rng = np.random.default_rng(4)
    C = 3
    chans = np.stack([rng.normal(i, 1.0, size=16 * 60 + 5) for i in range(C)])
    c = IdealemCodec(mode="std", block_size=16, num_dict=31, alpha=0.05,
                     rel_tol=0.5)
    s = c.session(channels=C)
    parts = [s.feed(chans[:, :333]), s.feed(chans[:, 333:]), s.finish()]
    for ci in range(C):
        blob = b"".join(p[ci] for p in parts)
        np.testing.assert_array_equal(c.decode(blob),
                                      c.decode(c.encode(chans[ci])))
    assert all(st.blocks == 60 for st in s.stats)


def test_session_misuse_raises():
    c = IdealemCodec(mode="std", block_size=16, num_dict=3, backend="numpy")
    s = c.session()
    with pytest.raises(ValueError):
        s.feed(np.zeros((2, 16)))  # 2-D chunk into a single-channel session
    s.finish()
    with pytest.raises(RuntimeError):
        s.feed(np.zeros(16))
    with pytest.raises(RuntimeError):
        s.finish()


# ------------------------------------------------------- serve-layer service
def test_compression_service_lifecycle():
    from repro.serve.compress import CompressionService
    rng = np.random.default_rng(0)
    x = rng.normal(size=32 * 120 + 11)
    svc = CompressionService(mode="std", block_size=32, num_dict=255,
                             alpha=0.01, rel_tol=0.5, backend="numpy")
    svc.open_stream("a")
    svc.open_stream("b", num_dict=3)
    with pytest.raises(KeyError):
        svc.open_stream("a")
    segs = [svc.feed("a", x[:1000]), svc.feed("a", x[1000:]),
            svc.close_stream("a")]
    y = decode_stream(b"".join(segs))
    codec = IdealemCodec(mode="std", block_size=32, num_dict=255, alpha=0.01,
                         rel_tol=0.5, backend="numpy")
    np.testing.assert_array_equal(y, codec.decode(codec.encode(x)))
    # stats survive close; unknown streams raise
    assert svc.stats("a")["blocks"] == 120
    assert "a" not in svc.active_streams and "b" in svc.active_streams
    with pytest.raises(KeyError):
        svc.feed("a", x)
    svc.feed("b", x[:100])
    assert svc.stats()["blocks"] >= 120
    svc.close_stream("b")


# ------------------------------------------------ time-based flush trigger
def test_flush_policy_deadline_is_pure():
    """max_age_s trips on the reported age alone -- no wall clock, and only
    when something is actually staged."""
    from repro.serve import FlushPolicy
    p = FlushPolicy(max_batch_blocks=100, max_batch_streams=10, max_age_s=2.0)
    assert not p.should_flush(1, 5, age_s=1.9)
    assert p.should_flush(1, 5, age_s=2.0)
    assert not p.should_flush(0, 0, age_s=50.0)  # nothing ready: no flush
    assert p.should_flush(1, 100, age_s=None)    # count triggers still work
    # age is optional: legacy two-argument callers are unaffected
    assert not FlushPolicy(max_age_s=0.1).should_flush(1, 1)


def test_coalescer_deadline_flush_injected_clock():
    """The coalescer measures batch age with an injectable clock: old
    staged payloads flush via poll()/submit() without count pressure."""
    from repro.serve import FlushPolicy
    from repro.serve.compress import StreamCoalescer
    t = [0.0]
    co = StreamCoalescer(
        policy=FlushPolicy(max_age_s=2.0, max_batch_blocks=10 ** 9,
                           max_batch_streams=10 ** 9),
        clock=lambda: t[0], mode="std", block_size=16, num_dict=8,
        alpha=0.05, rel_tol=0.5, backend="jax")
    rng = np.random.default_rng(0)
    co.open_stream("a")
    co.open_stream("b")
    assert co.submit("a", rng.normal(size=100)) is None  # batch born at t=0
    t[0] = 1.0
    assert co.submit("b", rng.normal(size=50)) is None
    assert co.poll() is None                   # oldest age 1.0 < 2.0
    t[0] = 2.5
    out = co.poll()                            # deadline expired
    assert out is not None and set(out) == {"a", "b"}
    y = decode_stream(out["a"] + co.close_stream("a"))
    assert len(y) == 100
    assert co.poll() is None                   # rearmed: nothing staged

    # sub-block staging alone must not trip the deadline (nothing to cut)
    co.submit("b", rng.normal(size=3))
    t[0] = 10.0
    assert co.poll() is None

    # a partial flush (close_stream) must not leave survivors aged by the
    # departed stream's older submissions
    co.open_stream("c")
    t[0] = 20.0
    co.submit("b", rng.normal(size=40))   # b staged at t=20
    t[0] = 21.5
    co.submit("c", rng.normal(size=40))   # c staged at t=21.5
    co.close_stream("b")
    t[0] = 22.5
    assert co.poll() is None              # c is only 1.0s old, not 2.5s
    t[0] = 23.6
    out = co.poll()                       # now c's own age crossed 2.0
    assert out is not None and set(out) == {"c"}
