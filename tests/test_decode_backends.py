"""Unified decode engine (ISSUE 4): backend parity and the fallback rule.

The load-bearing acceptance ring: for every golden mode x D case, every
backend in ``repro.core.decode.BACKENDS`` must reconstruct BYTE-identically
to the host path -- full decodes, random single ranges, and batched
multi-range plans.  A device backend the exactness probe finds inexact is
routed to the host, logged and counted; a device backend that raises is a
typed ``DeviceBackendError``, never a silent host result: a fallback that
pretended to be a device result would make the parity sweep vacuous.
"""
import logging
import zlib

import numpy as np
import pytest

from conftest import GOLDEN_CASES, golden_codec_kwargs, golden_signal
from repro.core import IdealemCodec
from repro.core import decode as decode_mod
from repro.core.decode import DecodePlan, PlanPart, pad_parts, reconstruct
from repro.core.stream import decode_stream
from repro.errors import DeviceBackendError, ReproError
from repro.serve import DecompressionService, FlushPolicy
from repro.store import Container, decode_range, decode_ranges, pack
from test_golden_corpus import _golden_bytes

BACKENDS = ["numpy", "jax", "pallas"]
DEVICE_BACKENDS = ["jax", "pallas"]
FEED = 100


def _session_stream(name, feed=FEED):
    codec = IdealemCodec(**golden_codec_kwargs(name))
    x = golden_signal(name)
    s = codec.session()
    segs = [s.feed(x[lo:lo + feed]) for lo in range(0, len(x), feed)]
    segs.append(s.finish())
    return b"".join(segs)


_PREPPED = {}


def _prepped(name):
    if name not in _PREPPED:
        blob = _session_stream(name)
        _PREPPED[name] = (blob, Container(pack(blob)), decode_stream(blob))
    return _PREPPED[name]


# ------------------------------------------------------------ parity sweep
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(GOLDEN_CASES))
def test_full_decode_parity(name, backend):
    """decode_stream on every backend == the host decode, bytes-for-bytes
    (one-shot golden stream AND the chunked multi-segment form)."""
    blob = _golden_bytes(name)
    want = decode_stream(blob)
    got = decode_stream(blob, backend=backend)
    assert got.tobytes() == want.tobytes()
    sblob, _, swant = _prepped(name)
    assert decode_stream(sblob, backend=backend).tobytes() == swant.tobytes()


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(GOLDEN_CASES))
def test_range_decode_parity(name, backend):
    """Random single ranges through the container on every backend equal
    the host full decode's slices."""
    _, store, y = _prepped(name)
    nb = store.total_blocks(0)
    B = store.header_of(int(store.chunks_of(0)[0])).block_size
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    ranges = [(0, nb), (0, 1), (nb - 1, nb)]
    ranges += [sorted((int(a), int(a) + int(b) + 1))
               for a, b in zip(rng.integers(0, nb - 1, size=6),
                               rng.integers(0, 8, size=6))]
    for i, j in ranges:
        j = min(j, nb)
        got = decode_range(store, i, j, backend=backend)
        assert got.tobytes() == y[i * B:j * B].tobytes(), (name, backend, i, j)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(GOLDEN_CASES))
def test_batched_ranges_parity(name, backend):
    """Many ragged requests in ONE padded plan/dispatch, on every backend."""
    _, store, y = _prepped(name)
    nb = store.total_blocks(0)
    B = store.header_of(int(store.chunks_of(0)[0])).block_size
    reqs = [(0, 0, nb), (0, 3, 5), (0, nb - 1, nb), (0, 7, 29),
            (0, nb // 2, nb // 2 + 1)]
    for (_, i, j), got in zip(reqs, decode_ranges(store, reqs,
                                                  backend=backend)):
        assert got.tobytes() == y[i * B:j * B].tobytes(), (name, backend, i, j)


def test_device_backends_actually_ran_on_device():
    """The sweep above is vacuous if every device call silently fell back;
    on the CPU harness the probe must pass and route to the device path."""
    decode_mod.reset_decode_stats()
    blob = _golden_bytes("delta_D32")
    want = decode_stream(blob)
    for backend in DEVICE_BACKENDS:
        assert decode_stream(blob, backend=backend).tobytes() == want.tobytes()
    stats = decode_mod.decode_stats()
    assert stats["device_calls"] == len(DEVICE_BACKENDS)
    assert stats["fallbacks"] == 0


def test_unknown_backend_rejected():
    blob = _golden_bytes("std_D1")
    with pytest.raises(ValueError, match="unknown decode backend"):
        decode_stream(blob, backend="tpu9000")


# ------------------------------------------------------- fallback contract
def test_fallback_is_logged_and_exact(monkeypatch, caplog):
    """A device backend that fails the exactness probe must (a) log the
    decision, (b) count it in decode_stats, and (c) still return the
    byte-exact host result."""
    blob = _golden_bytes("delta_D32")
    want = decode_stream(blob)

    def broken_run_device(plan, backend):
        out = decode_mod._reconstruct_numpy(plan).copy()
        out += 1e-9  # byte-wrong, numerically plausible
        return out

    monkeypatch.setattr(decode_mod, "_run_device", broken_run_device)
    monkeypatch.setattr(decode_mod, "_exact_cache", {})
    decode_mod.reset_decode_stats()
    with caplog.at_level(logging.WARNING, logger="repro.core.decode"):
        got = decode_stream(blob, backend="jax")
    assert got.tobytes() == want.tobytes()
    assert decode_mod.decode_stats()["fallbacks"] == 1
    assert decode_mod.decode_stats()["device_calls"] == 0
    assert any("not byte-exact" in r.message for r in caplog.records)


def test_crashing_device_backend_falls_back(monkeypatch):
    """A device backend whose probe raises does NOT fall back: the call
    raises the typed error and nothing is counted as served."""
    blob = _golden_bytes("std_D32")

    def crashing(plan, backend):
        raise RuntimeError("no f64 on this accelerator")

    monkeypatch.setattr(decode_mod, "_run_device", crashing)
    monkeypatch.setattr(decode_mod, "_exact_cache", {})
    decode_mod.reset_decode_stats()
    with pytest.raises(DeviceBackendError, match="failed its probe"):
        decode_stream(blob, backend="pallas")
    stats = decode_mod.decode_stats()
    assert stats["fallbacks"] == stats["host_calls"] == 0
    assert stats["device_calls"] == 0


def test_dispatch_failure_serves_from_host(monkeypatch):
    """The probe can pass while the REAL (bigger) dispatch fails -- device
    OOM, shape-specific compile error.  An explicit device backend then
    raises the typed error (a ``ReproError`` with a wire code): the call
    is neither served from the host nor counted as a device call."""
    blob = _golden_bytes("delta_D32")
    real_run = decode_mod._run_device

    def flaky(plan, backend):
        if plan.nb > 16:  # probe plans are 16 blocks; real calls are bigger
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return real_run(plan, backend)

    monkeypatch.setattr(decode_mod, "_run_device", flaky)
    monkeypatch.setattr(decode_mod, "_exact_cache", {})
    decode_mod.reset_decode_stats()
    with pytest.raises(DeviceBackendError, match="failed at dispatch") as ei:
        decode_stream(blob, backend="jax")
    assert isinstance(ei.value, ReproError)
    assert ei.value.code == "device_backend"
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    stats = decode_mod.decode_stats()
    assert stats["device_calls"] == 0
    assert stats["fallbacks"] == stats["host_calls"] == 0


def test_auto_routes_f64_to_host_counted(monkeypatch):
    """On a device without f64 (the TPU), ``auto`` routes f64 plans to the
    host by rule -- counted as ``host_routes``, never probed or caught --
    while an explicit device backend refuses them with the typed error.
    The device's lack of f64 is simulated here; f32 plans still run on
    the device."""
    monkeypatch.setattr(decode_mod, "_device_has_dtype",
                        lambda dt: np.dtype(dt) != np.float64)
    calls = []
    real_run = decode_mod._run_device

    def spy(plan, backend):
        calls.append(np.dtype(plan.dtype))
        return real_run(plan, backend)

    monkeypatch.setattr(decode_mod, "_run_device", spy)
    decode_mod.reset_decode_stats()
    plan = decode_mod._probe_plan(decode_mod.MODE_DELTA, np.float64, None, 32)
    want = decode_mod._reconstruct_numpy(plan)
    for _ in range(2):
        assert reconstruct(plan, backend="auto").tobytes() == want.tobytes()
    stats = decode_mod.decode_stats()
    assert stats["host_routes"] == 2 and stats["host_calls"] == 2
    assert stats["autotune_probes"] == 0 and stats["fallbacks"] == 0
    assert calls == []  # the device never saw the f64 plan
    with pytest.raises(DeviceBackendError, match="no exact float64"):
        reconstruct(plan, backend="pallas")
    plan32 = decode_mod._probe_plan(decode_mod.MODE_DELTA, np.float32, None,
                                    32)
    got = reconstruct(plan32, backend="jax")
    assert got.tobytes() == decode_mod._reconstruct_numpy(plan32).tobytes()
    assert decode_mod.decode_stats()["device_calls"] == 1


def test_fallback_probe_runs_once_per_combination(monkeypatch):
    """The probe is cached: a failing combination probes the device once,
    then every later call raises the cached typed error without touching
    the device again."""
    calls = []

    def crashing(plan, backend):
        calls.append(backend)
        raise RuntimeError("boom")

    monkeypatch.setattr(decode_mod, "_run_device", crashing)
    monkeypatch.setattr(decode_mod, "_exact_cache", {})
    blob = _golden_bytes("std_D32")
    for _ in range(3):
        with pytest.raises(DeviceBackendError, match="boom"):
            decode_stream(blob, backend="jax")
    assert calls == ["jax"]


# ------------------------------------------------- engine-internal parity
def test_seq_cumsum_kernels_match_numpy_bitwise():
    """The delta-mode exactness story: XLA's associative cumsum is NOT
    byte-exact in f64, the sequential fori_loop and the pallas kernel are."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.seq_cumsum import seq_cumsum

    rng = np.random.default_rng(0)
    for dtype, rows in [(np.float64, 37), (np.float32, 8), (np.float64, 1)]:
        x = rng.normal(0, 100, size=(rows, 31)).astype(dtype)
        x[0, 0] = -0.0  # a leading -0.0 must survive bit-for-bit
        want = np.cumsum(x, axis=1)
        with jax.enable_x64(True):
            got = np.asarray(seq_cumsum(jnp.asarray(x)))
            assert got.tobytes() == want.tobytes(), dtype
            xla = np.asarray(jnp.cumsum(jnp.asarray(x), axis=1))
        if dtype is np.float64 and rows > 8:
            # the premise for the kernel: plain XLA cumsum drifts
            assert xla.tobytes() != want.tobytes()


def test_pad_parts_padding_is_inert():
    """Pad blocks (all-miss, zero payload, block_idx 0) must not perturb
    the real blocks on any backend."""
    rng = np.random.default_rng(5)
    B = 8
    rows_a = rng.normal(size=(5, B - 1))
    rows_b = rng.normal(size=(2, B - 1))
    parts = [
        PlanPart(rows=rows_a, bases=rng.normal(size=5),
                 is_hit=np.array([False, True, False, True, True]),
                 block_idx=np.arange(10, 15)),
        PlanPart(rows=rows_b, bases=rng.normal(size=2),
                 is_hit=np.array([False, False]),
                 block_idx=np.arange(2)),
    ]
    plan, nbm = pad_parts(decode_mod.MODE_DELTA, B, np.float64, None, parts)
    assert nbm == 5
    for backend in BACKENDS:
        out = reconstruct(plan, backend=backend).reshape(2, nbm, B)
        solo = [reconstruct(pad_parts(decode_mod.MODE_DELTA, B, np.float64,
                                      None, [p])[0], backend=backend)
                for p in parts]
        assert out[0, :5].tobytes() == solo[0].tobytes()
        assert out[1, :2].tobytes() == solo[1].tobytes()


def test_empty_plan_reconstructs_empty():
    plan = DecodePlan(
        mode=decode_mod.MODE_STD, block_size=4, dtype=np.dtype(np.float64),
        value_range=None, payloads=np.zeros((0, 4)),
        src=np.zeros(0, np.int64), bases=None, is_hit=np.zeros(0, bool),
        block_idx=np.zeros(0, np.int64))
    for backend in BACKENDS:
        assert reconstruct(plan, backend=backend).shape == (0, 4)


# --------------------------------------------------- serving read parity
@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_decompression_service_device_flush(backend):
    """A device-backed service flush merges compatible requests -- across
    TWO attached stores -- into one dispatch and answers byte-identically
    to the host service."""
    blob = _session_stream("delta_D32")
    y = decode_stream(blob)
    packed = pack(blob)
    svc = DecompressionService(policy=FlushPolicy(max_batch_streams=4),
                               backend=backend)
    svc.attach("a", packed)
    svc.attach("b", packed)
    nb = Container(packed).total_blocks(0)
    assert svc.submit("r1", "a", 0, 4) is None
    assert svc.submit("r2", "b", 10, 12) is None
    assert svc.submit("r3", "a", 0, nb) is None
    d0 = svc.stats["dispatches"]
    ans = svc.submit("r4", "b", nb - 1, nb)  # trips the policy
    assert set(ans) == {"r1", "r2", "r3", "r4"}
    assert svc.stats["dispatches"] - d0 == 1  # ONE device dispatch, 2 stores
    B = 16
    for rid, i, j in [("r1", 0, 4), ("r2", 10, 12), ("r3", 0, nb),
                      ("r4", nb - 1, nb)]:
        assert ans[rid].tobytes() == y[i * B:j * B].tobytes()
    # immediate read path rides the same backend
    assert svc.read("a", 2, 6).tobytes() == y[2 * B:6 * B].tobytes()


def test_host_flush_buckets_device_flush_merges():
    """The host backend splits dissimilar request lengths into pow-2
    buckets (padding control); a device backend merges them into one
    dispatch (dispatch control)."""
    blob = _session_stream("std_D32")
    packed = pack(blob)
    nb = Container(packed).total_blocks(0)
    reqs = [("s1", 0, 1), ("s2", 0, nb)]  # 1 block vs nb blocks

    host = DecompressionService(policy=FlushPolicy(max_batch_streams=2),
                                backend="numpy")
    host.attach("s", packed)
    for rid, i, j in reqs[:1]:
        host.submit(rid, "s", i, j)
    host.submit(*("s2", "s", 0, nb))
    assert host.stats["dispatches"] == 2

    dev = DecompressionService(policy=FlushPolicy(max_batch_streams=2),
                               backend="jax")
    dev.attach("s", packed)
    dev.submit("s1", "s", 0, 1)
    dev.submit("s2", "s", 0, nb)
    assert dev.stats["dispatches"] == 1


def test_device_flush_splits_pathological_padding():
    """Merging buckets on a device backend must not let one huge request
    pad hundreds of tiny ones: when the padded batch exceeds both the
    policy block budget and 4x the real work, the group re-splits by
    length bucket -- and every answer stays exact."""
    blob = _session_stream("std_D32")
    y = decode_stream(blob)
    packed = pack(blob)
    nb = Container(packed).total_blocks(0)  # 40
    n_tiny = 30
    svc = DecompressionService(
        policy=FlushPolicy(max_batch_streams=n_tiny + 2,
                           max_batch_blocks=nb + n_tiny),
        backend="jax")
    svc.attach("s", packed)
    reqs = [("big", 0, nb)] + [(f"t{k}", k, k + 1) for k in range(n_tiny)]
    for rid, i, j in reqs[:-1]:
        assert svc.submit(rid, "s", i, j) is None
    rid, i, j = reqs[-1]
    ans = svc.submit(rid, "s", i, j)  # trips max_batch_streams
    # padded merged batch would be 31*40=1240 >> sum(70)*4 and > budget:
    # must have split into (at least) the 1-block and 64-block buckets
    assert svc.stats["dispatches"] >= 2
    B = 16
    for rid, i, j in reqs:
        assert ans[rid].tobytes() == y[i * B:j * B].tobytes(), rid


# ------------------------------------------------------- hypothesis widen
try:
    import hypothesis  # noqa: F401

    from hypothesis import given, settings
    from hypothesis import strategies as st

    @given(name=st.sampled_from(sorted(GOLDEN_CASES)),
           backend=st.sampled_from(BACKENDS),
           data=st.data())
    @settings(max_examples=40, deadline=None)
    def test_random_range_sets_any_backend(name, backend, data):
        """Property form: ANY set of ranges, batched on ANY backend,
        equals the host full decode's slices."""
        _, store, y = _prepped(name)
        nb = store.total_blocks(0)
        B = store.header_of(int(store.chunks_of(0)[0])).block_size
        n_req = data.draw(st.integers(min_value=1, max_value=6))
        reqs = []
        for _ in range(n_req):
            i = data.draw(st.integers(min_value=0, max_value=nb - 1))
            j = data.draw(st.integers(min_value=i + 1, max_value=nb))
            reqs.append((0, i, j))
        for (_, i, j), got in zip(reqs, decode_ranges(store, reqs,
                                                      backend=backend)):
            assert got.tobytes() == y[i * B:j * B].tobytes()

except ImportError:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_random_range_sets_any_backend():
        pass
