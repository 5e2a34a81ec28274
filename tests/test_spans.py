"""Program spans on the profiler's clock, and the counters placed with them.

Once JAX is imported a span is also a profiler ``TraceMe`` under its bare
name, its ring stamps lie on the trace's clock, it observes its histogram
once on clean exit, and ``repro.obs`` works in a process without JAX.
Through the front end, one feed and one decode move each layer's phase,
queue-wait, loop-lag and dispatch counts by what they should, and no span
is open while a request awaits its batch.
"""
import asyncio
import glob
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro import api, obs
from repro.core import IdealemCodec
from repro.obs import MetricsRegistry, SpanTracer
from repro.serve import FrontendClient, ServeFrontend
from repro.store import pack

SRC = Path(__file__).resolve().parents[1] / "src"


def _traced(tmp_path, body):
    """Run ``body`` under a ``jax.profiler`` trace; returns the host
    events as ``(name, start_ns, end_ns, stats)`` relative to the trace's
    start, and that start on the profiler's host clock."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host, start = [], None
    for plane in ProfileData.from_file(path).planes:
        start = dict(plane.stats).get("profile_start_time", start)
        if plane.name.startswith("/host:"):
            host += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                     for line in plane.lines for e in line.events]
    return host, start


# ---------------------------------------------------------------- profiler

def test_span_is_a_profiler_event_under_its_bare_name(tmp_path):
    def body():
        with obs.span("t.outer", attrs={"tenant": "a", "stream": "s"}):
            with obs.span("t.inner", attrs={"seq": 3}):
                time.sleep(0.002)

    host, _start = _traced(tmp_path, body)
    ev = {n: (a, b, st) for n, a, b, st in host if n.startswith("t.")}
    assert set(ev) == {"t.outer", "t.inner"}    # ids ride as metadata
    (oa, ob, ost), (ia, ib, ist) = ev["t.outer"], ev["t.inner"]
    assert oa <= ia < ib <= ob                   # the child inside its parent
    assert ost == {"tenant": "a", "stream": "s"} and ist == {"seq": 3}


def test_ring_stamps_agree_with_the_trace_within_100us(tmp_path):
    def body():
        with obs.span("t.clock"):
            time.sleep(0.005)

    host, start = _traced(tmp_path, body)
    rec = obs.tracer().records(name="t.clock")[-1]
    (a, b), = [(a, b) for n, a, b, _st in host if n == "t.clock"]
    assert start is not None
    assert abs(rec.start_s * 1e9 - (start + a)) < 1e5
    assert abs((rec.start_s + rec.duration_s) * 1e9 - (start + b)) < 1e5


def test_obs_works_in_a_process_without_jax():
    code = (
        "import sys\n"
        "sys.modules['jax'] = None  # any import of JAX now fails\n"
        "from repro import obs\n"
        "h = obs.MetricsRegistry().histogram('t_seconds')\n"
        "with obs.span('t.nojax', attrs={'k': 1}, histogram=h):\n"
        "    pass\n"
        "assert h.count == 1\n"
        "assert len(obs.tracer().records(name='t.nojax')) == 1\n"
        "assert sys.modules['jax'] is None\n")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


# ------------------------------------------------------ span + histogram

@pytest.mark.parametrize("enabled", [True, False])
def test_span_observes_its_histogram_once_on_clean_exit(enabled):
    h = MetricsRegistry().histogram("t_seconds")
    trc = SpanTracer(enabled=enabled)
    with trc.span("ok", histogram=h):
        time.sleep(0.001)
    with pytest.raises(ValueError):
        with trc.span("bad", histogram=h):
            raise ValueError("x")
    assert h.count == 1 and h.sum >= 0.001
    if enabled:   # one measurement: the ring holds what the histogram got
        (ok,), (bad,) = trc.records(name="ok"), trc.records(name="bad")
        assert ok.duration_s == h.sum and bad.status == "error"
    else:
        assert trc.records() == []


# ----------------------------------------------------------------- layers

def _count(name, labels=None):
    for v in obs.registry().snapshot().get(name, {}).get("values", []):
        if v["labels"] == (labels or {}):
            return v.get("count", v.get("value"))
    return 0


@pytest.mark.parametrize("backend,channels,dispatches", [
    ("jax", None, 1), ("jax", 3, 1), ("numpy", None, 0)])
def test_direct_session_feed_counts_one_scan_dispatch(backend, channels,
                                                      dispatches):
    sess = IdealemCodec(mode="std", block_size=32, num_dict=15,
                        backend=backend).session(channels=channels)
    x = np.sin(np.linspace(0, 20, 96))
    chunk = x if channels is None else np.stack([x] * channels)
    keys = {p: ("repro_encode_dispatches_total", {"path": p})
            for p in ("direct", "adaptive_batched", "adaptive_loop")}
    keys.update({p: ("repro_encode_phase_seconds", {"phase": p})
                 for p in ("prepare", "dispatch", "sync", "commit")})
    before = {k: _count(*v) for k, v in keys.items()}
    sess.feed(chunk)
    grew = {k: _count(*v) - before[k] for k, v in keys.items()}
    assert grew == {"direct": dispatches, "adaptive_batched": 0,
                    "adaptive_loop": 0, "prepare": 1,
                    "dispatch": dispatches, "sync": dispatches, "commit": 1}


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_direct_scan_traces_once_per_feed_shape(backend):
    """The direct scan is one cached executable per feed shape: three
    same-shape feeds trace it once, a feed at a new block count once more,
    while every feed counts its one dispatch."""
    # an alpha no other test uses: its d_crit keys a fresh executable
    sess = IdealemCodec(mode="std", block_size=32, num_dict=15, alpha=0.0173,
                        backend=backend).session(channels=3)
    x = np.sin(np.linspace(0, 20, 6 * 32))
    traces = ("repro_encode_scan_traces_total", {"path": "direct"})
    direct = ("repro_encode_dispatches_total", {"path": "direct"})
    t0, d0 = _count(*traces), _count(*direct)
    for lo in (0, 32, 64):
        sess.feed(np.stack([x[lo:lo + 96]] * 3))
    assert (_count(*traces) - t0, _count(*direct) - d0) == (1, 3)
    sess.feed(np.stack([x[:160]] * 3))
    assert (_count(*traces) - t0, _count(*direct) - d0) == (2, 4)


def test_feed_and_decode_move_each_layer_count():
    feed, dec = "POST /v1/feed", "POST /v1/decode"
    keys = {
        "feed_parse": ("repro_frontend_phase_seconds",
                       {"route": feed, "phase": "parse"}),
        "feed_encode": ("repro_frontend_phase_seconds",
                        {"route": feed, "phase": "encode"}),
        "decode_parse": ("repro_frontend_phase_seconds",
                         {"route": dec, "phase": "parse"}),
        "decode_encode": ("repro_frontend_phase_seconds",
                          {"route": dec, "phase": "encode"}),
        "queue_wait": ("repro_serve_queue_wait_seconds", None),
        "dispatch": ("repro_encode_phase_seconds", {"phase": "dispatch"}),
        "direct": ("repro_encode_dispatches_total", {"path": "direct"}),
        "plan": ("repro_serve_stage_seconds", {"stage": "plan"}),
    }
    before = {k: _count(*v) for k, v in keys.items()}
    lag_before = _count("repro_frontend_loop_lag_seconds")

    async def main():
        async with ServeFrontend(run_control=False,
                                 decode_backend="numpy") as fe:
            codec = IdealemCodec(mode="std", block_size=32, num_dict=15,
                                 backend="numpy")
            x = np.sin(np.linspace(0, 50, 16 * 32))
            async with FrontendClient(fe.host, fe.port, "spans") as c:
                await c.open("s", api.CodecConfig(backend="jax",
                                                  block_size=32,
                                                  num_dict=15))
                outs = await c.post_lines("/v1/feed", [
                    api.CompressRequest("s", x[:64]).to_json(),
                    api.CompressRequest("s", x[64:128]).to_json()])
                assert all("error" not in o for o in outs)
                await c.attach("st", pack(codec.encode(x)))
                rr = await c.decode("st", 2, 6)
                assert np.asarray(rr.values).size == 4 * 32
            await asyncio.sleep(0.02)        # a few more ticks

    asyncio.run(main())
    grew = {k: _count(*v) - before[k] for k, v in keys.items()}
    assert grew == {"feed_parse": 1, "feed_encode": 1, "decode_parse": 1,
                    "decode_encode": 1, "queue_wait": 1, "dispatch": 2,
                    "direct": 2, "plan": 1}
    assert _count("repro_frontend_loop_lag_seconds") > lag_before


def test_no_span_is_open_while_a_request_awaits_its_batch():
    async def main():
        async with ServeFrontend(run_control=False, decode_backend="numpy",
                                 tick_interval_s=None) as fe:
            codec = IdealemCodec(mode="std", block_size=32, num_dict=15,
                                 backend="numpy")
            x = np.sin(np.linspace(0, 50, 16 * 32))
            async with FrontendClient(fe.host, fe.port, "await") as c:
                await c.attach("st", pack(codec.encode(x)))
                t0 = time.time_ns() / 1e9    # the ring's clock
                task = asyncio.ensure_future(c.decode("st", 1, 3))
                while not any(r.start_s >= t0 for r in obs.tracer().records(
                        name="serve.submit")):
                    await asyncio.sleep(0.005)
                await asyncio.sleep(0.02)    # the request awaits its batch
                assert not task.done()
                assert obs.tracer()._stack() == []
                fe.tick()                    # past max_age_s: the batch cuts
                rr = await asyncio.wait_for(task, 10)
                assert np.asarray(rr.values).size == 2 * 32

    asyncio.run(main())
