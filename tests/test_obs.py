"""Unified telemetry layer (ISSUE 8): registry, tracer, exporters, wiring.

Four property groups: (1) the instruments themselves -- counter/gauge/
histogram semantics, Prometheus ``le`` bucket boundaries, label children,
type-conflict rejection, and exact totals under concurrent writers; (2)
the span tracer -- parent/child nesting per thread, error status, bounded
ring eviction (the profiler side is in ``test_spans.py``); (3) the
exporters -- a golden-format Prometheus text pin, the parse round trip,
and the JSON snapshot shape;
(4) the wiring -- the acceptance shape of ISSUE 8: ONE pipelined
``DecompressionService`` flush with ``backend="auto"`` must land stage
latency histograms for all four stages, autotune probe/hit counters,
cache hit counters and valid round-trippable exposition in a single
process-default registry snapshot.

Wiring tests assert *deltas* against the process-default registry (other
tests in the same pytest process write into it too; absolute values are
not meaningful there).
"""
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import MetricsRegistry, SpanTracer
from repro.serve import (DecompressionService, FlushPolicy, StagePipeline,
                         StreamCoalescer, SyncExecutor, ThreadStageExecutor)
from repro.store import Container, pack


# --------------------------------------------------------------- registry

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("t_ops_total", "ops")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("t_depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0
    assert reg.get_value("t_ops_total") == 3.5
    assert reg.get_value("t_never_written_total") == 0.0


def test_label_children_are_distinct_and_cached():
    reg = MetricsRegistry()
    a = reg.counter("t_total", labels={"k": "a"})
    b = reg.counter("t_total", labels={"k": "b"})
    a.inc(1)
    b.inc(2)
    assert (a.value, b.value) == (1.0, 2.0)
    # same (name, labels) returns the same child, label order irrelevant
    c = reg.counter("t2_total", labels={"x": "1", "y": "2"})
    assert reg.counter("t2_total", labels={"y": "2", "x": "1"}) is c


def test_type_and_bucket_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("t_total")
    with pytest.raises(ValueError):
        reg.gauge("t_total")
    reg.histogram("t_seconds", buckets=(0.1, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("t_seconds", buckets=(0.5, 1.0))
    with pytest.raises(ValueError):
        reg.counter("bad name!")
    with pytest.raises(ValueError):
        reg.counter("t3_total", labels={"bad-label": "v"})


def test_histogram_bucket_boundaries():
    """Prometheus ``le`` semantics: a value exactly on a bound lands in
    that bucket; above the last bound lands in +Inf."""
    reg = MetricsRegistry()
    h = reg.histogram("t_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.1, 1.0, 10.0):   # exactly on each bound
        h.observe(v)
    h.observe(0.05)              # below the first
    h.observe(10.0001)           # above the last -> +Inf
    assert h.bucket_counts() == (2, 1, 1, 1)  # per-bucket, +Inf last
    assert h.count == 5
    assert h.sum == pytest.approx(0.1 + 1.0 + 10.0 + 0.05 + 10.0001)


def test_default_latency_ladder():
    b = obs.DEFAULT_LATENCY_BUCKETS
    assert len(b) == 15 and b[0] == pytest.approx(1e-6) and b[-1] == 10.0
    assert list(b) == sorted(b)


def test_reset_keeps_handles_valid():
    reg = MetricsRegistry()
    c = reg.counter("t_total")
    h = reg.histogram("t_seconds")
    c.inc(7)
    h.observe(0.5)
    reg.reset()
    assert c.value == 0.0 and h.count == 0
    c.inc()  # the cached handle still writes into the registry
    assert reg.get_value("t_total") == 1.0


def test_disabled_registry_drops_writes():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("t_total")
    h = reg.histogram("t_seconds")
    c.inc(5)
    h.observe(1.0)
    assert c.value == 0.0 and h.count == 0
    reg.enabled = True
    c.inc()
    assert c.value == 1.0


def test_registry_thread_safety_exact_totals():
    """Concurrent flush simulation: many writers on shared and per-thread
    instruments, exact totals at the end (no lost updates)."""
    reg = MetricsRegistry()
    shared = reg.counter("t_shared_total")
    hist = reg.histogram("t_lat_seconds")
    n_threads, n_iter = 8, 2000

    def worker(i):
        own = reg.counter("t_labeled_total", labels={"w": str(i)})
        for _ in range(n_iter):
            shared.inc()
            own.inc()
            hist.observe(1e-4)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert shared.value == n_threads * n_iter
    assert hist.count == n_threads * n_iter
    for i in range(n_threads):
        assert reg.get_value("t_labeled_total", {"w": str(i)}) == n_iter


# ----------------------------------------------------------------- tracer

def test_span_nesting_parent_ids():
    trc = SpanTracer()
    with trc.span("outer") as outer_id:
        with trc.span("inner") as inner_id:
            trc.event("tick")
    recs = {r.name: r for r in trc.records()}
    assert recs["inner"].parent_id == outer_id
    assert recs["outer"].parent_id is None
    assert recs["tick"].parent_id == inner_id  # events nest under spans
    assert recs["tick"].kind == "event" and recs["tick"].duration_s == 0.0
    # inner finished first (ring is completion-ordered)
    names = [r.name for r in trc.records()]
    assert names == ["tick", "inner", "outer"]


def test_span_error_status_and_reraise():
    trc = SpanTracer()
    with pytest.raises(RuntimeError):
        with trc.span("boom"):
            raise RuntimeError("x")
    (rec,) = trc.records(name="boom")
    assert rec.status == "error" and rec.duration_s >= 0.0


def test_span_ring_eviction():
    trc = SpanTracer(capacity=3)
    for i in range(7):
        trc.event(f"e{i}")
    assert [r.name for r in trc.records()] == ["e4", "e5", "e6"]


def test_span_threads_nest_independently():
    trc = SpanTracer()
    err = []

    def worker(tag):
        try:
            with trc.span(f"{tag}.outer") as oid:
                with trc.span(f"{tag}.inner"):
                    pass
                assert trc._stack()[-1] == oid
        except BaseException as e:  # pragma: no cover - diagnostic
            err.append(e)

    threads = [threading.Thread(target=worker, args=(f"t{i}",))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not err
    for i in range(4):
        (inner,) = trc.records(name=f"t{i}.inner")
        (outer,) = trc.records(name=f"t{i}.outer")
        assert inner.parent_id == outer.span_id


def test_disabled_tracer_records_nothing():
    trc = SpanTracer(enabled=False)
    with trc.span("s") as sid:
        assert sid is None
    trc.event("e")
    assert trc.records() == []


# -------------------------------------------------------------- exporters

def _golden_registry():
    reg = MetricsRegistry()
    reg.counter("t_ops_total", "ops", labels={"op": "read"}).inc(2)
    reg.gauge("t_depth").set(1.5)
    h = reg.histogram("t_lat_seconds", "lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    return reg


def test_prometheus_golden_text():
    text = obs.to_prometheus(_golden_registry())
    assert text == (
        "# TYPE t_depth gauge\n"
        "t_depth 1.5\n"
        "# HELP t_lat_seconds lat\n"
        "# TYPE t_lat_seconds histogram\n"
        't_lat_seconds_bucket{le="0.1"} 1\n'
        't_lat_seconds_bucket{le="1"} 2\n'
        't_lat_seconds_bucket{le="+Inf"} 3\n'
        "t_lat_seconds_sum 5.55\n"
        "t_lat_seconds_count 3\n"
        "# HELP t_ops_total ops\n"
        "# TYPE t_ops_total counter\n"
        't_ops_total{op="read"} 2\n'
    )


def test_prometheus_parse_round_trip():
    reg = _golden_registry()
    parsed = obs.parse_prometheus(obs.to_prometheus(reg))
    assert parsed[("t_ops_total", (("op", "read"),))] == 2.0
    assert parsed[("t_depth", ())] == 1.5
    assert parsed[("t_lat_seconds_bucket", (("le", "+Inf"),))] == 3.0
    assert parsed[("t_lat_seconds_count", ())] == 3.0


def test_prometheus_label_escapes_round_trip():
    reg = MetricsRegistry()
    awkward = 'weird"\\label\nwith newline'
    reg.counter("t_total", labels={"op": awkward}).inc()
    parsed = obs.parse_prometheus(obs.to_prometheus(reg))
    assert parsed[("t_total", (("op", awkward),))] == 1.0


def test_json_snapshot_shape():
    reg = _golden_registry()
    trc = SpanTracer()
    with trc.span("s"):
        pass
    doc = obs.to_json(reg, trc)
    assert doc["version"] == 1
    hist = doc["metrics"]["t_lat_seconds"]
    assert hist["kind"] == "histogram"
    (entry,) = hist["values"]
    assert entry["count"] == 3 and entry["buckets"]["+Inf"] == 1
    assert doc["spans"][0]["name"] == "s"
    import json
    json.loads(json.dumps(doc))  # JSON-ready, no numpy scalars etc.


def test_selfcheck_clean():
    assert obs.selfcheck() == []


# ----------------------------------------------------------------- wiring

def _get(name, labels=None):
    return obs.registry().get_value(name, labels)


def _stage_counts():
    snap = obs.registry().snapshot()
    fam = snap.get("repro_serve_stage_seconds", {"values": []})
    return {v["labels"].get("stage"): v.get("count", 0)
            for v in fam["values"]}


def test_coalescer_flush_metrics_and_span():
    """A coalesced encode flush moves the pinned encode metric names and
    records an ``encode.flush`` span."""
    before = {k: _get(k) for k in (
        "repro_encode_flushes_total", "repro_encode_bytes_in_total",
        "repro_encode_bytes_out_total", "repro_encode_blocks_total")}
    spans_before = len(obs.tracer().records(name="encode.flush"))
    rng = np.random.default_rng(0)
    coal = StreamCoalescer(
        policy=FlushPolicy(max_batch_blocks=64, max_batch_streams=4),
        mode="std", block_size=16, num_dict=8)
    blobs = {}
    for sid in ("a", "b"):
        coal.open_stream(sid)
        blobs[sid] = b""
    for _ in range(3):
        for sid in blobs:
            out = coal.submit(sid, rng.normal(0, 1, size=64)) or {}
            for k, seg in out.items():
                blobs[k] += seg
    for sid in list(blobs):
        blobs[sid] += coal.close_stream(sid)
    for key, prev in before.items():
        assert _get(key) > prev, key
    assert len(obs.tracer().records(name="encode.flush")) > spans_before
    assert all(blobs.values())


def test_pipelined_auto_flush_single_snapshot_acceptance():
    """ISSUE 8 acceptance: one pipelined ``DecompressionService`` flush
    with ``backend="auto"`` yields, from a single registry snapshot:
    per-stage latency histograms for all four stages, autotune probe/hit
    counters, cache hit counters, and exposition text that parses back."""
    rng = np.random.default_rng(1)
    coal = StreamCoalescer(
        policy=FlushPolicy(max_batch_blocks=256, max_batch_streams=2),
        mode="std", block_size=16, num_dict=8)
    coal.open_stream("s")
    blob = b""
    for _ in range(4):
        out = coal.submit("s", rng.normal(0, 1, size=256)) or {}
        blob += out.get("s", b"")
    blob += coal.close_stream("s")

    stages_before = _stage_counts()
    tuning_before = sum(
        v["value"] for fam in ("repro_tuning_probes_total",
                               "repro_tuning_hits_total")
        for v in obs.registry().snapshot().get(
            fam, {"values": []})["values"])
    cache_before = (_get("repro_serve_cache_hits_total"),
                    _get("repro_serve_cache_misses_total"))

    svc = DecompressionService(
        policy=FlushPolicy(max_batch_streams=8, pipeline_depth=2),
        backend="auto")
    svc.attach("s", Container(pack(blob)))
    # two flush cycles over the same chunk: the first parse is the miss,
    # the second flush's parse must hit the segment LRU
    answers = {}
    for i, (lo, hi) in enumerate([(0, 8), (4, 12)]):
        svc.submit(f"r{i}", "s", lo, hi)
    answers.update(svc.flush())
    for i, (lo, hi) in enumerate([(2, 10), (0, 16)], start=2):
        svc.submit(f"r{i}", "s", lo, hi)
    answers.update(svc.flush())
    answers.update(svc.close())
    assert set(answers) == {"r0", "r1", "r2", "r3"}

    snap = obs.registry().snapshot()  # ONE snapshot, all of it below
    stages = {v["labels"].get("stage"): v.get("count", 0)
              for v in snap["repro_serve_stage_seconds"]["values"]}
    for stage in ("plan", "gather", "reconstruct", "emit"):
        assert stages.get(stage, 0) > stages_before.get(stage, 0), stage
    tuning_after = sum(
        v["value"] for fam in ("repro_tuning_probes_total",
                               "repro_tuning_hits_total")
        for v in snap.get(fam, {"values": []})["values"])
    assert tuning_after > tuning_before  # auto routed through the tuner
    hits_after = (_get("repro_serve_cache_hits_total"),
                  _get("repro_serve_cache_misses_total"))
    assert hits_after[0] > cache_before[0]
    assert hits_after[1] > cache_before[1]
    # the whole registry must export as valid, parseable exposition text
    parsed = obs.parse_prometheus(obs.to_prometheus())
    assert parsed[("repro_serve_cache_hits_total", ())] == hits_after[0]
    count_key = ("repro_serve_stage_seconds_count", (("stage", "plan"),))
    assert parsed[count_key] == float(stages["plan"])


def test_decode_stats_compat_view():
    """``decode_stats()`` stays a plain int dict (the pinned pre-obs
    API) while its storage lives on the registry."""
    from repro.core.decode import decode_stats
    stats = decode_stats()
    for key in ("host_calls", "device_calls", "fallbacks",
                "autotune_probes", "autotune_hits"):
        assert isinstance(stats[key], int)
    assert _get("repro_decode_host_calls_total") == stats["host_calls"]


# -------------------------------------------------------------- executors

def test_thread_executor_shutdown_idempotent_and_submit_after():
    ex = ThreadStageExecutor()
    assert ex.submit(lambda: 42).result() == 42
    ex.shutdown()
    ex.shutdown()  # second call must be a no-op, not a hang or raise
    fut = ex.submit(lambda: 1)
    with pytest.raises(RuntimeError, match="shut down"):
        fut.result()
    ex._thread.join(timeout=5)
    assert not ex.alive


def test_stage_pipeline_counts_stage_errors():
    before = _get("repro_serve_stage_errors_total")
    pipe = StagePipeline(SyncExecutor(), depth=1)
    ((meta, value, exc),) = pipe.push("m", lambda: 1 // 0)
    assert meta == "m" and value is None
    assert isinstance(exc, ZeroDivisionError)
    assert _get("repro_serve_stage_errors_total") == before + 1
