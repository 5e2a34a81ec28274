"""Tiny-fleet rehearsals of each cell on the CPU (Pallas interpreted):
set-up, window and the correctness comparison, end to end over the wire
with the load generator in its own process; then the same runs with the
timed path broken underneath, which the comparison must catch."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import rehearsal  # noqa: E402
from benchkit import harness, spec  # noqa: E402

SEED = 2**31 + 4242
SECONDS = 1.5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield rehearsal.make_root(tmp_path_factory.mktemp("rehearsal"))
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def run(root, cell, trace=False, seed=SEED):
    return harness.run_cell(root, cell, seed, SECONDS, trace,
                            require_tpu=False)


def test_gateway_backfill_rehearsal(root):
    res = run(root, "gateway-backfill")
    assert res["correct"], res["compared"]
    assert res["compared"]["streams_not_equal"] == {"value": 0, "limit": 0}
    assert res["compared"]["answers_missing"] == {"value": 0, "limit": 0}
    assert set(res["metrics"]) == {"ingest_samples_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "compared"


def test_archive_dashboard_rehearsal(root):
    res = run(root, "archive-dashboard")
    assert res["correct"], res["compared"]
    assert res["compared"]["values_not_equal"] == {"value": 0, "limit": 0}
    assert set(res["metrics"]) == {"decode_p95_ms", "setup_s"}
    assert res["attempted"] == round(20.0 * SECONDS)


def test_traced_rehearsal_reports_per_layer_metrics(root):
    res = run(root, "archive-dashboard", trace=True)
    assert res["correct"]
    # no device planes on the CPU: the device readers find nothing
    assert {"compiles_in_window.dashboard", "loadgen_late_p99_ms.dashboard",
            "decode_lru_hit_rate.dashboard"} <= set(res["metrics"])
    assert "device_idle_share.dashboard" not in res["metrics"]
    assert res["device"]["window_s"] == pytest.approx(SECONDS, rel=0.2)
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_fault_state_unchanged_is_caught(root, monkeypatch):
    from repro.core.session import IdealemSession

    orig = IdealemSession._decide

    def stuck(self, payload):
        out = orig(self, payload)
        self._dev_state = None          # the carry never advances
        return out

    monkeypatch.setattr(IdealemSession, "_decide", stuck)
    res = run(root, "gateway-backfill")
    assert not res["correct"]
    assert res["compared"]["streams_not_equal"]["value"] > 0


def test_fault_segment_altered_is_caught(root, monkeypatch):
    from repro.core import stream

    orig = stream.assemble_stream

    def altered(*a, **kw):
        seg = bytearray(orig(*a, **kw))
        seg[-1] ^= 1
        return bytes(seg)

    monkeypatch.setattr(stream, "assemble_stream", altered)
    res = run(root, "gateway-backfill")
    assert not res["correct"]


def test_fault_answer_altered_is_caught(root, monkeypatch):
    from repro.core import decode

    orig = decode.reconstruct

    def altered(plan, backend="numpy"):
        return np.nextafter(orig(plan, backend), np.inf).astype(
            np.dtype(plan.dtype))

    monkeypatch.setattr(decode, "reconstruct", altered)
    res = run(root, "archive-dashboard")
    assert not res["correct"]
    assert res["compared"]["values_not_equal"]["value"] > 0


def test_fault_half_the_batch_left_out_is_caught(root, monkeypatch):
    from repro.serve.compress import DecompressionService

    orig = DecompressionService._stage_emit
    count = [0]

    def half(self, *a, **kw):
        kept = {}
        for rid, values in orig(self, *a, **kw).items():
            count[0] += 1
            if count[0] % 2:
                kept[rid] = values
        return kept

    monkeypatch.setattr(DecompressionService, "_stage_emit", half)
    res = run(root, "archive-dashboard")
    assert not res["correct"]
    assert res["compared"]["answers_missing"]["value"] == res["failed"] > 0


@pytest.mark.parametrize("cell", ["gateway-backfill", "archive-dashboard"])
def test_lower_precision_control_is_not_correct(root, cell):
    c = spec.load_cell(root, cell)
    kind = spec.traffic_kind(root, c.mix["kind"])
    r = kind.control(c.cfg, c.mix, SEED, SECONDS, minutes=3)
    assert any(v > 0 for v in r.values()), r
