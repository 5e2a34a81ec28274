"""The ``sdrbench-isabel`` cell's own pieces: the field generator, the
plain error-bounded ``residual`` reference against the program at a small
size, planted faults that the comparison must catch, the lower-precision
control, the roofline's byte count, and a CPU rehearsal of the cell end
to end (Pallas interpreted)."""
import base64
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import rehearsal  # noqa: E402
from benchkit import fields, harness, spec  # noqa: E402

SEED = 2**31 + 1515
KIND = spec.traffic_kind(BENCH.parent, "field_upload")


def tiny_config(num_dict: int = 255, keep=(0, 1, 9)) -> dict:
    """The configuration with its widths and codec as published, on a
    small grid (4 levels of 20 x 100: 62 blocks and a 16-sample tail a
    feed) and three of its fields (a sparse one and two state ones)."""
    cfg = json.loads((BENCH / "configs" / "sdrbench-isabel.json")
                     .read_text())
    cfg["grid"] = {"z": 4, "y": 20, "x": 100}
    cfg["fields"] = [cfg["fields"][i] for i in keep]
    cfg["codec"] = dict(cfg["codec"], num_dict=num_dict)
    return cfg


def tiny_mix() -> dict:
    mix = json.loads((BENCH / "traffic" / "snapshots.json").read_text())
    mix.update(levels_per_feed=2, lead_s=0.2, warmup_rounds_max=2,
               check={"streams": 3})
    return mix


def program_stream(cfg, f, x, chunk, bound=None, stats=False):
    """The program's bytes for one stream fed ``x`` in ``chunk`` feeds
    (and with ``stats`` its session's hit count)."""
    from repro.api import CodecConfig
    from repro.core import IdealemCodec

    doc = fields.codec_doc(cfg, f)
    if bound is not None:
        doc["error_bound"] = bound
    s = IdealemCodec.from_config(CodecConfig(**doc)).session(
        dtype=np.float32)
    blob = b"".join([s.feed(x[i:i + chunk])
                     for i in range(0, len(x), chunk)] + [s.finish()])
    return (blob, s.stats.hits) if stats else blob


@pytest.fixture(scope="module")
def small():
    """Two programs' worth of streams: every field's level 1 fed five
    snapshots, with an 8-entry dictionary so that FIFO overwrites occur."""
    cfg = tiny_config(num_dict=8)
    chunk = KIND.level_samples(cfg)
    out = []
    for f in range(len(cfg["fields"])):
        x = fields.level_series(cfg, SEED, f, 1, 5)
        blob, hits = program_stream(cfg, f, x, chunk, stats=True)
        out.append((f, x, blob, hits))
    return cfg, chunk, out


# ------------------------------------------------------------- generator
def test_slices_are_seeded_and_independent_of_order():
    cfg = tiny_config()
    a = fields.level_slice(cfg, SEED, 1, 3, 2)
    assert a.dtype == np.float32 and a.shape == (2000,)
    np.testing.assert_array_equal(a, fields.level_slice(cfg, SEED, 1, 3, 2))
    assert not np.array_equal(a, fields.level_slice(cfg, SEED + 1, 1, 3, 2))
    series = fields.level_series(cfg, SEED, 1, 2, 4)
    np.testing.assert_array_equal(series[6000:], a)


def test_fields_in_range_sparse_ones_mostly_zero():
    cfg = json.loads((BENCH / "configs" / "sdrbench-isabel.json")
                     .read_text())
    cfg["grid"] = dict(cfg["grid"], y=100, x=100)
    for f, spec_f in enumerate(cfg["fields"]):
        x = fields.level_slice(cfg, SEED, f, 0, 50)
        lo, hi = spec_f["range"]
        assert lo <= x.min() and x.max() <= hi, spec_f["name"]
        if spec_f["kind"] == fields.SPARSE:
            zero = np.mean(x == 0)
            assert abs(zero - spec_f["zero_share"]) < 0.2, spec_f["name"]
            assert x.max() > 0
        else:
            assert np.std(x) > 0.02 * (hi - lo), spec_f["name"]


def test_thirteen_fields_thirteen_bounds():
    cfg = json.loads((BENCH / "configs" / "sdrbench-isabel.json")
                     .read_text())
    bounds = [fields.field_bound(cfg, f) for f in range(len(cfg["fields"]))]
    assert len(cfg["fields"]) == 13 and len(set(bounds)) == 13
    lo, hi = cfg["fields"][1]["range"]           # Pf
    assert bounds[1] == pytest.approx(0.01 * (hi - lo))


# ------------------------------------------------------------- reference
def test_reference_bytes_equal_the_program(small):
    cfg, chunk, out = small
    assert sum(h for *_a, h in out) > 0     # hits and misses both occur
    for f, x, got, _h in out:
        want = fields.encode_stream(cfg["codec"], fields.field_bound(cfg, f),
                                    x, chunk)
        assert got == want, cfg["fields"][f]["name"]


def test_reference_decode_equals_the_program_and_keeps_the_bound(small):
    from repro.core import IdealemCodec

    cfg, _chunk, out = small
    for f, x, got, _h in out:
        y = fields.decode_stream(got)
        np.testing.assert_array_equal(y, IdealemCodec().decode(got))
        assert fields.bound_violations(x, y, fields.field_bound(cfg, f),
                                       cfg["codec"]["block_size"]) == 0


def test_reference_imports_nothing_of_the_program():
    import ast

    tree = ast.parse((BENCH / "benchkit" / "fields.py").read_text())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module or "" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert names and not [m for m in names if m.split(".")[0] in
                          ("repro", "jax")]


@pytest.mark.parametrize("fault", ["one_ulp", "segment_byte",
                                   "raised_bound"])
def test_planted_faults_read_not_correct(small, fault):
    cfg, chunk, out = small
    streams = [{"field": f, "level": 1, "feeds": 5,
                "b64": base64.b64encode(got).decode()}
               for f, _x, got, _h in out]
    assert KIND.stream_readings(cfg, SEED, streams) == {
        "streams_not_equal": 0, "bound_violations": 0}

    def served(st, x, bound):
        if fault == "raised_bound":
            return program_stream(cfg, st["field"], x, chunk, 4 * bound)
        blob = bytearray(base64.b64decode(st["b64"]))
        # the first block is a miss: a 34-byte header, the slot, the base,
        # then its residuals from byte 39
        if fault == "one_ulp":
            y = np.frombuffer(bytes(blob[39:43]), "<f4")
            blob[39:43] = np.nextafter(y, np.inf).astype("<f4").tobytes()
        else:
            blob[34] ^= 0x01                     # the first slot byte
        return bytes(blob)

    r = KIND.stream_readings(cfg, SEED, streams, served=served)
    assert r["streams_not_equal"] > 0, r


def test_lower_precision_control_is_not_correct():
    r = KIND.control(tiny_config(), tiny_mix(), SEED, 1.0, minutes=2)
    assert any(v > 0 for v in r.values()), r


def test_checked_streams_take_the_most_fed_and_draw_the_rest():
    feeds = {(f, z): 0 for f in range(3) for z in range(4)}
    feeds.update({(1, 2): 3, (0, 0): 1, (2, 3): 1, (2, 1): 2})
    got = KIND.checked_streams({"check": {"streams": 3}}, SEED, feeds)
    assert got[0] == (1, 2) and len(got) == 3
    assert set(got[1:]) <= {(0, 0), (2, 3), (2, 1)}
    assert got == KIND.checked_streams({"check": {"streams": 3}}, SEED,
                                       feeds)


def test_requests_walk_levels_then_snapshots():
    cfg, mix = tiny_config(), tiny_mix()
    assert KIND.requests_per_snapshot(cfg, mix) == 2
    assert KIND.request_levels(cfg, mix, 0) == (0, range(0, 2))
    assert KIND.request_levels(cfg, mix, 3) == (1, range(2, 4))


def test_scan_bytes_counts_both_carries():
    # 7,812 blocks of 31 residuals, D=255
    nb, n, D = 7812, 31, 255
    carry = 2 * D * n * 4 + 2 * D * 4 + D + 4
    assert fields.scan_bytes(nb, n, D) == nb * (n * 4 + 6) + 4 + 2 * carry


# ------------------------------------------------------------- rehearsal
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    cfg = tiny_config()
    cfg["grid"] = {"z": 4, "y": 8, "x": 64}        # 16 blocks a feed
    yield rehearsal.make_root(tmp_path_factory.mktemp("isabel"), cells={
        "isabel-snapshots": ("sdrbench-isabel", cfg, "snapshots",
                             tiny_mix())})
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


PER_LAYER = ("scan_sync_wait_ms.snapshots", "feed_parse_ms.snapshots",
             "encode_hit_rate.snapshots", "bound_demotion_share.snapshots",
             "compiles_in_window.snapshots")


@pytest.mark.parametrize("trace", [False, True])
def test_isabel_snapshots_rehearsal(root, trace):
    res = harness.run_cell(root, "isabel-snapshots", SEED, 3.0, trace,
                           require_tpu=False)
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == set(KIND.LIMITS)
    assert res["attempted"] > 0 and res["failed"] == 0
    if not trace:
        assert set(res["metrics"]) == {"ingest_samples_per_s", "setup_s"}
    else:
        # no device planes on the CPU: the device readers find nothing
        assert set(PER_LAYER) <= set(res["metrics"])
        assert "device_idle_share.snapshots" not in res["metrics"]
        assert res["metrics"]["compiles_in_window.snapshots"]["value"] == 0


@pytest.mark.parametrize("name", PER_LAYER[:4])
def test_reader_finds_nothing_without_the_programs_counters(name):
    ctx = harness.RunContext(
        cell=None, kind=None, seed=SEED, t0=0.0, t1=1.0, setup_s=1.0,
        records=[], done={}, counters_before={}, counters_after={},
        compiles_setup={}, compiles_window={}, peaks={})
    assert spec.metric_reader(BENCH.parent, name)(ctx) is None
