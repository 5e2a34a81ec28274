"""Traffic generators and fleet signals: deterministic by seed, stated
distributions, and the same multiset of sizes for every seed."""
import collections
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchkit import fleet, spec, traffic  # noqa: E402

SEED = 2**31 + 12345        # larger than 32 signed bits hold
RANGE = spec.traffic_kind(BENCH.parent, "range_read")
UPLOAD = spec.traffic_kind(BENCH.parent, "upload")


def load(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def archive():
    return load("configs", "upmu-archive"), load("traffic", "dashboard")


def test_channel_series_deterministic_and_seeded():
    cfg = load("configs", "upmu-gateway")
    a = fleet.channel_series(cfg, SEED, 3, 1, 7200)
    assert a.dtype == np.float32
    np.testing.assert_array_equal(a, fleet.channel_series(cfg, SEED, 3, 1,
                                                          7200))
    assert not np.array_equal(a, fleet.channel_series(cfg, SEED + 1, 3, 1,
                                                      7200))
    assert not np.array_equal(a, fleet.channel_series(cfg, SEED, 4, 1,
                                                      7200))


def test_magnitude_levels_and_angles_in_range():
    cfg = load("configs", "upmu-gateway")
    v = fleet.channel_series(cfg, SEED, 0, 0, 72000)
    i = fleet.channel_series(cfg, SEED, 0, 4, 72000)
    ang = fleet.channel_series(cfg, SEED, 0, 8, 72000)
    assert abs(np.median(v) - 7200) < 100 and abs(np.median(i) - 300) < 20
    assert ang.min() >= 0 and ang.max() < 360


def test_events_come_at_a_rate_per_minute():
    cfg = load("configs", "upmu-gateway")
    cfg = {**cfg, "channels": [{**cfg["channels"][0], "noise": 0.0}],
           "events": {**cfg["events"], "shift_scale": 0.0}}
    x = fleet.channel_series(cfg, SEED, 0, 0, 30 * 7200)
    # noise and shifts off: what is left is the tap steps, 1.5 a minute
    starts = np.flatnonzero(np.diff((x != x[0]).astype(int)) == 1)
    assert 40 <= len(starts) <= 45


def test_zipf_counts_exact_and_skewed():
    c = traffic.zipf_counts(1000, 64, 1.0)
    assert c.sum() == 1000
    w = 1 / np.arange(1, 65)
    np.testing.assert_allclose(c, 1000 * w / w.sum(), atol=1.0)


def test_range_schedule_deterministic(archive):
    cfg, mix = archive
    a = RANGE.schedule(cfg, mix, SEED, 20.0)
    assert a == RANGE.schedule(cfg, mix, SEED, 20.0)
    assert a != RANGE.schedule(cfg, mix, SEED + 1, 20.0)


def test_range_schedule_distributions(archive):
    cfg, mix = archive
    s = RANGE.schedule(cfg, mix, SEED, 40.0)
    n = len(s)
    assert n == round(mix["rate_per_s"] * 40)
    dues = [r["due"] for r in s]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 40
    # Zipf(1) over 64 devices, uniform channels, 10% history probes
    devs = collections.Counter(r["device"] for r in s)
    np.testing.assert_array_equal(
        [devs.get(d, 0) for d in range(64)],
        traffic.zipf_counts(n, 64, mix["device_zipf_s"]))
    chans = collections.Counter(r["channel"] for r in s)
    assert max(chans.values()) - min(chans.values()) <= 1
    spm = fleet.samples_per_minute(cfg)
    hist = 0
    for r in s:
        kind = cfg["channels"][r["channel"]]["kind"]
        B = cfg["codecs"][kind]["block_size"]
        total = 60 * spm // B
        length = r["stop"] - r["start"]
        assert spm // B <= length <= 10 * spm // B
        assert 0 <= r["start"] and r["stop"] <= total
        hist += r["stop"] != total
    assert abs(hist - round(0.1 * n)) <= 1   # a probe may end at the end


def test_every_seed_gets_the_same_sizes(archive):
    cfg, mix = archive
    sizes = [sorted((r["channel"] < 6, r["stop"] - r["start"])
                    for r in RANGE.schedule(cfg, mix, s, 20.0))
             for s in (1, 2, SEED)]
    # lengths are stratified; only their pairing with channels moves
    lens = [sorted(x[1] for x in sz) for sz in sizes]
    assert len({len(x) for x in lens}) == 1
    devs = [sorted(r["device"] for r in RANGE.schedule(
        cfg, mix, s, 20.0)) for s in (1, 2, SEED)]
    assert devs[0] == devs[1] == devs[2]


def test_every_seed_gets_the_same_arrival_gaps(archive):
    cfg, mix = archive
    gaps = []
    for s in (1, 2, SEED):
        dues = np.array([r["due"] for r in RANGE.schedule(cfg, mix, s, 20.0)])
        gaps.append(np.diff(dues))
    # the same gaps (all but the one the last arrival leaves off), in
    # another order
    common = np.intersect1d(np.round(gaps[0], 9), np.round(gaps[1], 9))
    assert len(common) >= len(gaps[0]) - 1
    assert not np.array_equal(gaps[0], gaps[1])
    # exponential: about 63% of the gaps are under the mean
    mean = 20.0 / len(gaps[2])
    assert 0.6 < np.mean(gaps[2] < mean) < 0.67


def test_checked_reads_include_the_longest(archive):
    cfg, mix = archive
    s = RANGE.schedule(cfg, mix, SEED, 20.0)
    keep = RANGE.checked(cfg, mix, SEED, s)
    longest = max(range(len(s)),
                  key=lambda i: RANGE.samples(cfg, s[i]))
    assert longest in keep
    assert mix["check"]["requests"] <= len(keep) \
        <= mix["check"]["requests"] + 1
    assert keep == RANGE.checked(cfg, mix, SEED, s)


def test_upload_checked_devices():
    mix = load("traffic", "backfill")
    minutes = {d: 10 + (d == 5) for d in range(64)}
    picked = UPLOAD.checked_devices(mix, SEED, minutes)
    assert picked[0] == 5 and len(set(picked)) == mix["check"]["devices"]
    assert picked == UPLOAD.checked_devices(mix, SEED, minutes)
