"""Synthetic micro-PMU fleet signals, made from the run's seed.

A copy of the signal models the program's bring-up smoke used
(``repro.data.synthetic.pmu_magnitude`` / ``pmu_angle`` with the levels of
``chip_smoke.make_fleet``), kept here so that no later change to the
program can move the yardstick.  One change from the original: level
shifts and tap-change steps come at a fixed number per minute (the
configuration's ``events`` block), not a fixed number per call, so a long
backlog made in one call keeps its event density.

Every channel's series depends only on ``(seed, device, channel)`` and the
length asked for, so the load generator and the reference regenerate the
same samples independently.
"""
from __future__ import annotations

import numpy as np

__all__ = ["channel_series", "channel_kinds", "codec_doc", "tenant_of",
           "stream_id", "store_id", "samples_per_minute"]


def samples_per_minute(cfg: dict) -> int:
    return int(round(60 * cfg["sample_rate_hz"]))


def channel_kinds(cfg: dict) -> list:
    """The kind (``"magnitude"`` / ``"angle"``) of each channel of a device,
    in channel order."""
    return [ch["kind"] for ch in cfg["channels"]]


def codec_doc(cfg: dict, channel: int) -> dict:
    """The wire codec configuration of a channel: its kind's codec and
    the engine settings."""
    kind = cfg["channels"][channel]["kind"]
    return {**cfg["codecs"][kind], **cfg["engine"]}


def tenant_of(cfg: dict, device: int) -> str:
    per = -(-cfg["fleet"]["devices"] // cfg["fleet"]["tenants"])
    return f"substation-{device // per:02d}"


def stream_id(device: int, channel: int) -> str:
    return f"pmu{device:03d}/ch{channel:02d}"


def store_id(device: int) -> str:
    return f"pmu{device:03d}"


def _rng(seed: int, device: int, channel: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), device, channel])


def _event_positions(rng, n: int, per_minute: float, spm: int,
                     margin: int) -> np.ndarray:
    """Exactly floor((m+1)*rate) - floor(m*rate) events in minute m, each
    at a uniform position inside its minute (clipped to leave ``margin``
    samples before the series end)."""
    minutes = -(-n // spm)
    counts = (np.floor(np.arange(1, minutes + 1) * per_minute)
              - np.floor(np.arange(minutes) * per_minute)).astype(np.int64)
    starts = np.repeat(np.arange(minutes) * spm, counts)
    pos = starts + rng.integers(0, spm, size=len(starts))
    return np.clip(pos, 0, max(n - 1 - margin, 0))


def channel_series(cfg: dict, seed: int, device: int, channel: int,
                   n: int) -> np.ndarray:
    """``n`` float32 samples of one channel of one device."""
    ch = cfg["channels"][channel]
    ev = cfg["events"]
    spm = samples_per_minute(cfg)
    rng = _rng(seed, device, channel)
    if ch["kind"] == "magnitude":
        noise = ch["noise"]
        jitter = rng.standard_normal(n, dtype=np.float32) * np.float32(noise)
        steps = np.zeros(n + 1)
        pos = _event_positions(rng, n, ev["shifts_per_minute"], spm, 0)
        np.add.at(steps, pos, rng.normal(0.0, ev["shift_scale"] * noise,
                                         len(pos)))
        tl = ev["tap_len"]
        pos = _event_positions(rng, n, ev["taps_per_minute"], spm, tl)
        sign = rng.choice([-1.0, 1.0], size=len(pos)) * ch["tap_step"]
        np.add.at(steps, pos, sign)
        np.add.at(steps, pos + tl, -sign)
        steps[0] += ch["level"]
        return np.cumsum(steps[:n]).astype(np.float32) + jitter
    if ch["kind"] == "angle":
        slope = ch["slope"] + ch["slope_jitter"] * rng.standard_normal()
        x = np.arange(n, dtype=np.float64) * slope
        x += rng.standard_normal(n, dtype=np.float32) * ch["noise"]
        return np.mod(x, 360.0).astype(np.float32)
    raise ValueError(f"unknown channel kind {ch['kind']!r}")
