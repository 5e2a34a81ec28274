"""Traffic kind ``upload``: devices upload their backlog closed-loop.

Each of the fleet's devices has one uploader, which sends the device's
next ``samples_per_feed`` samples of every channel as one JSON-lines
``/v1/feed`` request and waits for the answer.  The backlog is
``backlog_minutes`` long.  The uploaders start as soon as warm-up is
done, ``lead_s`` before the window opens, so that the server is busy at
its start; a request belongs to the window whose answer came inside it,
whenever it was sent, so that the work cut off at the two edges roughly
cancels.

The correctness check closes ``check.devices`` devices' streams after the
window (the device that uploaded most and others drawn from the seed) and
compares every byte of each with the plain reference encoder fed the
same samples in the same chunks.
"""
from __future__ import annotations

import asyncio
import base64
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchkit import fleet, reference
from benchkit.wire import ANSWER_WAIT_S, Connection, feed_line, log

LIMITS = {"streams_not_equal": 0, "uploaders_drained": 0,
          "answers_missing": 0}


def backlog_samples(cfg: dict, mix: dict) -> int:
    return int(mix["backlog_minutes"] * fleet.samples_per_minute(cfg))


def checked_devices(mix: dict, seed: int, minutes: dict) -> list:
    """Devices whose streams the correctness check compares: the one that
    uploaded most, and ``check.devices - 1`` others (with at least one
    upload) drawn from the seed.  ``minutes`` maps device to uploads."""
    rng = np.random.default_rng([seed % (1 << 64), 7])
    devices = sorted(minutes)
    longest = max(devices, key=lambda d: minutes[d])
    others = [d for d in devices if d != longest and minutes[d] > 0]
    k = min(int(mix["check"]["devices"]) - 1, len(others))
    return [longest] + sorted(int(x) for x in rng.choice(others, size=k,
                                                         replace=False))


class Load:
    def __init__(self, spec: dict):
        self.cfg, self.mix = spec["config"], spec["traffic"]
        self.seed = spec["seed"]
        self.devices = range(self.cfg["fleet"]["devices"])
        self.chans = range(len(self.cfg["channels"]))
        self.feed = int(self.mix["samples_per_feed"])
        self.segments = {}
        self.minutes = {}
        self.records = []
        self.conns = {}
        self.drained = 0
        self.t1 = float("inf")
        self.tasks = []

    def _open_doc(self, sid: str, c: int) -> dict:
        return {"stream_id": sid, "config": fleet.codec_doc(self.cfg, c),
                "coalesce": bool(self.mix["coalesce"]),
                "dtype": self.cfg["dtype"]}

    def prepare(self) -> dict:
        t = time.monotonic()
        self.n = backlog_samples(self.cfg, self.mix)
        self.series = {(d, c): fleet.channel_series(self.cfg, self.seed, d,
                                                    c, self.n)
                       for d in self.devices for c in self.chans}
        return {"data_s": time.monotonic() - t}

    async def setup(self, host: str, port: int) -> dict:
        self.host, self.port = host, port
        t = time.monotonic()
        for d in self.devices:
            conn = self.conns[d] = await Connection(self.host,
                                                    self.port).open()
            tenant = fleet.tenant_of(self.cfg, d)
            for c in self.chans:
                sid = fleet.stream_id(d, c)
                await conn.post("/v1/open", tenant, self._open_doc(sid, c))
                self.segments[(d, c)] = []
        return {"streams": len(self.segments),
                "open_s": time.monotonic() - t}

    async def warm_round(self, i: int) -> None:
        """One extra device, on a tenant of its own, feeds
        ``warmup_feeds`` chunks of every channel and closes."""
        feeds = int(self.mix["warmup_feeds"])
        dev = self.cfg["fleet"]["devices"] + i
        conn = await Connection(self.host, self.port).open()
        try:
            for c in self.chans:
                await conn.post("/v1/open", "warmup",
                                self._open_doc(fleet.stream_id(dev, c), c))
            data = {c: fleet.channel_series(self.cfg, self.seed + 1, dev, c,
                                            feeds * self.feed)
                    for c in self.chans}
            for m in range(feeds):
                lo = m * self.feed
                docs = await conn.post_lines("/v1/feed", "warmup", [
                    feed_line(fleet.stream_id(dev, c),
                              data[c][lo:lo + self.feed])
                    for c in self.chans])
                bad = [x for x in docs if "error" in x]
                if bad:
                    raise RuntimeError(f"warm-up feed failed: {bad[0]}")
            for c in self.chans:
                await conn.post("/v1/close", "warmup",
                                {"stream_id": fleet.stream_id(dev, c)})
        finally:
            await conn.close()

    async def _uploader(self, d: int) -> None:
        conn = self.conns[d]
        tenant = fleet.tenant_of(self.cfg, d)
        m = 0
        while time.monotonic() < self.t1:
            lo = m * self.feed
            if lo + self.feed > self.n:
                self.drained += 1
                break
            lines = [feed_line(fleet.stream_id(d, c),
                               self.series[(d, c)][lo:lo + self.feed])
                     for c in self.chans]
            sent = time.monotonic()
            docs = []
            try:
                docs = await conn.post_lines("/v1/feed", tenant, lines)
            except (ConnectionError, OSError, ValueError) as exc:
                log(f"device {d} minute {m}: {exc!r}")
            finally:
                ok = len(docs) == len(self.chans) and all(
                    "error" not in x for x in docs)
                self.records.append({"device": d, "sent": sent,
                                     "done": time.monotonic(), "ok": ok,
                                     "samples": self.feed * len(self.chans)})
                self.minutes[d] = m + ok
            for c, doc in zip(self.chans, docs if ok else []):
                self.segments[(d, c)].append(
                    base64.b64decode(doc["segment"]))
            m += 1
            if not ok:
                break

    async def start(self) -> None:
        self.tasks = [asyncio.create_task(self._uploader(d))
                      for d in self.devices]

    async def window(self, t0: float, t1: float) -> None:
        """The uploaders send until the window closes; a request still
        unanswered a minute after the close counts as failed."""
        self.t1 = t1
        _done, pending = await asyncio.wait(
            self.tasks,
            timeout=max(t1 + ANSWER_WAIT_S - time.monotonic(), 1.0))
        for t in pending:
            t.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)

    async def finish(self) -> dict:
        """Close the checked devices' streams and hand back their bytes."""
        picked = checked_devices(
            self.mix, self.seed,
            {d: self.minutes.get(d, 0) for d in self.devices})
        streams = []
        for d in picked:
            conn, tenant = self.conns[d], fleet.tenant_of(self.cfg, d)
            for c in self.chans:
                sid = fleet.stream_id(d, c)
                try:
                    doc = await conn.post("/v1/close", tenant,
                                          {"stream_id": sid})
                    self.segments[(d, c)].append(
                        base64.b64decode(doc["segment"]))
                except (RuntimeError, ConnectionError, OSError) as exc:
                    log(f"close {sid}: {exc!r}")
                streams.append({
                    "device": d, "channel": c,
                    "minutes": self.minutes.get(d, 0),
                    "b64": base64.b64encode(
                        b"".join(self.segments[(d, c)])).decode("ascii")})
        for conn in self.conns.values():
            await conn.close()
        return {"streams": streams, "drained": self.drained,
                "minutes": {str(d): m for d, m in self.minutes.items()}}


def attempted(records: list, t0: float, t1: float) -> list:
    """The requests answered (or failed) inside the window."""
    return [r for r in records if t0 <= r["done"] < t1]


def latency(record: dict) -> float:
    return record["done"] - record["sent"]


def notes(cfg: dict, mix: dict, log) -> None:
    from benchkit import roofline

    calls = roofline.feed_calls(cfg, mix["samples_per_feed"])
    log(f"ungated KS comparisons per request (D*n^2 per block): "
        f"{sum(roofline.encode_scan_ks_compares(*c) for c in calls):.0f}")


def _codec(cfg: dict, channel: int) -> dict:
    return cfg["codecs"][cfg["channels"][channel]["kind"]]


def stream_readings(cfg: dict, mix: dict, seed: int, streams: List[dict],
                    served: Optional[Callable] = None, log=None) -> Dict:
    """``streams``: ``{device, channel, minutes, b64}`` as the load
    generator returns them.  ``served(stream, samples) -> bytes`` stands
    in for the served bytes (the control puts a second reference
    there)."""
    feed = int(mix["samples_per_feed"])
    n = backlog_samples(cfg, mix)
    bad = 0
    for st in streams:
        d, c, m = st["device"], st["channel"], st["minutes"]
        x = fleet.channel_series(cfg, seed, d, c, n)[:m * feed]
        want = reference.encode_stream(_codec(cfg, c), x, feed)
        got = (served(st, x) if served is not None
               else base64.b64decode(st["b64"]))
        if got != want:
            bad += 1
            if log is not None:
                at = next((i for i, (a, b) in enumerate(zip(got, want))
                           if a != b), min(len(got), len(want)))
                log(f"stream {fleet.stream_id(d, c)}: {len(got)} bytes "
                    f"served, {len(want)} expected, first difference at "
                    f"byte {at}")
    return {"streams_not_equal": bad}


def readings(cfg: dict, mix: dict, seed: int, done: dict, window: list,
             log=None) -> Dict:
    """Every checked stream's bytes against the reference; uploaders that
    ran out of backlog (no valid run); and every request of the run,
    lead included, that got no answer or an error document."""
    return {**stream_readings(cfg, mix, seed, done["streams"], log=log),
            "uploaders_drained": int(done.get("drained", 0)),
            "answers_missing": sum(1 for r in done["records"]
                                   if not r["ok"])}


def control(cfg: dict, mix: dict, seed: int, seconds: float,
            minutes: int = 10, log=None) -> Dict:
    """The stream readings with the reference at the precision below the
    configuration's serving, every device taken to have uploaded
    ``minutes`` minutes."""
    low = reference.lower_dtype(cfg["dtype"])
    devs = checked_devices(
        mix, seed, {d: minutes for d in range(cfg["fleet"]["devices"])})
    streams = [{"device": d, "channel": c, "minutes": minutes}
               for d in devs for c in range(len(cfg["channels"]))]

    def served(st, x):
        return reference.encode_stream(_codec(cfg, st["channel"]), x,
                                       int(mix["samples_per_feed"]),
                                       work_dtype=low)

    return stream_readings(cfg, mix, seed, streams, served=served, log=log)
