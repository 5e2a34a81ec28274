"""Traffic kind ``field_upload``: a finished simulation's field snapshots
uploaded for compression under a pointwise error bound.

One closed-loop uploader per field sends the field's snapshots in time
order: a ``/v1/feed`` request carries ``levels_per_feed`` z-levels of one
snapshot, one JSON line of ``y * x`` float32 samples per level, to the
level's direct stream (one stream per (field, level), opened with the
field's absolute bound and kept open across snapshots).  The slices come
from the seed (``benchkit.fields``), made by a worker thread of the load
generator while the previous request is in flight; each record carries
how long its data took to make and how long the uploader waited for it.
The uploaders start ``lead_s`` before the window; a request belongs to the
window whose answer came inside it, whenever it was sent.

Warm-up: each round opens one stream per field on a tenant of its own and
feeds it two level slices, one request each, so that both feed shapes
(a level's first feed and the one that completes its tail) run under every
field's bound.

The correctness check closes ``check.streams`` streams after the window
(the one fed most and others drawn from the seed) and compares every byte
with the plain reference fed the same samples in the same chunks, and
every decoded sample with its original under the field's bound.
"""
from __future__ import annotations

import asyncio
import base64
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional

import numpy as np

from benchkit import fields
from benchkit.wire import ANSWER_WAIT_S, Connection, feed_line, log

LIMITS = {"streams_not_equal": 0, "bound_violations": 0,
          "uploaders_drained": 0, "answers_missing": 0}


def level_samples(cfg: dict) -> int:
    return int(cfg["grid"]["y"]) * int(cfg["grid"]["x"])


def requests_per_snapshot(cfg: dict, mix: dict) -> int:
    return -(-int(cfg["grid"]["z"]) // int(mix["levels_per_feed"]))


def request_levels(cfg: dict, mix: dict, k: int):
    """The snapshot and levels of a field's ``k``-th request."""
    per = requests_per_snapshot(cfg, mix)
    lpf = int(mix["levels_per_feed"])
    t, z0 = divmod(k, per)
    return t, range(z0 * lpf, min((z0 + 1) * lpf, int(cfg["grid"]["z"])))


def checked_streams(mix: dict, seed: int, feeds: dict) -> list:
    """Streams ``(field, level)`` whose bytes the check compares: the one
    fed most (the first such in field, level order) and
    ``check.streams - 1`` others with at least one feed, drawn from the
    seed.  ``feeds`` maps each stream to its count of answered feeds."""
    fed = sorted(s for s, m in feeds.items() if m > 0)
    if not fed:
        return []
    most = max(fed, key=lambda s: feeds[s])
    others = [s for s in fed if s != most]
    k = min(int(mix["check"]["streams"]) - 1, len(others))
    rng = np.random.default_rng([seed % (1 << 64), 7])
    picked = rng.choice(len(others), size=k, replace=False) if k else []
    return [most] + sorted(others[int(i)] for i in picked)


class Load:
    def __init__(self, spec: dict):
        self.cfg, self.mix = spec["config"], spec["traffic"]
        self.seed = spec["seed"]
        self.fields = range(len(self.cfg["fields"]))
        self.levels = range(int(self.cfg["grid"]["z"]))
        self.snapshots = int(self.cfg["snapshots"])
        self.segments: Dict[tuple, List[bytes]] = {}
        self.feeds: Dict[tuple, int] = {}
        self.records = []
        self.conns = {}
        self.drained = 0
        self.t1 = float("inf")
        self.tasks = []
        self.pool = ThreadPoolExecutor(max_workers=4,
                                       thread_name_prefix="fields")
        self.next = {}

    def _open_doc(self, sid: str, f: int) -> dict:
        return {"stream_id": sid, "config": fields.codec_doc(self.cfg, f),
                "coalesce": bool(self.mix["coalesce"]),
                "dtype": self.cfg["dtype"]}

    def _lines(self, f: int, k: int):
        """The lines of field ``f``'s ``k``-th request (``None`` past the
        backlog), with the seconds they took to make."""
        t0 = time.monotonic()
        t, levels = request_levels(self.cfg, self.mix, k)
        if t >= self.snapshots:
            return None, 0.0
        lines = [feed_line(fields.stream_id(self.cfg, f, z),
                           fields.level_slice(self.cfg, self.seed, f, t, z))
                 for z in levels]
        return (lines, list(levels)), time.monotonic() - t0

    def prepare(self) -> dict:
        t = time.monotonic()
        self.first = {f: self._lines(f, 0) for f in self.fields}
        return {"data_s": time.monotonic() - t}

    async def setup(self, host: str, port: int) -> dict:
        self.host, self.port = host, port
        t = time.monotonic()
        for f in self.fields:
            conn = self.conns[f] = await Connection(host, port).open()
            tenant = fields.tenant_of(self.cfg, f)
            for z in self.levels:
                sid = fields.stream_id(self.cfg, f, z)
                await conn.post("/v1/open", tenant, self._open_doc(sid, f))
                self.segments[(f, z)] = []
                self.feeds[(f, z)] = 0
        return {"streams": len(self.segments),
                "open_s": time.monotonic() - t}

    async def warm_round(self, i: int) -> None:
        """One stream per field on the ``warmup`` tenant, fed a level's
        first and second snapshot (the two feed shapes), then closed."""
        loop = asyncio.get_running_loop()
        z = i % len(self.levels)
        data = await loop.run_in_executor(self.pool, lambda: {
            (f, t): fields.level_slice(self.cfg, self.seed + 1, f, t, z)
            for f in self.fields for t in (0, 1)})
        conn = await Connection(self.host, self.port).open()
        sid = {f: f"warmup{i}/{self.cfg['fields'][f]['name']}"
               for f in self.fields}
        try:
            for f in self.fields:
                await conn.post("/v1/open", "warmup",
                                self._open_doc(sid[f], f))
            for t in (0, 1):
                docs = await conn.post_lines("/v1/feed", "warmup", [
                    feed_line(sid[f], data[(f, t)]) for f in self.fields])
                bad = [x for x in docs if "error" in x]
                if bad or len(docs) != len(self.fields):
                    raise RuntimeError(f"warm-up feed failed: "
                                       f"{bad[0] if bad else docs}")
            for f in self.fields:
                await conn.post("/v1/close", "warmup",
                                {"stream_id": sid[f]})
        finally:
            await conn.close()

    async def _uploader(self, f: int) -> None:
        loop = asyncio.get_running_loop()
        conn = self.conns[f]
        tenant = fields.tenant_of(self.cfg, f)
        nxt = asyncio.get_running_loop().create_future()
        nxt.set_result(self.first.pop(f))
        k = 0
        while time.monotonic() < self.t1:
            t_wait = time.monotonic()
            (req, gen_s) = await nxt
            wait_s = time.monotonic() - t_wait
            if req is None:
                self.drained += 1
                break
            nxt = loop.run_in_executor(self.pool, self._lines, f, k + 1)
            lines, levels = req
            sent = time.monotonic()
            docs = []
            try:
                docs = await conn.post_lines("/v1/feed", tenant, lines)
            except (ConnectionError, OSError, ValueError) as exc:
                log(f"field {f} request {k}: {exc!r}")
            finally:
                ok = len(docs) == len(lines) and all(
                    "error" not in x for x in docs)
                self.records.append({
                    "field": f, "sent": sent, "done": time.monotonic(),
                    "ok": ok, "samples": level_samples(self.cfg) * len(lines),
                    "gen_s": gen_s, "wait_s": wait_s})
            if not ok:
                break
            for z, doc in zip(levels, docs):
                self.segments[(f, z)].append(base64.b64decode(doc["segment"]))
                self.feeds[(f, z)] += 1
            k += 1

    async def start(self) -> None:
        self.tasks = [asyncio.create_task(self._uploader(f))
                      for f in self.fields]

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
        """Close the checked streams and hand back their bytes."""
        self.pool.shutdown(wait=False, cancel_futures=True)
        if self.records:
            gen = np.array([r["gen_s"] for r in self.records])
            wait = np.array([r["wait_s"] for r in self.records])
            log(f"data per request: made in {1e3 * gen.mean():.1f} ms mean, "
                f"{1e3 * gen.max():.1f} ms max; uploaders waited for it "
                f"{1e3 * wait.mean():.2f} ms mean, {1e3 * wait.max():.2f} "
                f"ms max ({len(self.records)} requests)")
        streams = []
        for f, z in checked_streams(self.mix, self.seed, self.feeds):
            sid = fields.stream_id(self.cfg, f, z)
            try:
                doc = await self.conns[f].post(
                    "/v1/close", fields.tenant_of(self.cfg, f),
                    {"stream_id": sid})
                self.segments[(f, z)].append(
                    base64.b64decode(doc["segment"]))
            except (RuntimeError, ConnectionError, OSError) as exc:
                log(f"close {sid}: {exc!r}")
            streams.append({
                "field": f, "level": z, "feeds": self.feeds[(f, z)],
                "b64": base64.b64encode(
                    b"".join(self.segments[(f, z)])).decode("ascii")})
        for conn in self.conns.values():
            await conn.close()
        return {"streams": streams, "drained": self.drained}


def attempted(records: list, t0: float, t1: float) -> list:
    """The requests answered (or failed) inside the window."""
    return [r for r in records if t0 <= r["done"] < t1]


def latency(record: dict) -> float:
    return record["done"] - record["sent"]


def notes(cfg: dict, mix: dict, log) -> None:
    B = int(cfg["codec"]["block_size"])
    log(f"per level feed: {level_samples(cfg)} samples, "
        f"{level_samples(cfg) // B}-{-(-level_samples(cfg) // B)} blocks of "
        f"{B}; bounds " + ", ".join(
            f"{f['name']} {fields.field_bound(cfg, i):.6g}"
            for i, f in enumerate(cfg["fields"])))


def stream_readings(cfg: dict, seed: int, streams: List[dict],
                    served: Optional[Callable] = None, log=None) -> Dict:
    """``streams``: ``{field, level, feeds, b64}`` as the load generator
    returns them.  ``served(stream, samples, bound) -> bytes`` stands in
    for the served bytes (the control puts a second reference there)."""
    chunk = level_samples(cfg)
    B = int(cfg["codec"]["block_size"])
    bad = violations = 0
    for st in streams:
        f, z = st["field"], st["level"]
        x = fields.level_series(cfg, seed, f, z, st["feeds"])
        bound = fields.field_bound(cfg, f)
        want = fields.encode_stream(cfg["codec"], bound, x, chunk)
        got = (served(st, x, bound) if served is not None
               else base64.b64decode(st["b64"]))
        if got != want:
            bad += 1
            if log is not None:
                at = next((i for i, (a, b) in enumerate(zip(got, want))
                           if a != b), min(len(got), len(want)))
                log(f"stream {fields.stream_id(cfg, f, z)}: {len(got)} "
                    f"bytes served, {len(want)} expected, first difference "
                    f"at byte {at}")
        try:
            y = fields.decode_stream(got)
        except (ValueError, IndexError) as exc:
            if log is not None:
                log(f"stream {fields.stream_id(cfg, f, z)} does not "
                    f"decode: {exc}")
            violations += len(x)
            continue
        violations += fields.bound_violations(x, y, bound, B)
    return {"streams_not_equal": bad, "bound_violations": violations}


def readings(cfg: dict, mix: dict, seed: int, done: dict, window: list,
             log=None) -> Dict:
    """Every checked stream's bytes against the reference and its decoded
    samples against the bound; uploaders that ran out of backlog (no valid
    run); and every request of the run, lead included, that got no answer
    or an error document."""
    return {**stream_readings(cfg, seed, done["streams"], log=log),
            "uploaders_drained": int(done.get("drained", 0)),
            "answers_missing": sum(1 for r in done["records"]
                                   if not r["ok"])}


def control(cfg: dict, mix: dict, seed: int, seconds: float,
            minutes: int = 1, log=None) -> Dict:
    """The stream readings with the reference at the precision below the
    configuration's serving, every checked stream taken to have been fed
    ``minutes`` snapshots."""
    from benchkit.reference import lower_dtype

    low = lower_dtype(cfg["dtype"])
    feeds = {(f, z): int(minutes) for f in range(len(cfg["fields"]))
             for z in range(int(cfg["grid"]["z"]))}
    streams = [{"field": f, "level": z, "feeds": int(minutes)}
               for f, z in checked_streams(mix, seed, feeds)]

    def served(st, x, bound):
        return fields.encode_stream(cfg["codec"], bound, x,
                                    level_samples(cfg), work_dtype=low)

    return stream_readings(cfg, seed, streams, served=served, log=log)
