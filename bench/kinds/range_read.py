"""Traffic kind ``range_read``: open-loop range reads over ``/v1/decode``.

Reads arrive at ``rate_per_s``: the gaps between arrivals are the
quantiles of an exponential distribution in an order drawn from the seed
(``traffic.arrivals``), devices follow
Zipf(``device_zipf_s``), channels are uniform, ``recent_share`` of the
ranges end at the archive's end and the rest start uniformly inside it,
and range lengths are uniform over ``range_minutes`` of samples.  Every
seed gets the same multiset of sizes and of arrival gaps: device and
channel counts, the history share, the length quantiles and the gaps are
stratified, and only their order and pairing change with the seed.

The archive (the configuration's ``archive`` block) is built and attached
in the server's set-up.  Warm-up sends bursts of concurrent reads of
every padded size (``burst_sets``), then replays the mix on schedules of
its own, ``warmup_round_s`` a round.  A read belongs to the window it was due in;
its latency runs from its due time to its answer.  The correctness check
compares, bit for bit, ``check.requests`` answers drawn from the seed and
the longest read with the plain reference's decode of the same blocks.
"""
from __future__ import annotations

import asyncio
import base64
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchkit import fleet, reference, traffic
from benchkit.wire import (ANSWER_WAIT_S, Connection, array_nbytes, log,
                           sleep_until)

LIMITS = {"values_not_equal": 0, "answers_missing": 0}


def archive_samples(cfg: dict) -> int:
    return int(round(cfg["archive"]["hours"] * 60
                     * fleet.samples_per_minute(cfg)))


def schedule(cfg: dict, mix: dict, seed: int, seconds: float,
             stream: str = "window") -> list:
    """The open-loop schedule: a list of dicts ``{due, device, channel,
    start, stop}`` sorted by ``due`` (seconds from the window's start)."""
    rng = traffic.rng(seed, stream)
    n = int(round(mix["rate_per_s"] * seconds))
    devices = cfg["fleet"]["devices"]
    chans = len(cfg["channels"])
    due = traffic.arrivals(rng, n, seconds)
    dev = np.repeat(np.arange(devices),
                    traffic.zipf_counts(n, devices, mix["device_zipf_s"]))
    rng.shuffle(dev)
    ch = np.resize(np.arange(chans), n)
    rng.shuffle(ch)
    hist = np.arange(n) < int(round((1.0 - mix["recent_share"]) * n))
    rng.shuffle(hist)
    u = (np.arange(n) + 0.5) / n
    rng.shuffle(u)
    v = (np.arange(n) + 0.5) / n
    rng.shuffle(v)
    spm = fleet.samples_per_minute(cfg)
    total_samples = archive_samples(cfg)
    lo, hi = mix["range_minutes"]
    out = []
    for i in range(n):
        c = int(ch[i])
        total = traffic.channel_blocks(cfg, c, total_samples)
        length = traffic.channel_blocks(cfg, c,
                                        int((lo + (hi - lo) * u[i]) * spm))
        length = min(max(length, 1), total)
        if hist[i]:
            start = int(v[i] * (total - length + 1))
        else:
            start = total - length
        out.append({"due": float(due[i]), "device": int(dev[i]),
                    "channel": c, "start": start, "stop": start + length})
    return out


def samples(cfg: dict, req: dict) -> int:
    """Samples a range read returns."""
    kind = cfg["channels"][req["channel"]]["kind"]
    return (req["stop"] - req["start"]) * cfg["codecs"][kind]["block_size"]


def checked(cfg: dict, mix: dict, seed: int, sched: list) -> set:
    """Indices of the reads whose answers the correctness check compares:
    ``check.requests`` drawn from the seed, and the longest read."""
    n = len(sched)
    if not n:
        return set()
    rng = np.random.default_rng([seed % (1 << 64), 11])
    k = min(int(mix["check"]["requests"]), n)
    keep = set(int(i) for i in rng.choice(n, size=k, replace=False))
    keep.add(max(range(n), key=lambda i: samples(cfg, sched[i])))
    return keep


def _pow2s(lo: int, hi: int) -> list:
    p, out = 1, []
    while p < 2 * hi:
        if p >= lo:
            out.append(p)
        p *= 2
    return out


def burst_sets(lo: int, hi: int, max_reads: int) -> list:
    """Length sets for warm-up bursts of concurrent reads of one channel
    kind, lengths in ``[lo, hi]`` blocks: for every burst size ``R`` up to
    ``max_reads`` and every pair of power-of-two classes (``R`` times the
    longest read, and the summed lengths plus one) that such a burst can
    reach, one set of lengths that reaches it, shortest reads first."""
    sets = []
    for r in range(1, max_reads + 1):
        for a in _pow2s(lo, r * hi):
            m_lo, m_hi = max(lo, a // (2 * r) + 1), min(hi, a // r)
            if m_lo > m_hi:
                continue
            for m in sorted({m_lo, m_hi}):
                for b in _pow2s(lo, r * hi + 1):
                    # the others sum to s: m + s + 1 in (b/2, b]
                    s_lo = max((r - 1) * lo, b // 2 - m)
                    s_hi = min((r - 1) * m, b - 1 - m)
                    if r == 1:
                        if s_lo <= 0 <= s_hi:
                            sets.append((m,))
                        continue
                    if s_lo > s_hi:
                        continue
                    base, extra = divmod(s_lo, r - 1)
                    others = [base + (i < extra) for i in range(r - 1)]
                    sets.append(tuple(sorted(others)) + (m,))
    return sorted(set(sets), key=lambda t: (len(t), t))


class Load:
    def __init__(self, spec: dict):
        self.cfg, self.mix = spec["config"], spec["traffic"]
        self.seed = spec["seed"]
        self.seconds = float(spec["seconds"])
        self.pool = []
        self.records = []
        self.values = {}
        self.warm = []
        self.opened = 0

    def _body(self, req: dict, rid: str) -> bytes:
        return (json.dumps({"store_id": fleet.store_id(req["device"]),
                            "start_block": req["start"],
                            "stop_block": req["stop"],
                            "channel": req["channel"],
                            "request_id": rid}) + "\n").encode()

    async def _one(self, req: dict, rid: str, due: float, keep: bool,
                   out: list) -> None:
        """One read; its record is kept however it ends (a read cancelled
        after the answer wait counts as failed)."""
        conn = self.pool.pop() if self.pool else None
        sent = time.monotonic()
        ok = False
        try:
            if conn is None:
                self.opened += 1
                conn = await Connection(self.host, self.port).open()
            status, payload = await conn.request(
                "POST", "/v1/decode", fleet.tenant_of(self.cfg,
                                                      req["device"]),
                self._body(req, rid))
            doc = json.loads(payload)
            ok = (status == 200 and "error" not in doc
                  and array_nbytes(doc["values"])
                  == 4 * samples(self.cfg, req))
            if ok and keep:
                self.values[rid] = doc["values"]["b64"]
            self.pool.append(conn)
        except (ConnectionError, OSError, ValueError, KeyError) as exc:
            log(f"{rid}: {exc!r}")
            if conn is not None:
                await conn.close()
        finally:
            out.append({"id": rid, "due": due, "sent": sent,
                        "done": time.monotonic(), "ok": ok,
                        "samples": samples(self.cfg, req)})

    async def _replay(self, sched: list, t0: float, prefix: str,
                      keep=frozenset(), out=None) -> list:
        out = [] if out is None else out
        tasks = []
        for i, req in enumerate(sched):
            due = t0 + req["due"]
            await sleep_until(due)
            tasks.append(asyncio.create_task(self._one(
                req, f"{prefix}{i}", due, i in keep, out)))
        if tasks:
            _done, pending = await asyncio.wait(
                tasks, timeout=max(t0 + self.seconds + ANSWER_WAIT_S
                                   - time.monotonic(), 1.0))
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
        return out

    def prepare(self) -> dict:
        self.schedule = schedule(self.cfg, self.mix, self.seed, self.seconds)
        self.keep = checked(self.cfg, self.mix, self.seed, self.schedule)
        return {"requests": len(self.schedule)}

    async def setup(self, host: str, port: int) -> dict:
        self.host, self.port = host, port
        for _ in range(int(self.mix["connections"])):
            self.pool.append(await Connection(self.host, self.port).open())
        return {}

    async def _bursts(self) -> None:
        """Bursts of concurrent reads of the archive's end, one channel
        of each kind, whose lengths reach every power-of-two class of a
        batch's longest read times its reads and of its summed lengths
        (``burst_sets``): the classes the decode service pads a batch to.
        Replayed rounds of the mix alone left such shapes to compile in
        the window."""
        spm = fleet.samples_per_minute(self.cfg)
        total_samples = archive_samples(self.cfg)
        lo_m, hi_m = self.mix["range_minutes"]
        seen = set()
        for c, ch in enumerate(self.cfg["channels"]):
            if ch["kind"] in seen:
                continue
            seen.add(ch["kind"])
            total = traffic.channel_blocks(self.cfg, c, total_samples)
            lo = max(traffic.channel_blocks(self.cfg, c, int(lo_m * spm)), 1)
            hi = min(traffic.channel_blocks(self.cfg, c, int(hi_m * spm)),
                     total)
            for i, lengths in enumerate(burst_sets(
                    lo, hi, int(self.mix["warmup_burst"]))):
                now = time.monotonic()
                await asyncio.gather(*(
                    self._one({"device": 0, "channel": c,
                               "start": total - n, "stop": total},
                              f"b{c}_{i}_{j}", now, False, self.warm)
                    for j, n in enumerate(lengths)))

    async def warm_round(self, i: int) -> None:
        """The first round sends the bursts; every round replays the mix
        itself for ``warmup_round_s``, on a schedule of its own."""
        n = len(self.warm)
        if i == 0:
            await self._bursts()
        sched = schedule(self.cfg, self.mix, self.seed,
                         float(self.mix["warmup_round_s"]),
                         stream=f"warm{i}")
        await self._replay(sched, time.monotonic(), f"w{i}_", out=self.warm)
        bad = sum(1 for r in self.warm[n:] if not r["ok"])
        if bad:
            log(f"warm-up round {i}: {bad} of {len(self.warm) - n} reads "
                f"failed")

    async def window(self, t0: float, t1: float) -> None:
        await self._replay(self.schedule, t0, "r", keep=self.keep,
                           out=self.records)

    async def finish(self) -> dict:
        for conn in self.pool:
            await conn.close()
        log(f"connections opened beyond the first "
            f"{self.mix['connections']}: {self.opened}")
        out = []
        for i in sorted(self.keep):
            req, rid = self.schedule[i], f"r{i}"
            out.append({**req, "id": rid, "b64": self.values.get(rid)})
        return {"checked": out}


async def serve_setup(fe, cfg: dict, root, log) -> None:
    """Build the archive, or read it from the checkout's cache, and
    attach every device's container to its tenant."""
    from benchkit import server

    t = time.monotonic()
    containers, built = server.archive_containers(
        cfg, root / "artifacts" / "bench-archive")
    log(f"archive: {len(containers)} containers, "
        f"{sum(map(len, containers.values()))} bytes, "
        f"{'built' if built else 'read from the cache'} in "
        f"{time.monotonic() - t:.3f} s")
    await server.attach_archive(fe.host, fe.port, cfg, containers)


def attempted(records: list, t0: float, t1: float) -> list:
    """The reads due inside the window."""
    return [r for r in records if t0 <= r["due"] < t1]


def latency(record: dict) -> float:
    return record["done"] - record["due"]


def _codec(cfg: dict, channel: int) -> dict:
    return cfg["codecs"][cfg["channels"][channel]["kind"]]


def value_readings(cfg: dict, reqs: List[dict],
                   served: Optional[Callable] = None, log=None) -> Dict:
    """``reqs``: ``{device, channel, start, stop, b64}`` per checked read
    (``b64`` None when no answer came); the archive's samples come from
    the configuration's ``archive.data_seed``.  ``served(samples, codec,
    chunk, seed)`` stands in for the served decoder (the control puts a
    second reference there).  An answer that never came counts all its
    samples."""
    spm = fleet.samples_per_minute(cfg)
    n = archive_samples(cfg)
    seed_dec = int(cfg["archive"]["decode_seed"])
    seed_data = int(cfg["archive"]["data_seed"])
    by_chan: Dict[tuple, List[dict]] = {}
    for r in reqs:
        by_chan.setdefault((r["device"], r["channel"]), []).append(r)
    bad = 0
    for (d, c), rs in sorted(by_chan.items()):
        x = fleet.channel_series(cfg, seed_data, d, c, n)
        want_dec = reference.RangeDecoder(_codec(cfg, c), x, spm, seed_dec)
        got_dec = (served(x, _codec(cfg, c), spm, seed_dec)
                   if served is not None else None)
        for r in rs:
            want = want_dec.decode(r["start"], r["stop"])
            if got_dec is not None:
                got = got_dec.decode(r["start"], r["stop"])
            elif r.get("b64") is None:
                got = None
            else:
                got = np.frombuffer(base64.b64decode(r["b64"]), "<f4")
            if got is None or got.shape != want.shape:
                miss = len(want)
            else:
                miss = int(np.count_nonzero(got.view(np.uint32)
                                            != want.view(np.uint32)))
            if miss and log is not None:
                log(f"{fleet.store_id(d)} ch{c} [{r['start']}, "
                    f"{r['stop']}): {miss} of {len(want)} samples differ")
            bad += miss
    return {"values_not_equal": bad}


def readings(cfg: dict, mix: dict, seed: int, done: dict, window: list,
             log=None) -> Dict:
    """The checked answers against the reference, and every read due in
    the window that got no answer or an error document."""
    return {**value_readings(cfg, done["checked"], log=log),
            "answers_missing": sum(1 for r in window if not r["ok"])}


def control(cfg: dict, mix: dict, seed: int, seconds: float,
            minutes: int = 0, log=None) -> Dict:
    """The value readings of the window's checked reads with the
    reference at the precision below the configuration's serving."""
    low = reference.lower_dtype(cfg["dtype"])
    sched = schedule(cfg, mix, seed, seconds)
    reqs = [sched[i] for i in sorted(checked(cfg, mix, seed, sched))]

    def served(x, codec, chunk, dec_seed):
        return reference.RangeDecoder(codec, x, chunk, dec_seed,
                                      work_dtype=low)

    return value_readings(cfg, reqs, served=served, log=log)
