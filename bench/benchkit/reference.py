"""Plain IDEALEM reference: encoder, stream writer and range decoder.

Written from the method (arXiv:1911.06980, Secs. III-V) and the stream
layout the program documents, and independent of the program: it imports
nothing of ``repro`` and reads nothing the program made.  It serves both
configurations: ``encode_stream`` gives the bytes a direct stream must
carry, ``RangeDecoder`` the samples a range read must return.

Semantics, block by block (one channel):

* The payload of a block is the block itself (``std``) or its B-1 deltas
  (``delta``: ``x[k] - x[k-1]``, wrapped into ``[-w/2, w/2)`` for a value
  range of width ``w``); ``delta`` blocks also keep their first sample as
  the base.
* The dictionary holds up to D payload rows in slots ``0..D-1``.  A block
  hits the first slot, in slot order, that passes both gates: the min/max
  gate of eq. 3 with relative tolerance ``r``, evaluated in the working
  precision (``lo - t <= min(x) <= lo + t`` and ``hi - t <= max(x) <= hi +
  t`` with ``t = (hi - lo) * r``), and the two-sample KS test at level
  ``alpha`` (statistic at most the critical distance).  A miss writes its
  row into slot ``count % D`` (an overwrite once ``count >= D``).
* Stream segments (D >= 2): a 34-byte little-endian header, the raw tail,
  then per block ``[0xFF if overwrite][slot]`` and, for a miss, the raw
  block (``std``) or base + deltas (``delta``); a ``delta`` hit carries its
  base.  One segment per feed that completes a block (``MORE``; ``CONT``
  after the first), and a final segment with the tail.
* Decoding a hit: ``std`` permutes its source row by the argsort of
  SplitMix64 keys of ``(seed, global sample index)``; ``delta`` re-anchors
  the source deltas on its own base: ``base, base + cumsum(deltas)``,
  wrapped into the value range.

``work_dtype`` is the precision every value is computed in.  The
configuration states float32; the control (``bench/control.py``) runs the
same reference one precision lower (``lower_dtype``: bfloat16) and must
come out as not correct.
"""
from __future__ import annotations

import struct

import numpy as np

__all__ = ["MODES", "critical_distance", "StreamEncoder", "RangeDecoder",
           "encode_stream", "hit_permutation", "lower_dtype"]

MODES = {"std": 0, "residual": 1, "delta": 2}
_HDR = struct.Struct("<4sBBHBBBBddIH")
_FLAG_RANGE, _FLAG_F32, _FLAG_MORE, _FLAG_CONT = 1, 2, 4, 8
_STREAM_DTYPES = {"float32": np.dtype("<f4")}


def lower_dtype(stated: str) -> np.dtype:
    """The precision one step below the configuration's stated one."""
    if stated != "float32":
        raise ValueError(f"no control precision below {stated!r}")
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def _kolmogorov_sf(lam: float) -> float:
    if lam < 0.1:
        return 1.0
    j = np.arange(1, 41)
    q = 2.0 * np.sum((-1.0) ** (j - 1) * np.exp(-2.0 * j * j * lam * lam))
    return float(min(max(q, 0.0), 1.0))


def critical_distance(alpha: float, n1: int, n2: int) -> float:
    """Largest KS distance whose asymptotic p-value is still >= alpha."""
    en = n1 * n2 / (n1 + n2)
    lo, hi = 1e-9, 10.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if _kolmogorov_sf(mid) >= alpha:
            lo = mid
        else:
            hi = mid
    return lo / np.sqrt(en)


def _ks(xs: np.ndarray, ys: np.ndarray) -> float:
    """Two-sample KS statistic of sorted samples (exact ECDF steps)."""
    both = np.concatenate([xs, ys])
    fx = np.searchsorted(xs, both, side="right") / len(xs)
    fy = np.searchsorted(ys, both, side="right") / len(ys)
    return float(np.max(np.abs(fx - fy)))


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def hit_permutation(seed: int, block: int, B: int) -> np.ndarray:
    """The permutation a ``std`` hit at global block ``block`` receives."""
    with np.errstate(over="ignore"):
        s = _splitmix64(np.asarray(seed % (1 << 64), dtype=np.uint64)
                        + np.uint64(1))
    idx = np.uint64(block) * np.uint64(B) + np.arange(B, dtype=np.uint64)
    return np.argsort(_splitmix64(idx ^ s), kind="stable")


class StreamEncoder:
    """One direct stream, fed chunk by chunk like a wire stream.

    ``feed`` returns the segment bytes the stream must emit for the chunk,
    ``finish`` the final segment.  With ``record=True`` every block's
    decision is kept for ``RangeDecoder``."""

    def __init__(self, codec: dict, stream_dtype: str = "float32",
                 work_dtype=np.float32, record: bool = False):
        self.mode = MODES[codec["mode"]]
        if self.mode not in (MODES["std"], MODES["delta"]):
            raise ValueError(f"reference covers std and delta, not "
                             f"{codec['mode']!r}")
        self.B = int(codec["block_size"])
        self.D = int(codec["num_dict"])
        if self.D < 2:
            raise ValueError("reference covers num_dict >= 2")
        self.max_count = int(codec.get("max_count", 255))
        vr = codec.get("value_range")
        self.value_range = None if vr is None else (float(vr[0]),
                                                    float(vr[1]))
        self.out_dtype = _STREAM_DTYPES[stream_dtype]
        self.wd = np.dtype(work_dtype)
        self.r = np.asarray(codec["rel_tol"], dtype=self.wd)
        self.n = self.B if self.mode == MODES["std"] else self.B - 1
        self.d_crit = critical_distance(codec["alpha"], self.n, self.n)
        self.dmin = np.zeros(self.D, dtype=self.wd)
        self.dmax = np.zeros(self.D, dtype=self.wd)
        self.valid = np.zeros(self.D, dtype=bool)
        self.sorted = [None] * self.D
        self.owner = np.full(self.D, -1, dtype=np.int64)  # block of the miss
        self.count = 0
        self.blocks_done = 0
        self.tail = np.zeros(0, dtype=self.wd)
        self.started = False
        self.record = record
        self.rec_src, self.rec_base, self.rec_rows = [], [], {}

    # --------------------------------------------------------- transforms
    def _payload(self, blocks: np.ndarray):
        if self.mode == MODES["std"]:
            return blocks, None
        bases = blocks[:, 0].copy()
        d = blocks[:, 1:] - blocks[:, :-1]
        if self.value_range is not None:
            w = self.wd.type(self.value_range[1] - self.value_range[0])
            half = self.wd.type(0.5) * w
            d = np.mod(d + half, w) - half
        return d.astype(self.wd), bases

    def _decide(self, x: np.ndarray):
        xmin, xmax = x.min(), x.max()
        xs = np.sort(x)
        slots = np.flatnonzero(self.valid)
        if len(slots):
            lo, hi = self.dmin[slots], self.dmax[slots]
            t = (hi - lo) * self.r
            ok = ((lo - t <= xmin) & (xmin <= lo + t)
                  & (hi - t <= xmax) & (xmax <= hi + t))
            for s in slots[ok]:
                if _ks(xs, self.sorted[s]) <= self.d_crit:
                    return True, int(s), False
        s = self.count % self.D
        ovw = self.count >= self.D
        self.sorted[s] = xs
        self.dmin[s], self.dmax[s] = xmin, xmax
        self.valid[s] = True
        self.count += 1
        return False, s, ovw

    # ------------------------------------------------------------ segments
    def _header(self, nb: int, tail: np.ndarray, more: bool) -> bytes:
        flags = _FLAG_F32 if self.out_dtype == np.float32 else 0
        rmin = rmax = 0.0
        if self.value_range is not None:
            flags |= _FLAG_RANGE
            rmin, rmax = self.value_range
        if more:
            flags |= _FLAG_MORE
        if self.started:
            flags |= _FLAG_CONT
        return _HDR.pack(b"IDLM", 2, self.mode, self.B, self.D,
                         self.max_count, flags, 0, rmin, rmax, nb,
                         len(tail)) + tail.astype(self.out_dtype).tobytes()

    def feed(self, chunk: np.ndarray) -> bytes:
        joined = np.concatenate([self.tail, np.asarray(chunk, self.wd)])
        nb = len(joined) // self.B
        self.tail = joined[nb * self.B:]
        if nb == 0:
            return b""
        blocks = joined[:nb * self.B].reshape(nb, self.B)
        payload, bases = self._payload(blocks)
        body = bytearray()
        od = self.out_dtype
        for i in range(nb):
            hit, s, ovw = self._decide(payload[i])
            k = self.blocks_done + i
            if hit:
                body.append(s)
                if bases is not None:
                    body += bases[i:i + 1].astype(od).tobytes()
            else:
                if ovw:
                    body.append(0xFF)
                body.append(s)
                if bases is None:
                    body += blocks[i].astype(od).tobytes()
                else:
                    body += bases[i:i + 1].astype(od).tobytes()
                    body += payload[i].astype(od).tobytes()
                self.owner[s] = k
                if self.record:
                    self.rec_rows[k] = payload[i].copy()
            if self.record:
                self.rec_src.append(int(self.owner[s]))
                self.rec_base.append(None if bases is None else bases[i])
        self.blocks_done += nb
        seg = self._header(nb, np.zeros(0, self.wd), True) + bytes(body)
        self.started = True
        return seg

    def finish(self) -> bytes:
        return self._header(0, self.tail, False)


def encode_stream(codec: dict, samples: np.ndarray, chunk: int,
                  work_dtype=np.float32, finish: bool = True) -> bytes:
    """The bytes of a direct stream fed ``samples`` in ``chunk``-sample
    feeds (and closed, unless ``finish`` is False)."""
    enc = StreamEncoder(codec, work_dtype=work_dtype)
    parts = [enc.feed(samples[i:i + chunk])
             for i in range(0, len(samples), chunk)]
    if finish:
        parts.append(enc.finish())
    return b"".join(parts)


class RangeDecoder:
    """Samples of block ranges of one channel, decoded from the reference
    encoder's own decisions over the channel's raw samples."""

    def __init__(self, codec: dict, samples: np.ndarray, chunk: int,
                 seed: int, work_dtype=np.float32):
        self.enc = StreamEncoder(codec, work_dtype=work_dtype, record=True)
        for i in range(0, len(samples), chunk):
            self.enc.feed(samples[i:i + chunk])
        self.seed = seed

    @property
    def total_blocks(self) -> int:
        return self.enc.blocks_done

    def decode(self, start: int, stop: int) -> np.ndarray:
        e = self.enc
        out = []
        for k in range(start, stop):
            src = e.rec_src[k]
            row = e.rec_rows[src]
            if e.mode == MODES["std"]:
                vals = row if src == k else row[hit_permutation(
                    self.seed, k, e.B)]
            else:
                base = e.rec_base[k]
                vals = np.concatenate([[base], base + np.cumsum(row)])
                vals = vals.astype(e.wd)
                if e.value_range is not None:
                    rmin, rmax = (e.wd.type(v) for v in e.value_range)
                    vals = np.mod(vals - rmin, rmax - rmin) + rmin
            out.append(np.asarray(vals, dtype=e.wd))
        return np.concatenate(out).astype(e.out_dtype)
