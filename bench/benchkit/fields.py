"""Hurricane ISABEL field snapshots, made from the run's seed, and the plain
reference of the error-bounded ``residual`` stream they are encoded to.

The data.  SDRBench's Hurricane ISABEL set (the WRF run of the IEEE
Visualization 2004 contest) holds 13 fields of 100 x 500 x 500 float32
(z, y, x; x fastest) at 48 hourly snapshots.  With no copy of it here,
each (field, snapshot, level) slice of 500 x 500 samples is generated:

* state fields (P, TC, U, V, W, QVAPOR) are smooth Gaussian random fields
  with a power-law spectrum ``|F(k)|^2 ~ (k^2 + k0^2)^(-beta/2)``, whose
  phases turn by a per-wavenumber rate each hour (so snapshots of one
  level are related), mapped onto the field's value range (the mean
  drifts with height) and clipped to it;
* hydrometeor fields (CLOUD, PRECIP, QCLOUD, QGRAUP, QICE, QRAIN, QSNOW)
  are zero outside rain-band masks -- a second random field over a
  threshold set by the field's zero share -- and inside them rise from 0
  at the mask's edge towards the range's top.

Every slice depends only on ``(seed, field, level, snapshot)`` and the
configuration, so the load generator and the reference make the same
samples independently.  Value ranges, zero shares and spectra are the
configuration's ``fields`` and ``assumed``.

The reference.  Written from the method (arXiv:1911.06980 Secs. III-V)
and the stream layout, independent of the program (it imports nothing of
``repro``):

* a block of B samples keeps its first as the base and carries the B-1
  residuals ``x[k] - x[0]`` (``residual``, eq. 4; no value range);
* the dictionary holds up to D residual rows; a block hits the first slot,
  in slot order, that passes the min/max gate (eq. 3, relative tolerance
  ``r``, in the working precision), the pointwise bound (the largest
  ``|p - row|`` at most the bound, both rounded to the working precision)
  and the two-sample KS test at level ``alpha`` (exact ECDF distance at
  most the critical distance); a miss writes its row into slot
  ``count % D``;
* a segment is a 34-byte little-endian header (version 3, flags F32,
  MORE/CONT and the error-bounded flag EB), the tail, then per block
  ``[0xFF if overwrite][slot][base]`` and, for a miss, the B-1 residuals;
* decoding is ``base, base + row`` (no permutation: the bound holds on
  every sample).

``work_dtype`` is the precision every value is computed in: float32 as
stated, or one step lower (bfloat16) for the control, which must come out
as not correct.
"""
from __future__ import annotations

import struct

import numpy as np

from .reference import critical_distance

__all__ = ["STATE", "SPARSE", "field_bound", "codec_doc",
           "level_slice", "level_series", "stream_id", "tenant_of",
           "ResidualEncoder", "encode_stream", "decode_stream",
           "bound_violations", "scan_bytes"]

STATE, SPARSE = "state", "sparse"
MODE_RESIDUAL = 1
_HDR = struct.Struct("<4sBBHBBBBddIH")
_F32, _MORE, _CONT, _EB = 2, 4, 8, 32
_VERSION_EB = 3
_DECISION_BYTES = 1 + 4 + 1   # is_hit (bool), slot (int32), overwrite (bool)


def field_bound(cfg: dict, field: int) -> float:
    """The absolute pointwise bound of a field: the configuration's
    relative bound times the field's value range."""
    lo, hi = cfg["fields"][field]["range"]
    return float(cfg["relative_bound"]) * (float(hi) - float(lo))


def codec_doc(cfg: dict, field: int) -> dict:
    """The wire codec configuration of a field's streams."""
    return {**cfg["codec"], **cfg["engine"],
            "error_bound": field_bound(cfg, field)}


def stream_id(cfg: dict, field: int, level: int) -> str:
    return f"{cfg['fields'][field]['name']}/z{level:03d}"


def tenant_of(cfg: dict, field: int) -> str:
    return f"isabel-{cfg['fields'][field]['name']}"


# ------------------------------------------------------------------ data
def _rng(seed: int, field: int, level: int, part: int):
    return np.random.default_rng([seed % (1 << 64), field, level, part])


def _grf(seed: int, field: int, level: int, part: int, snapshot: int,
         ny: int, nx: int, spec: dict) -> np.ndarray:
    """One random field of unit variance (up to the clipping of the
    spectrum's finite grid): fixed amplitudes and phases per (field, level,
    part), each phase turning by its own rate per snapshot."""
    rng = _rng(seed, field, level, part)
    ky = np.fft.fftfreq(ny)[:, None] * ny
    kx = np.fft.rfftfreq(nx)[None, :] * nx
    k2 = ky * ky + kx * kx
    amp = (k2 + float(spec["k0"]) ** 2) ** (-float(spec["beta"]) / 4.0)
    amp[0, 0] = 0.0
    amp = amp * rng.rayleigh(1.0, size=amp.shape)
    phase = rng.uniform(0.0, 2.0 * np.pi, size=amp.shape)
    rate = rng.normal(0.0, float(spec["phase_rate"]), size=amp.shape)
    coef = amp * np.exp(1j * (phase + snapshot * rate))
    g = np.fft.irfft2(coef, s=(ny, nx), norm="ortho")
    # Parseval over the half spectrum: the columns 1 .. nx/2 - 1 stand for
    # two conjugate columns each
    w = np.full(amp.shape[1], 2.0)
    w[0] = 1.0
    if nx % 2 == 0:
        w[-1] = 1.0
    rms = np.sqrt(np.sum((amp * amp) * w[None, :]) / (ny * nx))
    return g / max(rms, 1e-30)


def level_slice(cfg: dict, seed: int, field: int, snapshot: int,
                level: int) -> np.ndarray:
    """The ``y * x`` float32 samples of one level of one snapshot of one
    field, row-major (x fastest)."""
    f = cfg["fields"][field]
    ny, nx = int(cfg["grid"]["y"]), int(cfg["grid"]["x"])
    nz = int(cfg["grid"]["z"])
    lo, hi = (float(v) for v in f["range"])
    spec = cfg["spectra"][f["kind"]]
    g = _grf(seed, field, level, 0, snapshot, ny, nx, spec)
    if f["kind"] == STATE:
        # mean drifts from the upper to the lower part of the range with
        # height; +-4 standard deviations span the range
        up = level / max(nz - 1, 1)
        mid = lo + (hi - lo) * (0.6 - 0.2 * up)
        x = mid + 0.125 * (hi - lo) * g
    elif f["kind"] == SPARSE:
        from statistics import NormalDist

        cut = NormalDist().inv_cdf(float(f["zero_share"]))
        v = _grf(seed, field, level, 1, snapshot, ny, nx, spec)
        rise = np.clip(0.5 * (g - cut), 0.0, 1.0)
        x = hi * rise * np.clip(0.6 + 0.2 * v, 0.0, 1.0)
    else:
        raise ValueError(f"unknown field kind {f['kind']!r}")
    return np.clip(x, lo, hi).astype(np.float32).reshape(-1)


def level_series(cfg: dict, seed: int, field: int, level: int,
                 snapshots: int) -> np.ndarray:
    """One stream's samples: its level of snapshots ``0 .. snapshots-1``,
    in time order."""
    parts = [level_slice(cfg, seed, field, t, level)
             for t in range(snapshots)]
    return (np.concatenate(parts) if parts
            else np.zeros(0, dtype=np.float32))


# ------------------------------------------------------------- reference
def _ks(xs: np.ndarray, ys: np.ndarray) -> float:
    """Two-sample KS statistic of sorted samples (exact ECDF steps,
    evaluated at every sample point of both: tied values take the count
    at the end of their run)."""
    both = np.concatenate([xs, ys])
    fx = np.searchsorted(xs, both, side="right") / len(xs)
    fy = np.searchsorted(ys, both, side="right") / len(ys)
    return float(np.max(np.abs(fx - fy)))


class ResidualEncoder:
    """One error-bounded ``residual`` stream, fed chunk by chunk like a
    wire stream: ``feed`` returns the segment bytes for the chunk,
    ``finish`` the final segment."""

    def __init__(self, codec: dict, bound: float, work_dtype=np.float32):
        if codec["mode"] != "residual" or codec.get("value_range"):
            raise ValueError("the field reference covers residual mode "
                             "without a value range")
        self.B = int(codec["block_size"])
        self.D = int(codec["num_dict"])
        if self.D < 2:
            raise ValueError("the field reference covers num_dict >= 2")
        self.max_count = int(codec.get("max_count", 255))
        self.wd = np.dtype(work_dtype)
        self.r = self.wd.type(codec["rel_tol"])
        self.bound = self.wd.type(bound)
        self.n = self.B - 1
        self.d_crit = critical_distance(codec["alpha"], self.n, self.n)
        self.rows = np.zeros((self.D, self.n), dtype=self.wd)
        self.sorted = np.zeros((self.D, self.n), dtype=self.wd)
        self.dmin = np.zeros(self.D, dtype=self.wd)
        self.dmax = np.zeros(self.D, dtype=self.wd)
        self.valid = np.zeros(self.D, dtype=bool)
        self.count = 0
        self.tail = np.zeros(0, dtype=self.wd)
        self.started = False

    def _decide(self, p: np.ndarray, xs: np.ndarray):
        xmin, xmax = xs[0], xs[-1]
        slots = np.flatnonzero(self.valid)
        if len(slots):
            lo, hi = self.dmin[slots], self.dmax[slots]
            t = (hi - lo) * self.r
            ok = ((lo - t <= xmin) & (xmin <= lo + t)
                  & (hi - t <= xmax) & (xmax <= hi + t))
            cand = slots[ok]
            if len(cand):
                err = np.max(np.abs(p[None, :] - self.rows[cand]), axis=1)
                for s in cand[err <= self.bound]:
                    if _ks(xs, self.sorted[s]) <= self.d_crit:
                        return True, int(s), False
        s = self.count % self.D
        ovw = self.count >= self.D
        self.rows[s], self.sorted[s] = p, xs
        self.dmin[s], self.dmax[s] = xmin, xmax
        self.valid[s] = True
        self.count += 1
        return False, s, ovw

    def _header(self, nb: int, tail: np.ndarray, more: bool) -> bytes:
        flags = _F32 | _EB | (_MORE if more else 0) \
            | (_CONT if self.started else 0)
        return _HDR.pack(b"IDLM", _VERSION_EB, MODE_RESIDUAL, self.B,
                         self.D, self.max_count, flags, 0, 0.0, 0.0, nb,
                         len(tail)) + tail.astype("<f4").tobytes()

    def feed(self, chunk: np.ndarray) -> bytes:
        joined = np.concatenate([self.tail, np.asarray(chunk, self.wd)])
        nb = len(joined) // self.B
        self.tail = joined[nb * self.B:]
        if nb == 0:
            return b""
        blocks = joined[:nb * self.B].reshape(nb, self.B)
        bases = blocks[:, 0]
        pay = (blocks[:, 1:] - bases[:, None]).astype(self.wd)
        srt = np.sort(pay, axis=1)
        body = bytearray()
        for i in range(nb):
            hit, s, ovw = self._decide(pay[i], srt[i])
            if ovw:
                body.append(0xFF)
            body.append(s)
            body += bases[i:i + 1].astype("<f4").tobytes()
            if not hit:
                body += pay[i].astype("<f4").tobytes()
        seg = self._header(nb, np.zeros(0, self.wd), True) + bytes(body)
        self.started = True
        return seg

    def finish(self) -> bytes:
        return self._header(0, self.tail, False)


def encode_stream(codec: dict, bound: float, samples: np.ndarray,
                  chunk: int, work_dtype=np.float32) -> bytes:
    """The bytes of a direct stream fed ``samples`` in ``chunk``-sample
    feeds and closed."""
    enc = ResidualEncoder(codec, bound, work_dtype=work_dtype)
    parts = [enc.feed(samples[i:i + chunk])
             for i in range(0, len(samples), chunk)]
    return b"".join(parts + [enc.finish()])


def decode_stream(blob: bytes) -> np.ndarray:
    """Float32 samples of an error-bounded ``residual`` stream (a chain of
    segments), decoded from its bytes alone.  Raises ``ValueError`` on a
    malformed stream."""
    out = []
    rows = None
    count = 0
    off = 0
    mv = memoryview(blob)
    while off < len(blob):
        try:
            (magic, ver, mode, B, D, _c, flags, _r, _a, _b, nb,
             tail) = _HDR.unpack_from(mv, off)
        except struct.error:
            raise ValueError("truncated header") from None
        if magic != b"IDLM" or mode != MODE_RESIDUAL or not flags & _F32:
            raise ValueError(f"not a float32 residual segment at {off}")
        off += _HDR.size
        n = B - 1
        if rows is None or not flags & _CONT:
            rows, count = np.zeros((D, n), np.float32), 0
        for _ in range(nb):
            ovw = mv[off] == 0xFF
            off += int(ovw)
            s = mv[off]
            base = np.frombuffer(blob, "<f4", 1, off + 1)[0]
            off += 5
            miss = ovw or (count < D and s == count % D)
            if miss:
                rows[s] = np.frombuffer(blob, "<f4", n, off)
                off += 4 * n
                count += 1
            out.append(np.concatenate([[base], base + rows[s]]))
        if off + 4 * tail > len(blob):
            raise ValueError("tail overruns the stream")
        out.append(np.frombuffer(blob, "<f4", tail, off))
        off += 4 * tail
    return (np.concatenate(out).astype(np.float32) if out
            else np.zeros(0, np.float32))


def bound_violations(x: np.ndarray, y: np.ndarray, bound: float,
                     B: int) -> int:
    """Decoded samples ``y`` farther from the originals ``x`` than the
    bound plus the float32 rounding the stream adds: half an ulp of the
    decoded value (the sum base + residual), half an ulp of the residual
    (the difference x - base) and one ulp of the bound (the gate compares
    against the bound rounded to float32).  A sample missing from ``y``
    counts as a violation."""
    m = min(len(x), len(y))
    x64, y64 = x[:m].astype(np.float64), y[:m].astype(np.float64)
    nb = m // B
    base = np.repeat(x[:nb * B].reshape(nb, B)[:, :1], B, axis=1).ravel()
    res = np.zeros(m, np.float32)
    res[:nb * B] = x[:nb * B] - base
    slop = (0.5 * np.spacing(np.abs(y[:m])) + 0.5 * np.spacing(np.abs(res))
            + np.spacing(np.float32(bound))).astype(np.float64)
    bad = np.abs(y64 - x64) > bound + slop
    return int(np.sum(bad | ~np.isfinite(y64))) + (len(x) - m)


def scan_bytes(nb: float, n: int, D: int, itemsize: int = 4) -> float:
    """Least HBM bytes of one error-bounded direct scan call over ``nb``
    blocks of one lane: payload read once, decisions and the demotion
    count written once, and the carry -- sorted rows, raw rows, extremes,
    valid flags and the FIFO count -- read once and written once."""
    carry = 2 * D * n * itemsize + 2 * D * itemsize + D + 4
    return nb * (n * itemsize + _DECISION_BYTES) + 4 + 2 * carry
