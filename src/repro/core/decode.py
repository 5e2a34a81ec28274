"""Unified decode engine: one reconstruction path for every consumer.

Reconstruction in IDEALEM (paper Sec. V-A2/V-B2) is per-block math: a hit
is either a random permutation of its source block (std mode) or the
stored transformed values re-anchored on the hit's own base (res/delta,
delta adding an in-block cumsum).  Before this module, that math lived in
three near-duplicate host walks -- ``core.stream.decode_stream``,
``store.reader.decode_range(s)`` and the ``DecompressionService`` flush
loop.  Now every consumer builds a :class:`DecodePlan` -- the explicit
struct-of-arrays form of "what feeds each output block" -- and calls
:func:`reconstruct` on it (DESIGN.md Sec. 8).

Plans are backend-agnostic.  Three backends produce byte-identical output:

  ``numpy``   -- the host reference (fancy-index gather + vectorized math);
  ``jax``     -- jnp gather / permutation-apply / re-anchor, with the delta
                 cumsum as a sequential ``fori_loop`` (XLA's associative
                 ``cumsum`` rounds f64 differently -- measured, see
                 tests/test_decode_backends.py);
  ``pallas``  -- the jax path with the cumsum in the
                 ``repro.kernels.seq_cumsum`` kernel.

Byte-exactness on an accelerator is *checked, never assumed*: the first
time a (backend, mode, dtype, value_range, block_size) combination runs,
a small probe plan is reconstructed on both paths and compared
``tobytes()``-for-``tobytes()``.  A device result that differs is a routing
fact: the engine logs it once and serves that combination from the host,
counted as a ``fallback`` in :func:`decode_stats`.  A device path that
*raises* is a fault, never a route: an explicit ``jax``/``pallas`` request
(and an ``auto`` probe) raises :class:`~repro.errors.DeviceBackendError`.
The TPU has no f64, so ``auto`` routes f64 plans to the host by rule --
counted as ``host_routes`` -- and an explicit device backend refuses them.

Device dispatch shapes are padded to powers of two (pad rows are zero-
payload misses the per-block math ignores), so serving traffic reuses a
handful of compiled shapes instead of recompiling per request length.
"""
from __future__ import annotations

import json
import logging
import os
import time
from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..errors import DeviceBackendError
from .transforms import np_wrap_range

__all__ = [
    "MODE_STD", "MODE_RESIDUAL", "MODE_DELTA", "BACKENDS",
    "DecodePlan", "PlanPart", "plan_from_parsed", "pad_parts",
    "reconstruct", "resolve_backend", "decode_sources", "hit_perms",
    "gather_rows", "decode_stats", "reset_decode_stats",
    "AUTOTUNE_VERSION", "AutotuneCacheError", "load_autotune",
    "save_autotune", "reset_autotune", "autotune_choices", "autotune_cached",
]

MODE_STD, MODE_RESIDUAL, MODE_DELTA = 0, 1, 2

#: Recognised ``backend=`` values (plus ``"auto"``: the measured-best
#: backend for the plan's (mode, dtype, size bucket) -- see the autotuner
#: below).
BACKENDS = ("numpy", "jax", "pallas")

logger = logging.getLogger("repro.core.decode")

# Per-process accounting of backend routing, held as counters on the
# repro.obs registry (ISSUE 8) -- :func:`decode_stats` is a dict-shaped
# compat view over them, byte-compatible with the pre-registry API that
# tests pin.  ``fallbacks`` counts calls that *asked* for a device
# backend but ran on the host because the exactness probe found the
# backend not byte-exact here; ``host_routes`` counts ``auto`` calls the
# engine routed to the host because the device has no exact form of the
# plan's dtype (f64 on a TPU).  Tests pin both, so a host result cannot
# masquerade as device coverage.  ``autotune_probes``/``autotune_hits``
# count measured first-use probes vs cached ``"auto"`` resolutions.
# Counters are individually locked, so the pipelined service's
# worker-thread bumps stay exact against the caller's reads.
_STAT_HELP = {
    "host_calls": "reconstruct calls served on the numpy host path",
    "device_calls": "reconstruct calls served on a device backend",
    "fallbacks": "device requests served on the host: probe not exact",
    "host_routes": "backend=auto calls routed to the host: no exact dtype",
    "autotune_probes": "backend=auto measured first-use probes",
    "autotune_hits": "backend=auto cached resolutions",
}
_stat_counters = {
    key: obs.registry().counter(f"repro_decode_{key}_total", help_text)
    for key, help_text in _STAT_HELP.items()
}
# resolved-backend routing, labelled per backend (the "backend choice
# counts" metric; decode_stats keeps only the host/device aggregate)
_backend_counters = {
    b: obs.registry().counter("repro_decode_backend_calls_total",
                              "reconstruct calls per resolved backend",
                              labels={"backend": b})
    for b in ("numpy", "jax", "pallas")
}
_exact_cache: dict = {}


def _bump(key: str, n: int = 1) -> None:
    _stat_counters[key].inc(n)


def decode_stats() -> dict:
    snap = {key: int(c.value) for key, c in _stat_counters.items()}
    return {**snap, "autotune_choices": autotune_choices()}


def reset_decode_stats() -> None:
    for c in _stat_counters.values():
        c.reset()
    for c in _backend_counters.values():
        c.reset()


# ------------------------------------------------------------------ the plan

@dataclass(frozen=True)
class DecodePlan:
    """Everything :func:`reconstruct` needs, as flat arrays.

    ``payloads`` holds each *source* block's stored values once (misses in
    stream order, plus any snapshot-materialized virtual misses and -- for
    padded batch plans -- one trailing all-zero row).  ``src[i]`` is the
    payload row feeding output block ``i``; hits share their source miss's
    row.  ``block_idx[i]`` is the block's global position in its stream:
    std-mode hit permutations are keyed on ``(seed, block_idx)``
    (:func:`hit_perms`), which is what makes any sub-range reconstruct
    byte-identically to the same rows of a full decode.  ``overwrite`` is
    carried for completeness/debugging; FIFO overwrites are a framing
    concern and do not affect reconstruction.
    """

    mode: int
    block_size: int
    dtype: np.dtype
    value_range: Optional[Tuple[float, float]]
    payloads: np.ndarray            # (n_rows, P) source payload rows
    src: np.ndarray                 # (nb,) payload row per output block
    bases: Optional[np.ndarray]     # (nb,) res/delta modes, else None
    is_hit: np.ndarray              # (nb,) bool
    block_idx: np.ndarray           # (nb,) global block positions
    seed: int = 0
    overwrite: Optional[np.ndarray] = None  # (nb,) bool, informational
    # Error-bounded streams (FLAG_EB) pin hits to the stored row order:
    # the std-mode hit permutation is skipped so max|x - x_hat| over a hit
    # is exactly the bound the encoder enforced.  Res/delta modes never
    # permute, so the flag only changes std-mode reconstruction.
    no_perm: bool = False

    @property
    def nb(self) -> int:
        return len(self.src)

    @property
    def payload_width(self) -> int:
        return int(self.payloads.shape[1])


class PlanPart(NamedTuple):
    """One request's worth of plan inputs, sources already resolved
    (``rows[i]`` is the payload feeding the part's block ``i``).  Parts
    from many requests -- across containers -- are padded into one
    :class:`DecodePlan` by :func:`pad_parts`."""

    rows: np.ndarray                # (n, P) per-block source payloads
    bases: Optional[np.ndarray]     # (n,) or None (std mode)
    is_hit: np.ndarray              # (n,) bool
    block_idx: np.ndarray           # (n,) global block positions


# ------------------------------------------------------- plan construction

def decode_sources(is_hit: np.ndarray, slot: np.ndarray) -> np.ndarray:
    """Payload row (miss ordinal) feeding each block: misses feed
    themselves, hits feed the most recent miss written to their slot.
    A hit with no preceding miss on its slot is malformed input."""
    from .stream import StreamFormatError  # typed error lives with the parser
    nb = len(is_hit)
    miss_pos = np.flatnonzero(~is_hit)
    hit_pos = np.flatnonzero(is_hit)
    src = np.zeros(nb, dtype=np.int64)
    src[miss_pos] = np.arange(len(miss_pos))
    if len(hit_pos):
        hit_slots = slot[hit_pos]
        miss_slots = slot[miss_pos]
        for s in np.unique(hit_slots):
            hp = hit_pos[hit_slots == s]
            mp = miss_pos[miss_slots == s]
            j = np.searchsorted(mp, hp) - 1
            if len(mp) == 0 or np.any(j < 0):
                raise StreamFormatError(f"hit on slot {s} before any miss")
            src[hp] = src[mp[j]]
    return src


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """SplitMix64 finalizer on uint64 arrays (wrapping arithmetic is the
    point; numpy only flags the wrap for 0-d inputs)."""
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def hit_perms(seed: int, block_idx: np.ndarray, B: int) -> np.ndarray:
    """Per-hit reconstruction permutations, stateless in the block position.

    Each permutation is the argsort of SplitMix64 keys of (seed, global
    sample index), so the permutation a block receives depends only on
    ``(seed, its index in the stream)`` -- never on how many other blocks
    share the reconstruct call."""
    with np.errstate(over="ignore"):  # seed 2**64-1 wraps on the +1
        s = _splitmix64(np.uint64(seed & 0xFFFFFFFFFFFFFFFF) + np.uint64(1))
        samp = (np.asarray(block_idx, dtype=np.uint64)[:, None] * np.uint64(B)
                + np.arange(B, dtype=np.uint64)[None, :])
    return np.argsort(_splitmix64(samp ^ s), axis=1, kind="stable")


def gather_rows(u8: np.ndarray, dt: np.dtype, offs: np.ndarray,
                width: int) -> np.ndarray:
    """One fancy-indexing pass over raw stream/container bytes:
    ``width``-value rows at byte offsets ``offs``."""
    if width == 0 or len(offs) == 0:
        return np.zeros((len(offs), width), dtype=dt)
    return u8[offs[:, None] + np.arange(width * dt.itemsize)].view(dt)


def plan_from_parsed(header, parsed, seed: int = 0, i0: int = 0) -> DecodePlan:
    """Plan for a full sequential decode of one parsed stream.

    ``header``/``parsed`` are duck-typed (``repro.core.stream`` supplies
    ``StreamHeader`` and its struct-of-arrays ``_Parsed``); block positions
    are ``i0..i0+nb`` (``i0`` offsets a restart section within a larger
    stream so permutations stay keyed on global position)."""
    nb = len(parsed.is_hit)
    return DecodePlan(
        mode=header.mode, block_size=header.block_size,
        dtype=np.dtype(header.dtype), value_range=header.value_range,
        payloads=parsed.payloads,
        src=decode_sources(parsed.is_hit, parsed.slot),
        bases=parsed.bases, is_hit=parsed.is_hit,
        block_idx=i0 + np.arange(nb, dtype=np.int64), seed=seed,
        overwrite=parsed.overwrite,
        no_perm=bool(getattr(header, "error_bounded", False)))


def pad_parts(mode: int, block_size: int, dtype, value_range,
              parts: Sequence[PlanPart], seed: int = 0,
              no_perm: bool = False) -> Tuple[DecodePlan, int]:
    """Pad R ragged request parts into ONE plan of shape ``(R * nbm,)``.

    The read-side mirror of the encoder's masked ragged batches: requests
    are stacked on a leading axis and padded to the longest; pad blocks
    are all-miss with a shared all-zero payload row, dead weight the
    per-block math ignores.  Returns ``(plan, nbm)``; callers reshape
    ``reconstruct(plan)`` to ``(R, nbm, B)`` and slice each request back
    out.
    """
    dt = np.dtype(dtype)
    R = len(parts)
    lens = [len(p.is_hit) for p in parts]
    nbm = max(lens)
    P = block_size if mode == MODE_STD else block_size - 1
    n_rows = sum(lens)
    payloads = np.zeros((n_rows + 1, P), dtype=dt)   # last row: shared pad
    src = np.full((R, nbm), n_rows, dtype=np.int64)
    is_hit = np.zeros((R, nbm), dtype=bool)
    block_idx = np.zeros((R, nbm), dtype=np.int64)
    bases = None if mode == MODE_STD else np.zeros((R, nbm), dtype=dt)
    pos = 0
    for r, (p, n) in enumerate(zip(parts, lens)):
        payloads[pos:pos + n] = p.rows
        src[r, :n] = np.arange(pos, pos + n)
        is_hit[r, :n] = p.is_hit
        block_idx[r, :n] = p.block_idx
        if bases is not None:
            bases[r, :n] = p.bases
        pos += n
    plan = DecodePlan(
        mode=mode, block_size=block_size, dtype=dt, value_range=value_range,
        payloads=payloads, src=src.ravel(),
        bases=None if bases is None else bases.ravel(),
        is_hit=is_hit.ravel(), block_idx=block_idx.ravel(), seed=seed,
        no_perm=no_perm)
    return plan, nbm


# ------------------------------------------------------------ numpy backend

def _reconstruct_numpy(plan: DecodePlan) -> np.ndarray:
    rows = plan.payloads[plan.src]          # fancy index: always a fresh copy
    if plan.mode == MODE_STD:
        out = rows
        hit_pos = (np.zeros(0, dtype=np.int64) if plan.no_perm
                   else np.flatnonzero(plan.is_hit))
        if len(hit_pos):
            perm = hit_perms(plan.seed, plan.block_idx[hit_pos],
                             plan.block_size)
            out[hit_pos] = np.take_along_axis(rows[hit_pos], perm, axis=1)
        return out
    base = plan.bases[:, None]
    t = rows if plan.mode == MODE_RESIDUAL else np.cumsum(rows, axis=1)
    out = np.concatenate([base, base + t], axis=1)
    if plan.value_range is not None:
        out = np_wrap_range(out, *plan.value_range)
    return out


# ----------------------------------------------------------- device backend

def _pow2(n: int) -> int:
    return max(1, 1 << (int(n) - 1).bit_length())


_dev_fns: dict = {}


def _device_fn(backend: str, mode: int, value_range):
    """Jitted device reconstruct for one (backend, mode, range) combo.
    Gather, permutation apply, re-anchor and (delta) sequential cumsum all
    run on device; inputs arrive pre-padded to power-of-two shapes."""
    key = (backend, mode, value_range)
    fn = _dev_fns.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    def _seq_cumsum_jnp(x):
        P = x.shape[1]

        def body(j, carry):
            acc, out = carry
            v = jax.lax.dynamic_slice_in_dim(x, j, 1, axis=1)[:, 0]
            acc = acc + v
            out = jax.lax.dynamic_update_slice_in_dim(
                out, acc[:, None], j, axis=1)
            return acc, out

        out0 = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros_like(x), x[:, :1], 0, axis=1)
        _, out = jax.lax.fori_loop(1, P, body, (x[:, 0], out0))
        return out

    if mode == MODE_STD:
        def fn(payloads, src, perm):
            rows = jnp.take(payloads, src, axis=0)
            return jnp.take_along_axis(rows, perm, axis=1)
    else:
        def fn(payloads, src, bases):
            rows = jnp.take(payloads, src, axis=0)
            if mode == MODE_RESIDUAL:
                t = rows
            elif backend == "pallas":
                from repro.kernels.seq_cumsum import seq_cumsum
                t = seq_cumsum(rows)
            else:
                t = _seq_cumsum_jnp(rows)
            out = jnp.concatenate([bases[:, None], bases[:, None] + t],
                                  axis=1)
            if value_range is not None:
                rmin, rmax = value_range
                out = jnp.mod(out - rmin, rmax - rmin) + rmin
            return out

    fn = _dev_fns[key] = jax.jit(fn)
    return fn


def _run_device(plan: DecodePlan, backend: str) -> np.ndarray:
    """Dispatch one plan on a device backend, padding shapes to powers of
    two so serving traffic reuses compiled shapes.  Only f64 plans run with
    64-bit mode on: under it every integer literal is int64, which Mosaic
    cannot lower in a Pallas index map (and the TPU only emulates)."""
    import jax
    dt = np.dtype(plan.dtype)
    nb, P = plan.nb, plan.payload_width
    nbp, nrp = _pow2(nb), _pow2(len(plan.payloads) + 1)
    payloads = np.zeros((nrp, P), dtype=dt)
    payloads[:len(plan.payloads)] = plan.payloads
    src = np.full(nbp, nrp - 1, dtype=np.int64)  # pads read the zero row
    src[:nb] = plan.src
    fn = _device_fn(backend, plan.mode, plan.value_range)
    with jax.enable_x64(dt == np.float64):
        if plan.mode == MODE_STD:
            perm = np.broadcast_to(
                np.arange(plan.block_size, dtype=np.int64),
                (nbp, plan.block_size)).copy()
            hit_pos = (np.zeros(0, dtype=np.int64) if plan.no_perm
                       else np.flatnonzero(plan.is_hit))
            if len(hit_pos):
                perm[hit_pos] = hit_perms(plan.seed, plan.block_idx[hit_pos],
                                          plan.block_size)
            out = fn(payloads, src, perm)
        else:
            bases = np.zeros(nbp, dtype=dt)
            bases[:nb] = plan.bases
            out = fn(payloads, src, bases)
        res = np.asarray(out)
    return res[:nb]


# --------------------------------------------- exactness probe + dispatch

def _probe_plan(mode: int, dtype, value_range, block_size: int,
                nb: int = 16, n_rows: int = 5) -> DecodePlan:
    """Small deterministic plan with mantissa-rich values: hits, misses,
    shared sources and (delta) long accumulation chains all present.
    The defaults are the exactness probe's; the autotuner reuses this with
    ``nb`` at the size-bucket it is timing."""
    dt = np.dtype(dtype)
    B = block_size
    P = B if mode == MODE_STD else B - 1
    bits = _splitmix64(np.arange(n_rows * P, dtype=np.uint64) + np.uint64(7))
    vals = (bits.astype(np.float64) / 2.0 ** 64 - 0.5) * 8.0
    payloads = vals.reshape(n_rows, P).astype(dt)
    src = (np.arange(nb, dtype=np.int64) * 3) % n_rows
    is_hit = np.ones(nb, dtype=bool)
    is_hit[:n_rows] = False
    bases = None
    if mode != MODE_STD:
        bbits = _splitmix64(np.arange(nb, dtype=np.uint64) + np.uint64(99))
        bases = ((bbits.astype(np.float64) / 2.0 ** 64 - 0.5) * 700.0
                 ).astype(dt)
    return DecodePlan(mode=mode, block_size=B, dtype=dt,
                      value_range=value_range, payloads=payloads, src=src,
                      bases=bases, is_hit=is_hit,
                      block_idx=np.arange(nb, dtype=np.int64), seed=3)


def _device_has_dtype(dtype) -> bool:
    """Whether the default device computes ``dtype`` exactly.  The TPU has
    no f64 (XLA emulates it, the Pallas kernels cannot compile it)."""
    import jax

    return np.dtype(dtype) != np.float64 or jax.default_backend() != "tpu"


def _device_exact(backend: str, plan: DecodePlan) -> bool:
    """Probe (once per combination) whether ``backend`` reproduces the host
    path byte-for-byte on this device.  An inexact result is logged once
    and routes the combination to the host.  A probe that raises is a
    device fault: it is cached and re-raised as ``DeviceBackendError`` for
    every later call of the combination, without touching the device."""
    key = (backend, plan.mode, np.dtype(plan.dtype).str, plan.value_range,
           plan.block_size)
    ok = _exact_cache.get(key)
    if ok is None:
        probe = _probe_plan(plan.mode, plan.dtype, plan.value_range,
                            plan.block_size)
        want = _reconstruct_numpy(probe)
        try:
            got = _run_device(probe, backend)
        except Exception as e:
            ok = _exact_cache[key] = DeviceBackendError(
                f"decode backend {backend!r} failed its probe on this "
                f"device for {key}: {e}")
            raise ok from e
        ok = got.tobytes() == want.tobytes()
        if not ok:
            logger.warning(
                "decode backend %r is not byte-exact on this device for "
                "%s; routing it to host reconstruction", backend, key)
        _exact_cache[key] = ok
    if isinstance(ok, DeviceBackendError):
        raise ok
    return ok


# ------------------------------------------------------ measured autotuner
#
# ``backend="auto"`` used to be a synonym for "jax"; it is now *measured*:
# the first time a (mode, dtype, size-bucket) combination is resolved, the
# engine times the host path against every device backend that passes the
# exactness probe on a bucket-sized probe plan, routes the combination to
# the fastest, and remembers the choice.  Choices persist in a versioned
# JSON cache (``decode_autotune.json`` by convention) when the
# ``REPRO_DECODE_AUTOTUNE`` env var names a path: the file is loaded lazily
# at first "auto" resolution and rewritten after each new probe.  A stale
# ``version`` field or a corrupt file is discarded (logged) and re-probed
# -- never trusted (DESIGN.md Sec. 9).
#
# The cache table itself (locking, lazy env load, validation, atomic
# persist) is the shared ``repro.core.tuning.MeasuredTuner`` -- the encode
# side's ``matcher="auto"`` runs on the same machinery (DESIGN.md Sec. 10);
# this module keeps only the decode-shaped parts: the probe plan, the
# exactness gating and the key format.

from .tuning import AutotuneCacheError, MeasuredTuner, best_of, pow2_bucket

AUTOTUNE_VERSION = 1
_BUCKET_MIN, _BUCKET_MAX = 64, 16384

_TUNER = MeasuredTuner(
    version=AUTOTUNE_VERSION, env_var="REPRO_DECODE_AUTOTUNE",
    validate_entry=lambda ent: ent.get("backend") in BACKENDS,
    log=logger, name="decode")


def _size_bucket(nb: int) -> int:
    """Pow-2 size bucket of a dispatch, clamped so the probe table stays
    small: everything below 64 blocks shares one bucket (dispatch overhead
    dominates), everything above 16384 another (bandwidth dominates)."""
    return pow2_bucket(nb, _BUCKET_MIN, _BUCKET_MAX)


def _autotune_key(mode: int, dtype, nb: int) -> str:
    return f"mode={mode}|dtype={np.dtype(dtype).str}|bucket={_size_bucket(nb)}"


def load_autotune(path: str, strict: bool = True) -> int:
    """Load persisted ``"auto"`` choices; returns the entry count.

    ``strict=True`` (the selfcheck contract) raises
    :class:`AutotuneCacheError` on a corrupt or version-stale file;
    ``strict=False`` (the serving path) logs, discards, and leaves the
    cache cold so the combination is re-probed."""
    return _TUNER.load(path, strict=strict)


def save_autotune(path: str) -> None:
    """Persist the in-memory choices as the versioned JSON cache (atomic
    replace, so a racing reader never sees a half-written file)."""
    _TUNER.save(path)


def reset_autotune() -> None:
    """Forget every choice (and the lazy disk load): next ``"auto"``
    resolution re-probes.  Test hook."""
    _TUNER.reset()


def autotune_choices() -> dict:
    """Current ``"auto"`` routing table: autotune key -> backend name."""
    return _TUNER.choices("backend")


def autotune_cached(mode: int, dtype, nb: int) -> bool:
    """Whether ``"auto"`` for this (mode, dtype, size-bucket) would resolve
    from cache (True) or have to run a timing probe (False).  The serving
    layer uses this to quiesce its pipeline before a cold probe -- timing
    backends while a reconstruct is in flight would poison the choice."""
    return _TUNER.cached(_autotune_key(mode, dtype, nb))


def _probe_autotune(mode: int, dtype, value_range, block_size: int,
                    bucket: int) -> dict:
    """Time host vs candidate device backends on a bucket-sized probe plan
    (pow-2 shapes, so the compiled shapes are the ones real traffic
    reuses).  Only backends that pass the exactness probe are candidates;
    ties and errors resolve toward the host path."""
    plan = _probe_plan(mode, dtype, value_range, block_size,
                       nb=bucket, n_rows=min(bucket, 64))

    times = {"numpy": best_of(lambda: _reconstruct_numpy(plan))}
    for b in BACKENDS[1:]:
        if _device_exact(b, plan):
            times[b] = best_of(lambda: _run_device(plan, b))
    # the host path wins ties: a device must be >5% faster on the probe to
    # take the route (noise margin; a near-tie is not worth the dispatch)
    backend = min(sorted(times), key=times.get)
    if times[backend] > times["numpy"] * 0.95:
        backend = "numpy"
    return {"backend": backend,
            "times_us": {k: round(v * 1e6, 3) for k, v in times.items()}}


def resolve_backend(backend: str, mode: int, dtype, nb: int,
                    value_range=None, block_size: int = 32) -> str:
    """Concrete backend for one dispatch.

    Explicit names pass through (validated); ``"auto"`` returns the
    measured-best backend for ``(mode, dtype, size bucket)`` -- probing,
    caching and (when ``REPRO_DECODE_AUTOTUNE`` is set) persisting on
    first use.  ``nb`` must be the size of the DISPATCH being routed (the
    serving layer passes its merged group's total blocks, not any single
    request's) -- routing measured at the wrong operating point would
    send large batches down a backend that only wins small ones."""
    if backend != "auto":
        if backend not in BACKENDS:
            raise ValueError(f"unknown decode backend {backend!r}; "
                             f"expected one of {BACKENDS + ('auto',)}")
        return backend
    key = _autotune_key(mode, dtype, nb)
    with _TUNER.lock:
        ent = _TUNER.lookup(key)
        if ent is not None:
            _bump("autotune_hits")
            return ent["backend"]
        ent = _TUNER.record(key, _probe_autotune(
            mode, np.dtype(dtype), value_range, block_size,
            _size_bucket(nb)))
        _bump("autotune_probes")
        logger.info("autotune: %s -> %s %s", key, ent["backend"],
                    ent["times_us"])
        return ent["backend"]


def reconstruct(plan: DecodePlan, backend: str = "numpy") -> np.ndarray:
    """Rebuild ``(nb, B)`` block values from a plan (paper Sec. V-A2/V-B2).

    ``backend`` is ``"numpy"`` (host reference), ``"jax"``/``"pallas"``
    (device; byte-identical, served from the host -- logged and counted as
    a fallback in :func:`decode_stats` -- when the exactness probe finds
    the backend inexact on the current device), or ``"auto"`` (the
    measured-best backend for the plan's (mode, dtype, size bucket) --
    :func:`resolve_backend`; dtypes the device cannot compute exactly go
    to the host, counted as ``host_routes``).  A device backend that
    raises -- at its probe or at this dispatch -- or an explicit device
    backend asked for a dtype the device lacks raises
    :class:`~repro.errors.DeviceBackendError`; no device failure is ever
    served from the host.  Purely per-block math: callers may stack many
    ranges into one padded plan (:func:`pad_parts`) and slice the result
    apart.
    """
    if plan.nb == 0:
        # validate the name, but never autotune-probe for an empty plan
        if backend != "auto" and backend not in BACKENDS:
            raise ValueError(f"unknown decode backend {backend!r}; "
                             f"expected one of {BACKENDS + ('auto',)}")
        return np.zeros((0, plan.block_size), dtype=np.dtype(plan.dtype))
    if backend != "numpy" and not _device_has_dtype(plan.dtype):
        if backend != "auto":
            raise DeviceBackendError(
                f"decode backend {backend!r} has no exact "
                f"{np.dtype(plan.dtype)} on this device; use 'numpy' or "
                f"'auto'")
        backend = "numpy"
        _bump("host_routes")
    backend = resolve_backend(backend, plan.mode, plan.dtype, plan.nb,
                              plan.value_range, plan.block_size)
    _backend_counters[backend].inc()
    if backend != "numpy":
        if _device_exact(backend, plan):
            try:
                out = _run_device(plan, backend)
            except Exception as e:
                # the probe passed but THIS shape failed (device OOM,
                # shape-specific compile error): a fault, not a route
                raise DeviceBackendError(
                    f"decode backend {backend!r} failed at dispatch "
                    f"(nb={plan.nb}): {e}") from e
            _bump("device_calls")
            return out
        _bump("fallbacks")
    _bump("host_calls")
    return _reconstruct_numpy(plan)
