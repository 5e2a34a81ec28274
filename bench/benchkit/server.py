"""The system under test, as the benchmark runs it: the program's
``ServeFrontend`` built from a configuration file, and the archive a
read configuration serves, built with the program's own batched encode
session.  This module holds the chip; it is imported only after the
harness has checked for one.
"""
from __future__ import annotations

import functools
import hashlib
import inspect
import json
import sys
import time
from pathlib import Path

import numpy as np

from . import fleet
from .wire import Connection

__all__ = ["import_program", "make_frontend", "archive_containers",
           "attach_archive", "annotate_program", "restore_program"]


def import_program(root: Path) -> None:
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def make_frontend(cfg: dict):
    """``ServeFrontend`` through its public constructor, with the settings
    the configuration's ``frontend`` block lists."""
    from repro.serve import FlushPolicy, ServeFrontend, TenantQuota

    fe = cfg["frontend"]
    kw = dict(fe.get("kwargs", {}))
    if "quota" in fe:
        kw["default_quota"] = TenantQuota(**fe["quota"])
    if "policy" in fe:
        kw["policy"] = FlushPolicy(**fe["policy"])
    return ServeFrontend(**kw)


def _codec(cfg: dict, kind: str):
    from repro.api import CodecConfig
    from repro.core import IdealemCodec

    c = dict(cfg["codecs"][kind])
    if c.get("value_range") is not None:
        c["value_range"] = tuple(c["value_range"])
    return IdealemCodec.from_config(CodecConfig(**c, **cfg["engine"]))


def _build_archive(cfg: dict) -> dict:
    """One container per device: every channel's hour-long stream, fed
    minute by minute through a batched session per channel kind."""
    from repro.store import pack

    seed = int(cfg["archive"]["data_seed"])
    spm = fleet.samples_per_minute(cfg)
    minutes = int(round(cfg["archive"]["hours"] * 60))
    n = minutes * spm
    devices = range(cfg["fleet"]["devices"])
    kinds = fleet.channel_kinds(cfg)
    streams = {}
    times = {}
    for kind in sorted(set(kinds)):
        t = time.monotonic()
        lanes = [(d, c) for d in devices for c, k in enumerate(kinds)
                 if k == kind]
        data = np.stack([fleet.channel_series(cfg, seed, d, c, n)
                         for d, c in lanes])
        times[f"{kind}_data_s"] = time.monotonic() - t
        t = time.monotonic()
        sess = _codec(cfg, kind).session(channels=len(lanes),
                                         dtype=np.dtype(cfg["dtype"]))
        parts = [[] for _ in lanes]
        for m in range(minutes):
            for i, seg in enumerate(sess.feed(data[:, m * spm:(m + 1)
                                                   * spm])):
                parts[i].append(seg)
        for i, seg in enumerate(sess.finish()):
            parts[i].append(seg)
        for i, lane in enumerate(lanes):
            streams[lane] = b"".join(parts[i])
        times[f"{kind}_encode_s"] = time.monotonic() - t
    t = time.monotonic()
    out = {d: pack([streams[(d, c)] for c in range(len(kinds))])
           for d in devices}
    times["pack_s"] = time.monotonic() - t
    print(f"[bench] archive build: {times}", file=sys.stderr, flush=True)
    return out


# what the archive's bytes depend on: its cache key
ARCHIVE_KEYS = ("sample_rate_hz", "dtype", "fleet", "codecs", "engine",
                "channels", "events", "archive")


def archive_containers(cfg: dict, cache_root: Path) -> tuple:
    """The archive's containers, built once per configuration (its
    samples come from the configuration's ``archive.data_seed``: the
    stored history is part of the deployment, the reads are what the
    run's seed draws) and kept under ``cache_root``.  Returns
    ``(containers, built)``."""
    content = {k: cfg[k] for k in ARCHIVE_KEYS}
    key = hashlib.sha256(json.dumps(content, sort_keys=True).encode()
                         ).hexdigest()
    d = Path(cache_root) / f"archive-{key[:20]}"
    devices = range(cfg["fleet"]["devices"])
    files = {dev: d / f"{fleet.store_id(dev)}.idlmc" for dev in devices}
    if (d / "complete").exists():
        return {dev: files[dev].read_bytes() for dev in devices}, False
    out = _build_archive(cfg)
    d.mkdir(parents=True, exist_ok=True)
    for dev, blob in out.items():
        files[dev].write_bytes(blob)
    (d / "complete").write_text("ok\n")
    return out, True


async def attach_archive(host: str, port: int, cfg: dict,
                         containers: dict) -> None:
    """Attach every device's container to its tenant over ``/v1/attach``."""
    import base64

    conn = await Connection(host, port).open()
    try:
        for dev, blob in sorted(containers.items()):
            await conn.post("/v1/attach", fleet.tenant_of(cfg, dev), {
                "store_id": fleet.store_id(dev),
                "container": base64.b64encode(blob).decode("ascii"),
                "seed": int(cfg["archive"]["decode_seed"])})
    finally:
        await conn.close()


# Program calls wrapped in profiler spans in a traced run, so that the
# trace can say what the host was doing while the device idled.  A name
# the program no longer has is skipped.
SPANS = (
    "repro.serve.frontend.ServeFrontend.tick",
    "repro.serve.tenancy.Tenant.feed",
    "repro.serve.tenancy.Tenant.close_stream",
    "repro.core.session.IdealemSession.prepare",
    "repro.core.session.IdealemSession.commit",
    "repro.core.session.IdealemSession._decide",
    "repro.serve.compress.DecompressionService.submit",
    "repro.serve.compress.DecompressionService.poll",
    "repro.serve.compress.DecompressionService._stage_plan",
    "repro.serve.compress.DecompressionService._stage_gather",
    "repro.serve.compress.DecompressionService._stage_reconstruct",
    "repro.serve.compress.DecompressionService._stage_emit",
    "repro.api.CompressRequest.from_json",
    "repro.api.FeedResult.to_json",
    "repro.api.RangeResult.to_json",
)


def annotate_program(spans=SPANS) -> list:
    """Wrap each named synchronous method in a ``TraceAnnotation``;
    returns ``(class, name, original)`` for each method wrapped, which
    ``restore_program`` puts back."""
    import importlib

    import jax

    done = []
    for dotted in spans:
        mod_name, cls_name, attr = dotted.rsplit(".", 2)
        try:
            cls = getattr(importlib.import_module(mod_name), cls_name)
            raw = cls.__dict__[attr]
        except (ImportError, AttributeError, KeyError):
            continue
        is_cm = isinstance(raw, classmethod)
        fn = raw.__func__ if is_cm else raw
        if not callable(fn) or inspect.iscoroutinefunction(fn):
            continue
        label = f"bench:{cls_name}.{attr}"

        def wrapped(*a, __fn=fn, __label=label, **kw):
            with jax.profiler.TraceAnnotation(__label):
                return __fn(*a, **kw)

        functools.update_wrapper(wrapped, fn)
        setattr(cls, attr, classmethod(wrapped) if is_cm else wrapped)
        done.append((cls, attr, raw))
    return done


def restore_program(wrapped: list) -> None:
    for cls, attr, raw in wrapped:
        setattr(cls, attr, raw)
