"""repro -- IDEALEM statistical-similarity data reduction, at scale.

Curated public surface (``repro.__all__``).  Attribute access is lazy
(PEP 562): ``import repro`` pulls only the dependency-light wire layer
(``repro.api``, ``repro.errors``); the codec/device stack loads on first
use of a name that needs it, so clients of the wire types never pay the
jax import.

Layers (DESIGN.md Sec. 1, 14):

* ``repro.api``    -- wire-typed requests/responses + ``CodecConfig``
* ``repro.errors`` -- the ``ReproError`` hierarchy + protocol codes
* ``repro.core``   -- codec, sessions, decode engine, KS machinery
* ``repro.store``  -- indexed random-access containers
* ``repro.serve``  -- services, coalescer, front end, control loop
* ``repro.obs``    -- metrics registry, spans, exporters, SLOs
"""
from __future__ import annotations

import importlib

# name -> defining submodule; the curated public surface.
_PUBLIC = {
    # wire API (dependency-light)
    "CodecConfig": "repro.api",
    "CompressRequest": "repro.api",
    "FeedResult": "repro.api",
    "DecodeRangeRequest": "repro.api",
    "RangeResult": "repro.api",
    # error hierarchy
    "ReproError": "repro.errors",
    "StreamFormatError": "repro.errors",
    "ContainerFormatError": "repro.errors",
    "AutotuneCacheError": "repro.errors",
    "KernelShapeError": "repro.errors",
    "DeviceBackendError": "repro.errors",
    "ApiError": "repro.errors",
    "AdmissionError": "repro.errors",
    "QuotaExceededError": "repro.errors",
    "RateLimitedError": "repro.errors",
    "OverloadedError": "repro.errors",
    "NotFoundError": "repro.errors",
    # codec core
    "IdealemCodec": "repro.core",
    "IdealemSession": "repro.core",
    "SessionStats": "repro.core",
    "critical_distance": "repro.core",
    "ks_pvalue": "repro.core",
    "ks_statistic": "repro.core",
    # store
    "Container": "repro.store",
    "ContainerWriter": "repro.store",
    "pack": "repro.store",
    "decode_range": "repro.store",
    "decode_ranges": "repro.store",
    "decode_channels": "repro.store",
    # serving
    "FlushPolicy": "repro.serve",
    "CompressionService": "repro.serve",
    "DecompressionService": "repro.serve",
    "StreamCoalescer": "repro.serve",
    "ServeFrontend": "repro.serve",
    "FrontendClient": "repro.serve",
    "TenantQuota": "repro.serve",
    "TenantRegistry": "repro.serve",
    "ControlLoop": "repro.serve",
}

# public submodules, importable both as attributes and via ``import repro.x``
_SUBMODULES = ("api", "errors", "core", "store", "serve", "obs", "kernels",
               "launch", "baselines", "data", "models")

__all__ = sorted(_PUBLIC) + list(_SUBMODULES)


def __getattr__(name: str):
    target = _PUBLIC.get(name)
    if target is not None:
        value = getattr(importlib.import_module(target), name)
        globals()[name] = value  # cache: next access skips this hook
        return value
    if name in _SUBMODULES:
        module = importlib.import_module(f"repro.{name}")
        globals()[name] = module
        return module
    raise AttributeError(f"module 'repro' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
