"""Unified exception hierarchy: every typed repro failure under one root.

``ReproError`` is the root; the pre-existing typed exceptions
(``StreamFormatError``, ``ContainerFormatError``, ``AutotuneCacheError``,
``KernelShapeError``) are re-parented under it *without* losing their
``ValueError`` base, so ``except ValueError`` call sites and tests keep
working.  Their historical import paths (``repro.core.stream``,
``repro.store.container``, ``repro.core.tuning``,
``repro.kernels.dict_match``) re-export from here.

Every class carries the protocol mapping the serving front end
(``repro.serve.frontend``) speaks on the wire:

* ``code``        -- stable machine-readable error code (snake_case);
* ``http_status`` -- the HTTP status the front end answers with.

``error_payload`` builds the JSON error body; ``ERROR_CODES`` maps codes
back to classes so wire clients can re-raise typed errors.

This module is dependency-free (stdlib only): it sits below ``core``,
``store``, ``kernels`` and ``serve`` in the import graph.
"""
from __future__ import annotations

from typing import Dict, Optional, Type

__all__ = [
    "ReproError",
    "StreamFormatError",
    "ContainerFormatError",
    "AutotuneCacheError",
    "KernelShapeError",
    "DeviceBackendError",
    "ApiError",
    "AdmissionError",
    "QuotaExceededError",
    "RateLimitedError",
    "OverloadedError",
    "NotFoundError",
    "ERROR_CODES",
    "error_payload",
    "error_from_payload",
]


class ReproError(Exception):
    """Root of every typed repro failure.

    ``code``/``http_status`` are class attributes so subclasses declare
    their protocol mapping declaratively; unknown/unexpected exceptions
    map to the root's ``internal``/500.
    """

    code: str = "internal"
    http_status: int = 500


# ------------------------------------------------------------- re-parented
# The four pre-existing typed exceptions.  Each keeps ``ValueError`` in its
# bases (callers and tests match on it) and gains the ``ReproError`` root +
# a protocol code.  The defining modules import these back, so both the old
# and the new import paths name the SAME class object.

class StreamFormatError(ReproError, ValueError):
    """Malformed/truncated IDEALEM stream.  ``offset`` is the byte position
    at which parsing failed (raw ``struct.error``/``IndexError`` from the
    walk are never surfaced to callers)."""

    code = "stream_format"
    http_status = 400

    def __init__(self, message: str, offset: int = 0):
        super().__init__(f"{message} (at byte {offset})")
        self.offset = offset


class ContainerFormatError(ReproError, ValueError):
    """Malformed container: bad magic/version/CRC or inconsistent index."""

    code = "container_format"
    http_status = 400


class AutotuneCacheError(ReproError, ValueError):
    """A persisted autotune cache failed validation (corrupt JSON, wrong
    structure, or a stale ``version`` field)."""

    code = "autotune_cache"
    http_status = 500


class KernelShapeError(ReproError, ValueError):
    """An operand shape violates a kernel's tiling contract.

    Raised instead of a bare assert so a bad launch plan fails with the
    actual dimensions and the required padding in the message."""

    code = "kernel_shape"
    http_status = 500


class DeviceBackendError(ReproError, RuntimeError):
    """An explicitly requested device backend cannot serve the call on this
    device: its first probe or its dispatch raised, or the plan's dtype has
    no exact form there (f64 on a TPU).  Raised instead of silently serving
    the call from the host, so device coverage can never be faked."""

    code = "device_backend"
    http_status = 500


# ------------------------------------------------------------ serving layer
class ApiError(ReproError, ValueError):
    """A request payload failed validation (bad JSON, missing field,
    wrong type) before reaching any service."""

    code = "bad_request"
    http_status = 400


class NotFoundError(ReproError, KeyError):
    """A named resource (stream, store, tenant, route) does not exist."""

    code = "not_found"
    http_status = 404

    def __str__(self) -> str:  # KeyError would repr() the message
        return self.args[0] if self.args else ""


class AdmissionError(ReproError):
    """Base of the typed admission-control rejections the front end maps
    onto 429/503.  ``retry_after_s`` (when known) becomes the protocol's
    ``retry_after_s`` field and the ``Retry-After`` header."""

    code = "admission"
    http_status = 429

    def __init__(self, message: str,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class QuotaExceededError(AdmissionError):
    """A per-tenant quota (streams, stores, staged blocks) is exhausted:
    the tenant must shed load; retrying without closing something is
    futile, so no ``retry_after_s`` is implied."""

    code = "quota_exceeded"
    http_status = 429


class RateLimitedError(AdmissionError):
    """The tenant's bytes/s token bucket is empty; ``retry_after_s`` says
    when enough tokens will have refilled."""

    code = "rate_limited"
    http_status = 429


class OverloadedError(AdmissionError):
    """Global (cross-tenant) backpressure: the server's staged work
    exceeds its flush pipeline's budget.  Retry after the pipeline
    drains -- a server-health condition, hence 503 not 429."""

    code = "overloaded"
    http_status = 503


ERROR_CODES: Dict[str, Type[ReproError]] = {
    cls.code: cls
    for cls in (ReproError, StreamFormatError, ContainerFormatError,
                AutotuneCacheError, KernelShapeError, DeviceBackendError,
                ApiError,
                NotFoundError, AdmissionError, QuotaExceededError,
                RateLimitedError, OverloadedError)
}


def error_payload(exc: BaseException) -> dict:
    """The protocol error body for an exception: ``{"error": {"code",
    "message", ...}}``.  Non-``ReproError`` exceptions map to the root
    ``internal`` code (the message still travels, the type does not)."""
    code = exc.code if isinstance(exc, ReproError) else ReproError.code
    body = {"code": code, "message": str(exc)}
    retry = getattr(exc, "retry_after_s", None)
    if retry is not None:
        body["retry_after_s"] = float(retry)
    return {"error": body}


def error_from_payload(doc: dict) -> ReproError:
    """Re-raise-able typed error from a protocol error body (the client
    half of :func:`error_payload`); unknown codes become ``ReproError``."""
    body = doc.get("error", doc)
    cls = ERROR_CODES.get(body.get("code", ""), ReproError)
    msg = body.get("message", "")
    if issubclass(cls, AdmissionError):
        return cls(msg, retry_after_s=body.get("retry_after_s"))
    if issubclass(cls, StreamFormatError):
        err = ReproError.__new__(cls)
        Exception.__init__(err, msg)
        err.offset = 0
        return err
    return cls(msg)
