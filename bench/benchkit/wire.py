"""A minimal client of the front end's wire protocol: HTTP/1.1 keep-alive
with JSON-lines bodies and an ``x-tenant`` header (the protocol
``repro.serve.FrontendClient`` speaks), written against the wire and not
the program, so that the load generator never imports JAX.

Arrays travel as ``{"dtype": "<f4", "b64": ...}``: base64 of their raw
little-endian bytes (``feed_line`` writes them; answers are read by
length here and decoded where they are checked).
"""
from __future__ import annotations

import asyncio
import base64
import json
import sys
import time
from typing import Tuple

import numpy as np

__all__ = ["ANSWER_WAIT_S", "Connection", "array_nbytes", "feed_line",
           "log", "sleep_until"]

# how long after the window's close an answer is still waited for
ANSWER_WAIT_S = 60.0


def log(msg: str) -> None:
    print(f"[loadgen] {msg}", file=sys.stderr, flush=True)


async def sleep_until(t: float) -> None:
    dt = t - time.monotonic()
    if dt > 0:
        await asyncio.sleep(dt)


def array_nbytes(doc: dict) -> int:
    """Byte length of an encoded array, read off the base64 text."""
    b64 = doc["b64"]
    return len(b64) // 4 * 3 - b64[-2:].count("=") if b64 else 0


def feed_line(stream_id: str, samples: np.ndarray) -> bytes:
    """One ``/v1/feed`` JSON line (no newline)."""
    b64 = base64.b64encode(np.ascontiguousarray(samples).tobytes())
    return (b'{"stream_id": "' + stream_id.encode() + b'", "samples": '
            b'{"dtype": "' + samples.dtype.str.encode() + b'", "b64": "'
            + b64 + b'"}}')


class Connection:
    """One keep-alive connection; requests on it run one at a time."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self._reader = self._writer = None

    async def open(self) -> "Connection":
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port)
        return self

    async def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            try:
                await self._writer.wait_closed()
            except (ConnectionError, OSError):
                pass
            self._reader = self._writer = None

    async def request(self, method: str, path: str, tenant: str = "",
                      body: bytes = b"") -> Tuple[int, bytes]:
        if self._writer is None:
            await self.open()
        head = (f"{method} {path} HTTP/1.1\r\nhost: {self.host}\r\n"
                f"x-tenant: {tenant}\r\ncontent-type: application/json\r\n"
                f"content-length: {len(body)}\r\n\r\n")
        self._writer.write(head.encode() + body)
        await self._writer.drain()
        status_line = await self._reader.readline()
        if not status_line:
            raise ConnectionError("server closed the connection")
        status = int(status_line.split()[1])
        length = 0
        while True:
            h = await self._reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode("latin-1").partition(":")
            if k.strip().lower() == "content-length":
                length = int(v.strip())
        payload = await self._reader.readexactly(length) if length else b""
        return status, payload

    async def post(self, path: str, tenant: str, doc: dict) -> dict:
        """One JSON document in, one out; raises on an error document."""
        status, payload = await self.request(
            "POST", path, tenant, (json.dumps(doc) + "\n").encode())
        out = json.loads(payload)
        if status != 200 or "error" in out:
            raise RuntimeError(f"{path} -> {status}: {out}")
        return out

    async def post_lines(self, path: str, tenant: str, lines) -> list:
        """A JSON-lines body in (``bytes`` lines), one document per line
        out; per-line errors stay in their documents."""
        _status, payload = await self.request(
            "POST", path, tenant, b"\n".join(lines) + b"\n")
        return [json.loads(ln) for ln in payload.splitlines() if ln.strip()]
