"""Counts executables JAX builds in this process, through
``jax.monitoring``: compiled by the backend, or read back from the
persistent compilation cache.  A copy of the bring-up smoke's
``CompileLog``, kept with the benchmark, which also keeps the
``Compiling ...`` debug lines of JAX's ``pxla`` logger (function, shapes)
with their time, so that a compile inside the window can be named."""
from __future__ import annotations

import logging
import time

__all__ = ["CompileLog"]

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_PXLA = "jax._src.interpreters.pxla"     # logs "Compiling <fn> with ..."


class CompileLog:
    def __init__(self):
        self.compiled = 0
        self.compile_s = 0.0
        self.cache_reads = 0
        self.messages = []
        self._handler = _Keep(self.messages)
        self._saved = None

    def register(self) -> "CompileLog":
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        logger = logging.getLogger(_PXLA)
        self._saved = (logger.level, logger.propagate)
        logger.setLevel(logging.DEBUG)
        logger.addHandler(self._handler)
        logger.propagate = False     # kept here, not printed
        return self

    def unregister(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)
        logger = logging.getLogger(_PXLA)
        logger.removeHandler(self._handler)
        if self._saved is not None:
            logger.setLevel(self._saved[0])
            logger.propagate = self._saved[1]

    def on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _COMPILE:
            self.compiled += 1
            self.compile_s += secs

    def on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_reads += 1

    def mark(self) -> dict:
        return {"compiled": self.compiled, "compile_s": self.compile_s,
                "cache_reads": self.cache_reads}

    @staticmethod
    def since(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


class _Keep(logging.Handler):
    def __init__(self, out: list):
        super().__init__(logging.DEBUG)
        self.out = out

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.out.append((time.monotonic(), msg))
