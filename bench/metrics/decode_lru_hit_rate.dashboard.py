"""Parsed-segment LRU hits over all lookups in the window, in percent
(``repro_serve_cache_hits_total`` and ``..._misses_total``)."""


def read(ctx):
    hits = ctx.counter("repro_serve_cache_hits_total")
    misses = ctx.counter("repro_serve_cache_misses_total")
    return 100.0 * hits / (hits + misses) if hits + misses else None
