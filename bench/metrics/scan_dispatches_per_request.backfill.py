"""Direct-session encode-scan dispatches per feed request:
``repro_encode_dispatches_total{path="direct"}`` over the feed requests,
counted by the front end's parse phase of ``POST /v1/feed``."""


def read(ctx):
    _s, requests = ctx.histogram("repro_frontend_phase_seconds",
                                 {"route": "POST /v1/feed", "phase": "parse"})
    n = ctx.counter("repro_encode_dispatches_total", {"path": "direct"})
    return n / requests if requests and n else None
