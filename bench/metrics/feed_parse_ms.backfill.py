"""Time the front end takes to parse a feed request (body split, JSON,
typed requests): the mean of ``repro_frontend_phase_seconds`` for
``POST /v1/feed``, phase ``parse``, observed once per request."""


def read(ctx):
    s, n = ctx.histogram("repro_frontend_phase_seconds",
                         {"route": "POST /v1/feed", "phase": "parse"})
    return 1e3 * s / n if n else None
