"""Time the front end takes to encode a range read's answer (typed result,
JSON, base64): the mean of ``repro_frontend_phase_seconds`` for
``POST /v1/decode``, phase ``encode``."""


def read(ctx):
    s, n = ctx.histogram("repro_frontend_phase_seconds",
                         {"route": "POST /v1/decode", "phase": "encode"})
    return 1e3 * s / n if n else None
