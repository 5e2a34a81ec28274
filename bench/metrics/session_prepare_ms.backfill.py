"""Host time per feed request in session staging, cutting blocks and
transforming them: the sum of
``repro_encode_phase_seconds{phase="prepare"}`` over the feed requests,
counted by the front end's parse phase of ``POST /v1/feed`` (one
observation per request)."""
PHASE = "prepare"


def read(ctx):
    _s, requests = ctx.histogram("repro_frontend_phase_seconds",
                                 {"route": "POST /v1/feed", "phase": "parse"})
    s, n = ctx.histogram("repro_encode_phase_seconds", {"phase": PHASE})
    return 1e3 * s / requests if requests and n else None
