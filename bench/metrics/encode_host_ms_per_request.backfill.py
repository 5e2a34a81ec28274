"""Host time per feed request: the mean of the front end's
``repro_frontend_request_seconds`` for ``POST /v1/feed`` over the window,
less the encode-scan device time per request from the trace (one scan
call per channel of the request)."""
from benchkit import xplane

PROGRAM = "jit_scan"


def read(ctx):
    s, n = ctx.histogram("repro_frontend_request_seconds",
                         {"route": "POST /v1/feed"})
    if ctx.trace is None or n == 0:
        return None
    calls = xplane.program_calls(ctx.trace, PROGRAM)
    if calls == 0:
        return None
    scan_per_request = (xplane.program_time(ctx.trace, PROGRAM) / calls
                        * len(ctx.cfg["channels"]))
    return 1e3 * (s / n - scan_per_request)
