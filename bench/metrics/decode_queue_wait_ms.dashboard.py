"""Time a range read waits in the decode service's queue: the mean of
``repro_serve_queue_wait_seconds`` (per request, from ``submit`` to the
cut of the batch that takes it, on the service's clock)."""


def read(ctx):
    s, n = ctx.histogram("repro_serve_queue_wait_seconds")
    return 1e3 * s / n if n else None
