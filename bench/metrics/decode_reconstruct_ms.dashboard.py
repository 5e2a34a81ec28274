"""Device decode per flush: the mean reconstruct time of
``repro_serve_stage_seconds`` over the window (dispatch, device work and
the copy back, by the host's clock)."""


def read(ctx):
    s, n = ctx.histogram("repro_serve_stage_seconds",
                         {"stage": "reconstruct"})
    return 1e3 * s / n if n else None
