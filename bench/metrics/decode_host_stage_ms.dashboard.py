"""Host decode stages per flush: the sum of the mean plan, gather and
emit times of ``repro_serve_stage_seconds`` over the window."""

STAGES = ("plan", "gather", "emit")


def read(ctx):
    total = 0.0
    for stage in STAGES:
        s, n = ctx.histogram("repro_serve_stage_seconds", {"stage": stage})
        if n == 0:
            return None
        total += s / n
    return 1e3 * total
