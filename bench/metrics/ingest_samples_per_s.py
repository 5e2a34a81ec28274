"""Samples of every request answered inside the window, whenever it was
sent, over the window, by the host's clock.  The uploaders are busy
before the window opens and after it closes, so the work cut off at the
two edges roughly cancels."""


def read(ctx):
    got = sum(r["samples"] for r in ctx.window_records() if r["ok"])
    return got / (ctx.t1 - ctx.t0) if got else None
