"""How late the front end's event loop runs: the mean of
``repro_frontend_loop_lag_seconds``, how far each deadline tick woke past
its interval."""


def read(ctx):
    s, n = ctx.histogram("repro_frontend_loop_lag_seconds")
    return 1e3 * s / n if n else None
