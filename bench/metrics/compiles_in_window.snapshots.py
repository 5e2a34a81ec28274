"""Executables built inside the window, counted with ``jax.monitoring``
(a read from the persistent compilation cache is built too: JAX times it
as a backend compile, and counts it as a cache hit besides)."""


def read(ctx):
    return ctx.compiles_window["compiled"]
