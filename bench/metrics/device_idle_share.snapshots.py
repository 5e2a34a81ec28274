"""Share of the traced window in which no XLA program ran on the chip,
from the profiler trace (``benchkit.xplane.idle_share``), in percent."""
from benchkit import xplane


def read(ctx):
    return None if ctx.trace is None else xplane.idle_share(ctx.trace)
