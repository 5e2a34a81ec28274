"""95th percentile (nearest rank) of the latency of every range read due
in the window, from its due time to its answer, by the host's clock.  A
read that failed or never came counts as 60,000 ms, the longest the load
generator waits.  (The 99th percentile, which the run also prints, is set
by a few stalls of 100-300 ms a window and spread by 33-90% between runs
of one build: no bound the contract allows admits it.)"""
from benchkit.harness import nearest_rank

FAILED_MS = 60000.0


def read(ctx):
    lat = [1e3 * ctx.latency(r) if r["ok"] else FAILED_MS
           for r in ctx.window_records()]
    return nearest_rank(lat, 0.95) if lat else None
