"""99th percentile (nearest rank) of how late the load generator sent
each read due in the window: send time minus due time."""
from benchkit.harness import nearest_rank


def read(ctx):
    late = [1e3 * (r["sent"] - r["due"]) for r in ctx.window_records()]
    return nearest_rank(late, 0.99) if late else None
