"""95th percentile of all per-round walls in the window (ms)."""
import statistics


def read(ctx):
    if len(ctx.walls) < 20:
        return None
    return 1e3 * statistics.quantiles(ctx.walls, n=20)[-1]
