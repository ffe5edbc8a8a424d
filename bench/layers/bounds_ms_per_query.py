"""CHI bounds time per executed query, in ms: the sum of the responses'
``stats.bound_time_s`` (page deltas included) over the executed queries."""

from mbench import layerlib


def read(ctx):
    n = len(layerlib.queries(ctx))
    if not n:
        return None
    return 1e3 * layerlib.stat_sum(ctx, "bound_time_s") / n
