"""Verification time per executed query, in ms: the sum of the responses'
``stats.verify_time_s`` (the scheduler's fused time apportioned to each
job; page deltas included) over the executed queries."""

from mbench import layerlib


def read(ctx):
    n = len(layerlib.queries(ctx))
    if not n:
        return None
    return 1e3 * layerlib.stat_sum(ctx, "verify_time_s") / n
