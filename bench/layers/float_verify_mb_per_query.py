"""Resident mask megabytes the device verification steps gathered per
executed query: the sum of the answers' ``stats.resident_bytes`` (1e6
bytes to the MB) over the executed queries.  A program whose answers
carry no ``resident_bytes`` reads nothing."""

from mbench import layerlib


def read(ctx):
    n = len(layerlib.queries(ctx))
    if not n or not any("resident_bytes" in d for _, d in ctx.deltas):
        return None
    return layerlib.stat_sum(ctx, "resident_bytes") / 1e6 / n
