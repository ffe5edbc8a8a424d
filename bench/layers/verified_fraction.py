"""Share of the candidates the engine had to verify, in %: the sum of
``n_verified`` over the sum of ``n_candidates`` of the executed queries."""

from mbench import layerlib


def read(ctx):
    cand = layerlib.stat_sum(ctx, "n_candidates")
    if cand <= 0:
        return None
    return 100.0 * layerlib.stat_sum(ctx, "n_verified") / cand
