"""The device bounds steps' share of the HBM roofline, in %: the CHI bytes
the window's bounds passes needed (``stats.chi_bytes``) at the chip's peak
bandwidth, over the device time of the jitted bounds steps in the trace."""

from mbench import layerlib

STEPS = ("_device_cp_bounds", "_device_pair_cells")


def read(ctx):
    return layerlib.hbm_share(ctx, layerlib.stat_sum(ctx, "chi_bytes"), STEPS)
