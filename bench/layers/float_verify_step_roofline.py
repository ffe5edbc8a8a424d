"""The float verification steps' share of the HBM roofline, in %: the
resident mask bytes the window's executed queries gathered (the sum of
their ``stats.resident_bytes``, rows × one stored 802,816 B row) at the
chip's peak bandwidth, over the device time of the float verification
steps.  Each gathered row is read and written by the gather and read
again by a kernel, so the share stays below 100%.  A program whose
answers carry no ``resident_bytes`` reads nothing."""

from mbench import layerlib

# ``gather``: the pair pass's row gathers, ahead of ``pair_counts``
STEPS = ("_device_multi_counts", "_device_group_counts", "gather",
         "pair_counts")


def read(ctx):
    return layerlib.hbm_share(ctx, layerlib.stat_sum(ctx, "resident_bytes"),
                              STEPS)
