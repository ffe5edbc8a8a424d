"""The device verification steps' share of the HBM roofline, in %: the
mask rows the window verified, at one stored row's bytes each, at the
chip's peak bandwidth, over the device time of the verification steps.

Rows: the union masks of every fused CP pass (``fused_masks``), two rows
per pair of every fused pair pass (``pair_pairs``), and two rows per
image group that a grouped MASK_AGG verified on its own."""

from mbench import layerlib

# ``gather``: the eager row gathers of the fused pair pass
STEPS = ("_device_multi_counts", "_device_multi_counts_packed",
         "_device_group_counts", "_device_group_counts_packed",
         "_device_fused_verify", "gather", "pair_counts",
         "pair_counts_packed")


def read(ctx):
    groups = sum(d.get("n_verified", 0) for r, d in ctx.deltas
                 if r.req["spec"]["kind"] == "mask_agg"
                 and not r.body.get("cache_hit"))
    rows = ctx.sched["fused_masks"] + 2 * ctx.sched["pair_pairs"] + 2 * groups
    return layerlib.hbm_share(ctx, rows * ctx.row_bytes, STEPS)
