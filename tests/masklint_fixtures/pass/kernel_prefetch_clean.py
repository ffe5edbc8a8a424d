"""MUST STAY CLEAN: scalar-prefetch index maps take the grid's program ids
plus one ref per prefetched operand — the shape of the real kernels."""
import functools

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def count_kernel(rois_ref, bounds_ref, x_ref, o_ref, *, bh):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[i] = 0

    m = x_ref[0]
    rr = jax.lax.broadcasted_iota(jnp.int32, m.shape, 0) + j * bh
    keep = (rr >= rois_ref[4 * i]) & (m >= bounds_ref[0])
    o_ref[i] += jnp.sum(keep.astype(jnp.int32))


def launch(x, rois, bounds, bh):
    b, h, w = x.shape
    return pl.pallas_call(
        functools.partial(count_kernel, bh=bh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // bh),
            in_specs=[pl.BlockSpec((1, bh, w), lambda i, j, r, v: (i, j, 0))],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
    )(rois, bounds, x)
