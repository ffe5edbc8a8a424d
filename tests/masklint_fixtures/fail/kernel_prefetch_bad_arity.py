"""MUST TRIGGER kernel-constraints: under a PrefetchScalarGridSpec the
index map must also take one ref per scalar-prefetch operand."""
import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def count_kernel(rois_ref, x_ref, o_ref):
    i = pl.program_id(0)
    o_ref[i] = jnp.sum((x_ref[0] >= rois_ref[i]).astype(jnp.int32))


def launch(x, rois):
    b, h, w = x.shape
    return pl.pallas_call(
        count_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))],  # 1 arg, needs 2
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
    )(rois, x)
