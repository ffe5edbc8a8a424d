"""Compile the kernel wrappers with the TPU compiler for a described (not
attached) v5e chip, at the deployment's shapes: B = 256 float32 224×224
masks, packed rows of 7 words, Q = 4 descriptors, S = 2 mask types, CHI
grid 16 × 16 bins.  Also one sharded kernel step on ``v5e:2x2``, and the
device backend's steps over resident stores of 2-D mask rows and of float
masks in lanes of 128, whose CP and grouped kernels read the rows in
place.

Nothing runs: a compile that passes here is not a chip run.  It catches
what the chip's compiler refuses (block shapes off the (8, 128) tiling,
kernels that cannot be partitioned) at no chip time.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist
from repro.kernels import ops as kops

B, H, W, WORDS, Q, S, G, NB = 256, 224, 224, 7, 4, 2, 16, 16
PAPER_MASKS = 22275


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Steer the wrappers' default dispatch onto the compiled Pallas path
    (the process's own backend is the CPU), with JAX's persistent cache
    off: entries compiled for an absent chip cannot be read back."""
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _specs(sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    rois, qrois = s((B, 4), i32), s((Q, B, 4), i32)
    vals = s((Q,), f32)
    return {
        "cp_count": ((s((B, H, W), f32), rois, s((), f32), s((), f32)), {}),
        "cp_count_multi": ((s((B, H, W), f32), qrois, vals, vals), {}),
        "chi_cell_hist": ((s((B, H, W), f32), s((NB - 1,), f32)),
                          {"grid": G}),
        "mask_agg_counts": ((s((B // S, S, H, W), f32), s((B // S, 4), i32),
                             s((), f32)), {}),
        "pair_counts": ((s((B, H, W), f32), s((B, H, W), f32), rois,
                         s((), f32), s((), f32)), {}),
        "cp_count_packed": ((s((B, H, WORDS), u32), rois, s((), f32),
                             s((), f32)), {}),
        "cp_count_multi_packed": ((s((B, H, WORDS), u32), qrois, vals, vals),
                                  {}),
        "mask_agg_counts_packed": ((s((B // S, S, H, WORDS), u32),
                                    s((B // S, 4), i32), s((), f32)), {}),
        "pair_counts_packed": ((s((B, H, WORDS), u32), s((B, H, WORDS), u32),
                                rois, s((), f32), s((), f32)), {}),
        "fused_bounds_verify": ((s((B, H, WORDS), u32), qrois, vals, vals,
                                 s((Q, B), i32), s((Q, B), i32)), {}),
    }


KERNELS = sorted(_specs(None))


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_wrapper_compiles_for_v5e(name, one_chip, tpu_dispatch):
    args, kw = _specs(one_chip)[name]
    raw = getattr(kops, name).__wrapped__.__wrapped__   # unjitted wrapper
    compiled = jax.jit(functools.partial(raw, **kw)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_verify_step_compiles_on_v5e_2x2(topo, tpu_dispatch):
    """A Mosaic kernel cannot be partitioned automatically: the mesh steps
    run it per shard under shard_map."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    step = dist.make_fused_verify_step(mesh)

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    qb = P(None, "data")
    compiled = step.lower(
        s((B, H, WORDS), jnp.uint32, P("data", None, None)),
        s((Q, B, 4), jnp.int32, P(None, "data", None)),
        s((Q,), jnp.float32, P()), s((Q,), jnp.float32, P()),
        s((Q, B), jnp.int32, qb), s((Q, B), jnp.int32, qb)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_device_verify_step_compiles_over_paper_scale_store(one_chip,
                                                            tpu_dispatch):
    """The device backend's verification step gathers a batch from the
    resident paper-scale store (22,275 masks, 4.47 GB, held as 2-D rows)
    and fits the chip's 16 GB."""
    from repro.core.backend import _device_multi_counts

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = _device_multi_counts.lower(
        s((PAPER_MASKS, H * W), jnp.float32), s((B,), jnp.int32),
        s((Q, B, 4), jnp.int32), s((Q,), jnp.float32),
        s((Q,), jnp.float32), row_shape=(H, W)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


# The resident stores of the gathering steps: the packed tier's 22,274
# masks of 448 rows × 14 words (0.56 GB), and 4,000 float 448 × 448 masks
# (3.2 GB; float rows at 12,000 masks exceed the chip in either form).
PACKED_STORE = (22274, (448, 14), jnp.uint32)
FLOAT_STORE = (4000, (448, 448), jnp.float32)
GATHER_STEPS = {
    "_device_multi_counts_packed": PACKED_STORE,
    "_device_group_counts_packed": PACKED_STORE,
    "_device_fused_verify": PACKED_STORE,
    "gather": PACKED_STORE,
    "_device_multi_counts": FLOAT_STORE,
    "_device_group_counts": FLOAT_STORE,
}


@pytest.mark.parametrize("name", sorted(GATHER_STEPS))
def test_device_gather_step_reads_resident_rows_without_relayout(
        name, one_chip, tpu_dispatch):
    """Each device step gathers its batch from the store's 2-D rows.  Over
    a 3-D ``(n, H, W')`` store the chip's compact layout makes XLA copy
    the whole store before the gather (0.73 GB of temporaries for the
    packed steps, 3.9 GB for the float ones).  Packed rows gather as they
    lie; float 2-D rows of 784 KB still pass through column slabs of the
    whole store, but never more than its own size beside the batch (the
    store holds such floats in lanes instead: the lane-row test below)."""
    from repro.core import backend as be

    n, row_shape, dtype = GATHER_STEPS[name]
    row = row_shape[0] * row_shape[1]

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rows, pos, qrois, vals = (s((n, row), dtype), s((B,)), s((Q, B, 4)),
                              s((Q,), jnp.float32))
    thresh = s((), jnp.float32)
    args = {
        "_device_multi_counts_packed": (rows, pos, qrois, vals, vals),
        "_device_multi_counts": (rows, pos, qrois, vals, vals),
        "_device_group_counts_packed": (rows, pos, s((B // S, 4)), thresh),
        "_device_group_counts": (rows, pos, s((B // S, 4)), thresh),
        "_device_fused_verify": (rows, pos, qrois, vals, vals, s((Q, B)),
                                 s((Q, B))),
        "gather": (rows, pos),
    }[name]
    static = {"s": S} if "group" in name else {}
    compiled = getattr(be, name).lower(*args, row_shape=row_shape,
                                       **static).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    if dtype == jnp.uint32:
        store_copy = re.compile(rf"= u32\[{n},[^=]* copy\(")
        assert not [ln for ln in compiled.as_text().splitlines()
                    if store_copy.search(ln)]
        assert temp < 0.1e9
    else:
        assert temp <= (n + B) * row * np.dtype(dtype).itemsize


# The float tier at the paper's 448 × 448: 16,000 masks held in lanes,
# (n, 1568, 128), 12.8 GB of the chip's 16 (MaskStore.device_row_shape).
LANE_STORE = (16000, 448 * 448 // 128, 128)
LANE_STEPS = {"_device_multi_counts": B, "_device_group_counts": 2 * B,
              "gather": B}


@pytest.mark.parametrize("name", sorted(LANE_STEPS))
def test_float_step_reads_lane_rows_in_place(name, one_chip, tpu_dispatch):
    """Over float rows in lanes of 128 the CP and grouped steps hand the
    whole store and the positions to their kernels, which read each row
    where it lies: under 50 MB of temporaries, against 0.2–0.9 GB for a
    gathered, copied and relaid-out batch, and no batch-sized
    ``f32[B,1568,128]`` or ``f32[B,448,448]`` value in the program.  The
    pair pass's ``gather`` still gathers a batch, 0.2 GB, under 1 GB.
    Over 2-D rows ``(n, 200704)`` XLA passes the whole store through
    column slabs: as many temporaries as the store holds (7.2 GB at 9,000
    masks), and no fit at 14,000."""
    from repro.core import backend as be

    b = LANE_STEPS[name]

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rows, pos = s(LANE_STORE, jnp.float32), s((b,))
    vals, thresh = s((Q,), jnp.float32), s((), jnp.float32)
    args = {
        "_device_multi_counts": (rows, pos, s((Q, b, 4)), vals, vals),
        "_device_group_counts": (rows, pos, s((b // S, 4)), thresh),
        "gather": (rows, pos),
    }[name]
    static = {"s": S} if "group" in name else {}
    compiled = getattr(be, name).lower(*args, row_shape=(448, 448),
                                       **static).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    if name == "gather":
        assert temp < 1e9
        return
    batch = re.compile(rf"f32\[{b},(1568,128|448,448)\]")
    assert not [ln for ln in compiled.as_text().splitlines()
                if batch.search(ln)]
    assert "tpu_custom_call" in compiled.as_text()
    assert temp < 50e6


@pytest.mark.parametrize("name,s", [("_device_multi_counts", 1),
                                    ("_device_group_counts", 4)])
def test_inplace_step_tiles_rows_past_the_vmem_budget(name, s, one_chip,
                                                      tpu_dispatch):
    """A 2048 × 2048 float row is 16 MB: read whole, its double-buffered
    blocks (four members' for the grouped step) overflow VMEM.  The
    in-place kernels read such rows in tiles of lane rows, so the steps
    compile at any mask size."""
    from repro.core import backend as be

    def spec(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rows = spec((64, 2048 * 2048 // 128, 128), jnp.float32)
    vals = spec((1,), jnp.float32)
    if s == 1:
        args, static = (rows, spec((16,)), spec((1, 16, 4)), vals, vals), {}
    else:
        args = (rows, spec((8 * s,)), spec((8, 4)), spec((), jnp.float32))
        static = {"s": s}
    compiled = getattr(be, name).lower(*args, row_shape=(2048, 2048),
                                       **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_device_bounds_step_gathers_chi_rows_without_relayout(one_chip):
    """The CHI row gather goes through a 2-D view: gathering rows of the
    4-D (N, 17, 17, 17) table relays out all of it (9.3 GB of temporaries
    at paper scale), which does not fit beside the resident masks."""
    from repro.core.backend import _device_cp_bounds

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    edges = s((G + 1,), jnp.int32)
    compiled = _device_cp_bounds.lower(
        s((PAPER_MASKS, G + 1, G + 1, NB + 1), jnp.int32),
        s((PAPER_MASKS,), jnp.int32), s((PAPER_MASKS, 4), jnp.int32),
        edges, edges, s((4,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
