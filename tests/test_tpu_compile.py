"""Compile the kernel wrappers with the TPU compiler for a described (not
attached) v5e chip, at the deployment's shapes: B = 256 float32 224×224
masks, packed rows of 7 words, Q = 4 descriptors, S = 2 mask types, CHI
grid 16 × 16 bins.  Also one sharded kernel step on ``v5e:2x2`` and the
device backend's verification step over the paper-scale resident store.

Nothing runs: a compile that passes here is not a chip run.  It catches
what the chip's compiler refuses (block shapes off the (8, 128) tiling,
kernels that cannot be partitioned) at no chip time.
"""

import functools
import os

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
    resident paper-scale store (22,275 masks, 4.47 GB) and fits the
    chip's 16 GB."""
    from repro.core.backend import _device_multi_counts

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = _device_multi_counts.lower(
        s((PAPER_MASKS, H, W), jnp.float32), s((B,), jnp.int32),
        s((Q, B, 4), jnp.int32), s((Q,), jnp.float32),
        s((Q,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


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
