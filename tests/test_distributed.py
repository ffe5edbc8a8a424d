"""Multi-device tests for the distributed query engine + sharded training.

The main pytest session must see 1 device (dry-run isolation), so these run
in subprocesses that set XLA_FLAGS=--xla_force_host_platform_device_count=8
before importing jax.
"""

import os
import subprocess
import sys


SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_query_engine():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import chi, cp, distributed as dist
from repro.data.masks import saliency_masks

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
N, H, W = 64, 64, 64
cfg = chi.CHIConfig(grid=8, num_bins=8, height=H, width=W)
masks = saliency_masks(N, H, W, seed=3)[0]
tables = chi.build_chi_np(masks, cfg)
rois = np.tile([8, 8, 56, 56], (N, 1)).astype(np.int32)
eng = dist.DistributedEngine(mesh, cfg)
t_sh = jax.device_put(jnp.asarray(tables), dist.row_sharding(mesh, 4))
r_sh = jax.device_put(jnp.asarray(rois), dist.row_sharding(mesh, 2))
lv, uv, T = 0.5, 1.0, 200
accept, undecided, counts = eng.filter_bounds(t_sh, r_sh, lv, uv, "<", T)
exact = np.array([cp.cp_exact_np(m, rois[0], lv, uv) for m in masks])
acc, und = np.asarray(accept), np.asarray(undecided)
assert np.all(exact[acc] < T)
assert np.all(exact[~(acc | und)] >= T)
assert int(counts[1]) < N, "bounds must decide something on blobby masks"

vals, ids, tau, surv = eng.topk_candidates(t_sh, r_sh, lv, uv, k=5)
top5 = set(np.argsort(-exact, kind="stable")[:5])
assert top5.issubset(set(np.nonzero(np.asarray(surv))[0]))
assert np.asarray(surv).sum() < N, "top-k pruning must drop candidates"

m_sh = jax.device_put(jnp.asarray(masks), dist.row_sharding(mesh, 3))
got = np.asarray(eng.verify(m_sh, r_sh, lv, uv))
assert np.array_equal(got, exact)
print("DIST_ENGINE_OK", int(counts[1]), int(np.asarray(surv).sum()))
""")


def test_mesh_backend_multi_device_matches_host():
    """run_plan(backend="mesh") over a real 8-device mesh returns the host
    backend's exact ids/scores and n_verified — including a candidate count
    that does NOT divide the device count (exercises the padding path)."""
    _run("""
import numpy as np, jax
from repro.core import CHIConfig, MaskStore
from repro.core.backend import MeshBackend
from repro.core.exprs import AggCP, BinOp, Cmp, CP, RoiArea
from repro.core.plan import LogicalPlan, run_plan
from repro.core.store import MASK_META_DTYPE
from repro.data.masks import object_boxes, saliency_masks

B, H, W = 52, 64, 64          # 52 % 8 != 0 -> padding exercised
rois = object_boxes(B, H, W, seed=2)
masks, _ = saliency_masks(B, H, W, seed=1, attacked_fraction=0.25, boxes=rois)
meta = np.zeros(B, MASK_META_DTYPE)
meta["mask_id"] = np.arange(B) + 100
meta["image_id"] = np.arange(B) // 2
meta["mask_type"] = np.arange(B) % 2 + 1
cfg = CHIConfig(grid=8, num_bins=8, height=H, width=W)
store = MaskStore.create_memory(masks, meta, cfg)
be = MeshBackend(store, jax.make_mesh((8,), ("data",),
                 axis_types=(jax.sharding.AxisType.Auto,)))

plans = [
    LogicalPlan(predicate=Cmp(CP(None, 0.5, 1.0), ">", 500.0)),
    LogicalPlan(order_by=CP(None, 0.2, 0.6), k=7),
    LogicalPlan(predicate=Cmp(CP("provided", 0.8, 1.0), ">", 50.0),
                order_by=BinOp("/", CP(None, 0.2, 0.6), RoiArea(None)),
                k=5, desc=False),
    LogicalPlan(agg="MAX", agg_expr=CP(None, 0.4, 0.8)),
    LogicalPlan(select="image_id", order_by=AggCP("union", 0.8, None), k=5),
]
for plan in plans:
    got, st = run_plan(store, plan, provided_rois=rois, verify_batch=8,
                       backend=be)
    want, st0 = run_plan(store, plan, provided_rois=rois, verify_batch=8,
                         backend="host")
    if isinstance(want, tuple):
        assert list(got[0]) == list(want[0]), plan.kind
        np.testing.assert_allclose(got[1], want[1])
    elif isinstance(want, float):
        assert got == want, plan.kind
    else:
        assert list(got) == list(want), plan.kind
    assert st.n_verified == st0.n_verified, plan.kind
print("MESH_BACKEND_OK")
""")


def test_sharded_train_step_matches_single_device():
    _run("""
import numpy as np, jax, jax.numpy as jnp, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import load_smoke
from repro.models import build_model
from repro.launch import sharding as sh
from repro.launch.mesh import make_local_mesh
from repro.data.pipeline import SyntheticLMData
from repro.train.optimizer import OptConfig
from repro.train.train_loop import init_train_state, make_train_step

cfg = dataclasses.replace(load_smoke("granite_3_2b"), dtype="float32")
model = build_model(cfg)
opt_cfg = OptConfig(warmup_steps=0, total_steps=10)
params, axes, opt = init_train_state(model, jax.random.PRNGKey(0), opt_cfg)
data = SyntheticLMData(cfg, seq_len=16, global_batch=8)
batch = data.batch_at(0)

# single device reference
step = make_train_step(model, opt_cfg)
p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

# 2x4 mesh with full sharding rules
mesh = make_local_mesh((2, 4), ("data", "model"))
sh.install_activation_rules(mesh)
shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
pshard = sh.param_sharding_tree(mesh, shapes, axes)
p_dev = jax.tree.map(jax.device_put, params, pshard)
o_dev = jax.device_put(opt)
b_dev = jax.tree.map(
    lambda x: jax.device_put(np.asarray(x), NamedSharding(mesh, P("data"))), batch)
step_sh = make_train_step(model, opt_cfg, param_shardings=pshard)
p_sh, _, m_sh = jax.jit(step_sh)(p_dev, o_dev, b_dev)
# losses agree to f32 roundoff; sharded reductions reorder float adds and
# Adam's normalization amplifies that for near-zero grads — so params match
# to ~1e-3, not bitwise (measured: loss delta 1.3e-5, param delta 5e-4).
assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3
err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
          for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)))
assert err < 5e-3, f"sharded step diverges from single-device: {err}"
print("SHARDED_TRAIN_OK", float(m_sh["loss"]))
""")


def test_decode_with_seq_sharded_cache():
    _run("""
import numpy as np, jax, jax.numpy as jnp, dataclasses
from repro.configs import load_smoke
from repro.models import build_model
from repro.launch import sharding as sh
from repro.launch.mesh import make_local_mesh

cfg = dataclasses.replace(load_smoke("granite_3_2b"), dtype="float32")
model = build_model(cfg)
params, axes = model.init(jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)

cache = model.init_cache(2, 16)
logits_ref, cache_ref = model.prefill(params, {"tokens": tokens}, cache)
logits_ref2, _ = model.decode_step(params, cache_ref, tokens[:, -1:],
                                   jnp.int32(8))

mesh = make_local_mesh((1, 8), ("data", "model"))
sh.install_activation_rules(mesh)
cache_shapes = jax.eval_shape(lambda: model.init_cache(2, 16))
cshard = sh.cache_sharding_tree(mesh, cache_shapes)
cache_sh = jax.tree.map(lambda s, d: jax.device_put(jnp.zeros(s.shape, s.dtype), d),
                        cache_shapes, cshard)
logits_p, cache_sh = jax.jit(model.prefill)(params, {"tokens": tokens}, cache_sh)
logits_d, _ = jax.jit(model.decode_step)(params, cache_sh, tokens[:, -1:],
                                         jnp.int32(8))
np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_ref),
                           rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_ref2),
                           rtol=1e-4, atol=1e-4)
print("SP_DECODE_OK")
""")
