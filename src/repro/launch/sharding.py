"""Logical-axis → mesh-axis mapping (the GSPMD plan for every arch).

Models annotate parameters and activations with *logical* names
(models/layers.py); this module turns them into ``PartitionSpec``s for
whatever mesh is active, with two safety rules:

  * **divisibility** — a mesh axis is only used if it divides the dimension
    (GQA kv=8 on a 16-way "model" axis falls back to replication, matching
    practice);
  * **single-use** — a mesh axis appears at most once per spec (e.g. the
    RG-LRU (w, w) square matrices shard only one side).

The plan (DESIGN.md §6):
  params   — FSDP ("embed" over data×pod, ZeRO-3) × TP ("model" on
             heads/mlp/vocab) × EP (experts over "model");
  acts     — batch over data×pod, heads/mlp/vocab over "model";
  caches   — decode KV **sequence** over "model" (flash-decoding SP);
             SSM/RG-LRU states shard heads/width over "model".
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import layers as L

# Candidate mesh axes per logical axis, in priority order.  Tuples are used
# jointly (FSDP over data AND pod); the resolver drops members that are
# absent, already used, or non-divisible.
PARAM_RULES: dict[str, tuple] = {
    "vocab": ("model",),
    "embed": ("data", "pod"),          # ZeRO-3 / FSDP
    "q_heads": ("model",),
    "kv_heads": ("model",),
    "heads": ("model",),
    "mlp": ("model",),
    "expert_mlp": (),                  # experts already take "model"
    "experts": ("model",),
    "q_lora": (), "kv_lora": (), "head_dim": (), "conv": (),
    "state": (), "mlp2": (), "layers": (),
}

ACT_RULES: dict[str, tuple] = {
    "batch": ("pod", "data"),
    "tokens": ("pod", "data"),         # flattened (B·S) MoE dispatch rows
    "seq": (),
    "embed": (),
    "mlp": ("model",),
    "expert_mlp": (),
    "experts": ("model",),
    "heads": ("model",),
    "q_heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "kv_seq": ("model",),              # seq-parallel cross/decode KV
}

# Pure-DP variant (small dense models, §Perf iter 8): batch over the whole
# mesh, no tensor parallelism; vocab keeps "model" (free in fwd, one small
# AR in bwd) so the logits never replicate.
PURE_DP_PARAM_RULES = dict(PARAM_RULES, **{
    "q_heads": (), "kv_heads": (), "heads": (), "mlp": (), "experts": (),
    "embed": ("data",),                # ZeRO over data only
})
PURE_DP_ACT_RULES = dict(ACT_RULES, **{
    "batch": ("pod", "data", "model"),
    "tokens": ("pod", "data", "model"),
    "mlp": (), "heads": (), "q_heads": (), "kv_heads": (), "experts": (),
})


def rules_for(cfg=None, mesh=None):
    """(param_rules, act_rules) for a config (pure-DP override aware).

    Pure DP only pays when the global batch covers the whole mesh (train_4k
    batch 256 == the 256-chip single pod); on the 512-chip multi-pod mesh
    the same batch cannot, so those cells keep the TP mapping."""
    if cfg is not None and getattr(cfg, "prefer_pure_dp", False):
        if mesh is None or "pod" not in mesh.axis_names:
            return PURE_DP_PARAM_RULES, PURE_DP_ACT_RULES
    return PARAM_RULES, ACT_RULES


def _resolve_dim(mesh: Mesh, cand: tuple, size: int, used: set):
    """Pick the largest usable prefix of candidate axes for one dimension."""
    picked = []
    prod = 1
    for ax in cand:
        if ax not in mesh.axis_names or ax in used:
            continue
        n = mesh.shape[ax]
        if size % (prod * n) == 0:
            picked.append(ax)
            prod *= n
    for ax in picked:
        used.add(ax)
    if not picked:
        return None
    return tuple(picked) if len(picked) > 1 else picked[0]


def spec_for(mesh: Mesh, rules: dict, axes: tuple, shape: tuple) -> P:
    used: set = set()
    out = []
    for name, size in zip(axes, shape):
        if name is None:
            out.append(None)
            continue
        cand = rules.get(name, ())
        out.append(_resolve_dim(mesh, cand, int(size), used))
    return P(*out)


def param_sharding_tree(mesh: Mesh, shapes: Any, axes: Any, cfg=None):
    """shapes: pytree of ShapeDtypeStruct (from eval_shape); axes: logical
    axes pytree.  → same-structure tree of NamedSharding."""
    rules = rules_for(cfg, mesh)[0]
    is_tup = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    return jax.tree.map(
        lambda s, a: NamedSharding(mesh, spec_for(mesh, rules, a, s.shape)),
        shapes, axes, is_leaf=lambda x: x is None or is_tup(x))


def install_activation_rules(mesh: Mesh, cfg=None) -> None:
    """Hook models' shard_act onto this mesh (launcher entry point)."""
    rules = rules_for(cfg, mesh)[1]

    def rule(x, axes):
        spec = spec_for(mesh, rules, axes, x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    L.set_activation_rule(rule)


# -- cache shardings (decode / prefill) --------------------------------------

_CACHE_LEAF_AXES = {
    # leaf-name → logical axes by rank
    "k": ("batch", "kv_seq", "kv_heads_repl", None),
    "v": ("batch", "kv_seq", "kv_heads_repl", None),
    "xk": ("batch", "kv_seq", "kv_heads_repl", None),
    "xv": ("batch", "kv_seq", "kv_heads_repl", None),
    "ckv": ("batch", "kv_seq", None),
    "kpe": ("batch", "kv_seq", None),
    "state": ("batch", "heads", None, None),
    "conv": ("batch", None, "mlp"),
    "h": ("batch", "mlp"),
}

_CACHE_RULES = dict(ACT_RULES)
_CACHE_RULES["kv_heads_repl"] = ()     # seq takes "model"; heads replicate


def cache_sharding_tree(mesh: Mesh, cache_shapes: Any):
    """Assign shardings to a cache pytree (by leaf name, via tree paths).
    Stacked group caches get their leading layer axis replicated."""

    def assign(path, leaf):
        name = None
        for entry in reversed(path):
            key = getattr(entry, "key", None)
            if isinstance(key, str):
                name = key
                break
        axes = _CACHE_LEAF_AXES.get(name)
        if axes is None:
            return NamedSharding(mesh, P())
        rank = len(leaf.shape)
        if rank == len(axes) + 1:      # stacked over scan groups
            axes = (None,) + axes
        axes = axes[:rank] if len(axes) >= rank else axes + (None,) * (
            rank - len(axes))
        return NamedSharding(mesh, spec_for(mesh, _CACHE_RULES, axes,
                                            leaf.shape))

    return jax.tree_util.tree_map_with_path(assign, cache_shapes)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
