"""AdamW with global-norm clipping and cosine schedule — pure JAX.

Mixed-precision policy (DESIGN.md §6): model params live in bf16; the
optimizer keeps fp32 first/second moments **and an fp32 master copy** of the
params.  update() consumes bf16 grads, updates fp32 state, and emits fresh
bf16 params — the standard large-scale recipe.  All optimizer state shards
exactly like its parameter (ZeRO-style; the launcher assigns shardings from
the same logical axes).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    # Memory policy.  Default: fp32 moments + fp32 master weights
    # (14 B/param with bf16 params).  Low-mem mode for the ≥200B MoE cells:
    # bf16 moments, no master (6 B/param) — production would use 8-bit
    # moments instead.
    moments_dtype: str = "float32"
    use_master: bool = True


class OptState(NamedTuple):
    step: jax.Array            # () int32
    mu: Any                    # moments_dtype, like params
    nu: Any                    # moments_dtype, like params
    master: Any                # fp32 master weights (or () in low-mem mode)


def init_opt_state(params, cfg: OptConfig | None = None) -> OptState:
    cfg = OptConfig() if cfg is None else cfg
    mdt = jnp.dtype(cfg.moments_dtype)
    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, mdt), params)
    master = (jax.tree.map(lambda x: x.astype(jnp.float32), params)
              if cfg.use_master else ())
    return OptState(jnp.zeros((), jnp.int32), zeros,
                    jax.tree.map(jnp.copy, zeros), master)


def lr_at(cfg: OptConfig, step) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = cfg.learning_rate * step / max(cfg.warmup_steps, 1)
    frac = jnp.clip((step - cfg.warmup_steps) /
                    max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (
        1 + jnp.cos(jnp.pi * frac))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.learning_rate * cos)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def apply_updates(cfg: OptConfig, params, grads, state: OptState):
    """→ (new_params (bf16-like params), new_state, metrics)."""
    step = state.step + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    lr = lr_at(cfg, step)

    mdt = jnp.dtype(cfg.moments_dtype)

    def upd(g, mu, nu, ref):
        """ref: fp32 master (master mode) or the bf16 param (low-mem)."""
        g = g.astype(jnp.float32) * scale
        mu32 = mu.astype(jnp.float32)
        nu32 = nu.astype(jnp.float32)
        mu32 = b1 * mu32 + (1 - b1) * g
        nu32 = b2 * nu32 + (1 - b2) * jnp.square(g)
        update = (mu32 / bc1) / (jnp.sqrt(nu32 / bc2) + cfg.eps)
        w = ref.astype(jnp.float32)
        w = w - lr * (update + cfg.weight_decay * w)
        return mu32.astype(mdt), nu32.astype(mdt), w

    flat_g, treedef = jax.tree.flatten(grads)
    flat_mu = treedef.flatten_up_to(state.mu)
    flat_nu = treedef.flatten_up_to(state.nu)
    flat_p = treedef.flatten_up_to(params)
    flat_ref = (treedef.flatten_up_to(state.master) if cfg.use_master
                else flat_p)
    out = [upd(g, m, n, w) for g, m, n, w in
           zip(flat_g, flat_mu, flat_nu, flat_ref)]
    mu = jax.tree.unflatten(treedef, [o[0] for o in out])
    nu = jax.tree.unflatten(treedef, [o[1] for o in out])
    master = (jax.tree.unflatten(treedef, [o[2] for o in out])
              if cfg.use_master else ())

    new_params = jax.tree.unflatten(
        treedef, [o[2].astype(p.dtype) for o, p in zip(out, flat_p)])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, OptState(step, mu, nu, master), metrics
