"""Shared model components: params-with-logical-axes, norms, RoPE, MLPs.

Parameters are plain pytrees of arrays.  Every parameter is created through
:func:`param`, which records a tuple of *logical axis names* alongside the
value; :func:`split_params` separates the two trees.  The launcher maps
logical names onto mesh axes (launch/sharding.py) — models never mention
physical axes.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass
class Param:
    """A parameter leaf paired with its logical sharding axes."""

    value: Array
    axes: tuple[str | None, ...]


# Registered as a pytree (axes static) so param trees survive vmap/scan —
# group stacking vmaps the init function directly.
jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), p.axes),
    lambda axes, children: Param(children[0], axes),
)


def is_param(x) -> bool:
    return isinstance(x, Param)


def param(key, shape, axes, *, dtype=jnp.float32, scale: float | str = "fan_in"):
    """Create a Param with truncated-normal init (or zeros/ones)."""
    assert len(shape) == len(axes), (shape, axes)
    if scale == "zeros":
        v = jnp.zeros(shape, dtype)
    elif scale == "ones":
        v = jnp.ones(shape, dtype)
    else:
        if scale == "fan_in":
            fan = shape[-2] if len(shape) >= 2 else shape[-1]
            scale = 1.0 / math.sqrt(fan)
        v = (scale * jax.random.truncated_normal(
            key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)
    return Param(v, tuple(axes))


def split_params(tree):
    """Param tree → (values tree, axes tree)."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)
    return values, axes


def count_params(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Activation sharding constraints (logical → physical happens in launch/)
# ---------------------------------------------------------------------------

_ACT_RULE: Callable[[Array, tuple], Array] | None = None


def set_activation_rule(fn) -> None:
    """Install the logical→physical activation-sharding hook (launcher only)."""
    global _ACT_RULE
    _ACT_RULE = fn


def shard_act(x: Array, axes: tuple[str | None, ...]) -> Array:
    """Annotate an activation with logical axes (no-op without a launcher)."""
    if _ACT_RULE is None:
        return x
    return _ACT_RULE(x, axes)


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: Array, weight: Array, eps: float = 1e-6) -> Array:
    """RMSNorm with a hand-written VJP (the fused-layernorm-backward
    pattern): reductions run in f32, but the (B,S,D) output AND its
    cotangent stay in the compute dtype.  Without this, the f32 variance
    branch keeps the whole backward residual stream in f32 and every TP
    boundary collective pays 2× ICI bytes (EXPERIMENTS.md §Perf iter 7)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * scale * (1.0 + weight.astype(x.dtype))


def _rms_fwd(x, weight, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s32 = jax.lax.rsqrt(var + eps)                     # (B,S,1) f32
    y = x * s32.astype(x.dtype) * (1.0 + weight.astype(x.dtype))
    return y, (x, s32, weight)


def _rms_bwd(eps, res, g):
    x, s32, weight = res
    xf = x.astype(jnp.float32)
    gw = g.astype(jnp.float32) * (1.0 + weight.astype(jnp.float32))
    d = x.shape[-1]
    # dx = s·gw − x·s³·mean(gw·x)
    m = jnp.sum(gw * xf, axis=-1, keepdims=True) / d
    dx = s32 * gw - xf * (s32 ** 3) * m
    dw = jnp.sum((g.astype(jnp.float32) * xf * s32).reshape(-1, d), axis=0)
    return dx.astype(x.dtype), dw.astype(weight.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


def init_rms(key, dim, axes=("embed",)):
    # stored as (weight - 1): zeros init → identity norm (gemma convention,
    # shared across all archs here)
    return param(key, (dim,), axes, scale="zeros")


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# Rotary position embeddings (NeoX half-rotation)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                            / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, D) with positions (..., S) — rotate pairs (d, d+D/2).

    Angle tables are computed in f32 (positions up to 512k need it) but the
    rotation itself runs in the compute dtype: sin/cos ∈ [−1, 1] lose ~3
    bits in bf16 (standard practice) and keeping the (B,S,H,D) tensors
    bf16 keeps their backward cotangents bf16 (§Perf iter 7)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(d, theta), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * freqs     # (..., S, D/2)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)           # (..., S, 1, D/2)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def sinusoidal_positions(length: int, dim: int) -> np.ndarray:
    pos = np.arange(length)[:, None]
    div = np.exp(-math.log(10000.0) * np.arange(0, dim, 2) / dim)
    enc = np.zeros((length, dim), np.float32)
    enc[:, 0::2] = np.sin(pos * div)
    enc[:, 1::2] = np.cos(pos * div)
    return enc


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_swiglu(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": param(k1, (d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "up": param(k2, (d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "down": param(k3, (d_ff, d_model), ("mlp", "embed"), dtype=dtype),
    }


def swiglu(p, x: Array) -> Array:
    h = silu(x @ p["gate"]) * (x @ p["up"])
    h = shard_act(h, ("batch", "seq", "mlp"))
    return h @ p["down"]


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "up": param(k1, (d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "down": param(k2, (d_ff, d_model), ("mlp", "embed"), dtype=dtype),
    }


def gelu_mlp(p, x: Array) -> Array:
    h = gelu(x @ p["up"])
    h = shard_act(h, ("batch", "seq", "mlp"))
    return h @ p["down"]


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int, dtype):
    return param(key, (vocab, d_model), ("vocab", "embed"), dtype=dtype,
                 scale=1.0)


def embed(p_emb: Array, tokens: Array) -> Array:
    x = jnp.take(p_emb, tokens, axis=0)
    return shard_act(x, ("batch", "seq", "embed"))


def logits_from_tied(p_emb: Array, h: Array, valid_vocab: int = 0) -> Array:
    """LM head against (possibly pad-extended) embedding rows.  Columns
    ≥ valid_vocab (the padding that made vocab 16-divisible) are masked to
    −inf so softmax/argmax never see them."""
    out = h @ p_emb.T
    out = shard_act(out, ("batch", "seq", "vocab"))
    if valid_vocab and valid_vocab < p_emb.shape[0]:
        col = jax.lax.broadcasted_iota(jnp.int32, out.shape, out.ndim - 1)
        out = jnp.where(col < valid_vocab, out, jnp.asarray(-2.0e38, out.dtype))
    return out


def cross_entropy(logits: Array, labels: Array, mask: Array | None = None):
    """Mean token cross-entropy in f32; labels < 0 are ignored."""
    logits = logits.astype(jnp.float32)
    valid = labels >= 0 if mask is None else mask & (labels >= 0)
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    ll = jnp.where(valid, ll, 0.0)
    denom = jnp.maximum(jnp.sum(valid), 1)
    return -jnp.sum(ll) / denom
