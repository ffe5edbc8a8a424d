"""GQA attention: full/local variants, qk-norm, RoPE, KV cache, SP decode.

Two memory/perf-critical design points:

* **Chunked (memory-efficient) attention.**  Materializing (S × S) f32
  scores at 4k–32k sequence lengths costs tens of GB per device; queries are
  processed in unrolled blocks of ``cfg.attn_q_block`` (exact row softmax per
  block — no online accumulation needed since each block sees all its keys).
  Blocks are a static python loop, NOT a scan, so ``cost_analysis`` counts
  their FLOPs.

* **Local layers slice K/V.**  Sliding-window layers (gemma3 5:1,
  recurrentgemma) gather only the ``q_block + window`` keys a block can see —
  O(S·W) compute and memory instead of O(S²), matching production kernels.

* **KV repeat for TP.**  K/V are repeated to the full query-head count
  before the score einsum so the "heads" axis shards over "model" even when
  ``kv_heads`` doesn't divide it (kv=8 on a 16-way axis).  The repeat is
  cheap (bf16 K/V, heads sharded); the scores it unlocks sharding for are
  the expensive tensor.

Cache layout is ``(B, S_max, kv_heads, head_dim)``.  For decode shapes the
launcher shards the cache's **sequence** axis over "model"
(flash-decoding-style SP): per-step scores come out seq-sharded and XLA
inserts the partial-softmax all-reduces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import (apply_rope, init_rms, param, rms_norm, shard_act)

Array = jax.Array
NEG_INF = -2.0e38


def init_attention(key, cfg, dtype):
    k1, k2, k3, k4, kn1, kn2 = jax.random.split(key, 6)
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": param(k1, (d, hq, hd), ("embed", "q_heads", "head_dim"), dtype=dtype),
        "wk": param(k2, (d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wv": param(k3, (d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wo": param(k4, (hq, hd, d), ("q_heads", "head_dim", "embed"), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms(kn1, hd, axes=("head_dim",))
        p["k_norm"] = init_rms(kn2, hd, axes=("head_dim",))
    return p


def _theta(cfg, kind: str) -> float:
    if kind == "local" and cfg.rope_theta_local is not None:
        return cfg.rope_theta_local
    return cfg.rope_theta


def _qkv(p, cfg, x: Array, positions: Array, kind: str):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embedding == "rope":
        theta = _theta(cfg, kind)
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    q = shard_act(q, ("batch", "seq", "q_heads", None))
    k = shard_act(k, ("batch", "seq", "kv_heads", None))
    v = shard_act(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def repeat_kv(k: Array, groups: int) -> Array:
    if groups == 1:
        return k
    out = jnp.repeat(k, groups, axis=2)
    return shard_act(out, ("batch", "kv_seq", "heads", None))


def _block_attend(qb: Array, k: Array, v: Array, q_pos: Array, k_pos: Array,
                  causal: bool, window: int) -> Array:
    """One query block against a key slice.  qb: (B,bq,H,D), k/v: (B,T,H,D),
    q_pos: (bq,), k_pos: (T,).  Full heads (already repeated)."""
    d = qb.shape[-1]
    scores = jnp.einsum("bshd,bthd->bhst", qb, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    scores = shard_act(scores, ("batch", "heads", None, "kv_seq"))
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _pick_block(cfg, s: int) -> int:
    bq = cfg.attn_q_block or s
    bq = min(bq, s)
    while s % bq:
        bq -= 1
    return max(bq, 1)


def _sdpa(q: Array, k: Array, v: Array, cfg, *, causal: bool, window: int,
          offset: int = 0) -> Array:
    """(B,S,Hq,D) × (B,T,Hkv,D) chunked grouped attention, f32 softmax."""
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)
    bq = _pick_block(cfg, s)
    k_pos_all = jnp.arange(t)
    outs = []
    for i in range(s // bq):                      # static unroll (see module doc)
        qs = i * bq
        qb = jax.lax.slice_in_dim(q, qs, qs + bq, axis=1)
        q_pos = jnp.arange(qs, qs + bq) + offset
        if window > 0 and t > bq + window:
            # local layers: only the visible key stripe
            ks = max(qs + offset - window + 1, 0)
            klen = min(bq + window, t - ks)
            kb = jax.lax.slice_in_dim(k, ks, ks + klen, axis=1)
            vb = jax.lax.slice_in_dim(v, ks, ks + klen, axis=1)
            k_pos = jnp.arange(ks, ks + klen)
        else:
            kb, vb, k_pos = k, v, k_pos_all
        outs.append(_block_attend(qb, kb, vb, q_pos, k_pos, causal, window))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return shard_act(out, ("batch", "seq", "heads", None))


def attention(p, cfg, x: Array, positions: Array, kind: str = "global") -> Array:
    """Training/prefill self-attention (causal; sliding window if local)."""
    q, k, v = _qkv(p, cfg, x, positions, kind)
    window = cfg.local_window if kind == "local" else 0
    out = _sdpa(q, k, v, cfg, causal=True, window=window)
    return jnp.einsum("bshd,hdo->bso", out, p["wo"])


def bidirectional_attention(p, cfg, x: Array, positions: Array) -> Array:
    """Encoder self-attention (whisper encoder)."""
    q, k, v = _qkv(p, cfg, x, positions, "global")
    out = _sdpa(q, k, v, cfg, causal=False, window=0)
    return jnp.einsum("bshd,hdo->bso", out, p["wo"])


# ---------------------------------------------------------------------------
# KV cache (prefill + decode)
# ---------------------------------------------------------------------------


def init_cache(cfg, batch: int, max_len: int, kind: str, dtype):
    """Zeroed cache for one attention layer.  Local layers only retain a
    window-sized ring (sub-quadratic memory for the hybrid archs)."""
    length = min(max_len, cfg.local_window) if (kind == "local" and
                                                cfg.local_window) else max_len
    shape = (batch, length, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill_attention(p, cfg, x, positions, kind, cache):
    """Run self-attention AND fill the cache (positions 0..s)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions, kind)
    window = cfg.local_window if kind == "local" else 0
    out = _sdpa(q, k, v, cfg, causal=True, window=window)
    length = cache["k"].shape[1]
    if length >= s:
        cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
        }
    else:  # ring for local windows shorter than the prompt
        cache = {"k": k[:, -length:], "v": v[:, -length:]}
    return jnp.einsum("bshd,hdo->bso", out, p["wo"]), cache


def decode_attention(p, cfg, x, pos: Array, kind: str, cache):
    """One-token decode against the cache.

    ``pos``: () int32 — current absolute position.  The new K/V is written at
    ``pos`` (global layers) or ``pos % window`` (local ring); the softmax
    masks out unwritten / out-of-window slots.  With the cache's seq axis
    sharded over "model", the (1 × T) score row is seq-sharded and XLA
    all-reduces the partial softmax (SP decode).
    """
    b = x.shape[0]
    q, k_new, v_new = _qkv(p, cfg, x, jnp.full((b, 1), pos), kind)
    length = cache["k"].shape[1]
    window = cfg.local_window if (kind == "local" and cfg.local_window) else 0
    slot = (pos % length) if window else pos
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
    hq, hkv = q.shape[2], k.shape[2]
    kf = repeat_kv(k, hq // hkv)
    vf = repeat_kv(v, hq // hkv)
    idx = jnp.arange(length)
    if window:
        age = (slot - idx) % length
        valid = (age < jnp.minimum(pos + 1, window))
    else:
        valid = idx <= pos
    d = q.shape[-1]
    scores = jnp.einsum("bshd,bthd->bhst", q, kf).astype(jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    scores = shard_act(scores, ("batch", "heads", None, "kv_seq"))
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(vf.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, vf)
    return (jnp.einsum("bshd,hdo->bso", out, p["wo"]),
            {"k": k, "v": v})


def causal_mask(s: int, t: int, offset: int, window: int = 0) -> Array:
    """(1,1,s,t) bool helper retained for tests."""
    qi = jnp.arange(s)[:, None] + offset
    kj = jnp.arange(t)[None, :]
    m = kj <= qi
    if window > 0:
        m &= kj > (qi - window)
    return m[None, None]


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder → encoder states)
# ---------------------------------------------------------------------------


def init_cross_attention(key, cfg, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": param(k1, (d, hq, hd), ("embed", "q_heads", "head_dim"), dtype=dtype),
        "wk": param(k2, (d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wv": param(k3, (d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wo": param(k4, (hq, hd, d), ("q_heads", "head_dim", "embed"), dtype=dtype),
    }


def cross_kv(p, enc_out: Array):
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"])
    return {"k": shard_act(k, ("batch", "kv_seq", "kv_heads", None)),
            "v": shard_act(v, ("batch", "kv_seq", "kv_heads", None))}


def cross_attention(p, cfg, x: Array, kv) -> Array:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    out = _sdpa(q, kv["k"], kv["v"], cfg, causal=False, window=0)
    return jnp.einsum("bshd,hdo->bso", out, p["wo"])
