"""Decoder-LM assembly: every non-enc-dec architecture in the zoo.

Layer stacking follows the config's ``layer_pattern`` repeating unit
(DESIGN.md §5): the stack is

    [prefix]  first_k_dense layers, unrolled   (DeepSeek's leading dense FFNs)
    [groups]  num_scan_groups × pattern, **scanned** (compile-time O(1) HLO)
    [tail]    pattern remainder, unrolled      (gemma3's trailing 2 locals)

Group parameters are stacked pytrees (leading "layers" axis) built by
vmapping the group initializer.  Scan keeps compile time flat across 24–64
layer models.

Block kinds: "global"/"local" (GQA or MLA attention + FFN), "rglru"
(recurrent block + FFN), "ssm" (Mamba-2 block, no separate FFN).
MoE replaces the dense FFN after ``first_k_dense`` layers.  DeepSeek-V3's
MTP head is an extra shared-embedding block predicting t+2.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mla as mla_lib
from . import moe as moe_lib
from . import rglru as rglru_lib
from . import ssm as ssm_lib
from .layers import (Param, cross_entropy, embed, init_embedding, init_rms,
                     init_swiglu, logits_from_tied, param, rms_norm, shard_act,
                     split_params, swiglu)

Array = jax.Array


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _uses_moe(cfg) -> bool:
    return cfg.num_experts > 0


def init_block(key, cfg, kind: str, use_moe: bool, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": init_rms(k1, cfg.d_model)}
    if kind in ("global", "local"):
        if cfg.attention == "mla":
            p["mixer"] = mla_lib.init_mla(k2, cfg, dtype)
        else:
            p["mixer"] = attn.init_attention(k2, cfg, dtype)
    elif kind == "rglru":
        p["mixer"] = rglru_lib.init_rglru(k2, cfg, dtype)
    elif kind == "ssm":
        p["mixer"] = ssm_lib.init_ssm(k2, cfg, dtype)
    else:
        raise ValueError(kind)
    if kind != "ssm":
        p["ln2"] = init_rms(k3, cfg.d_model)
        if use_moe:
            p["ffn"] = moe_lib.init_moe(k4, cfg, dtype)
        else:
            p["ffn"] = init_swiglu(k4, cfg.d_model, cfg.d_ff, dtype)
    return p


def apply_block(p, cfg, kind: str, use_moe: bool, x: Array,
                positions: Array) -> tuple[Array, Array]:
    """Training-path block.  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("global", "local"):
        if cfg.attention == "mla":
            h = mla_lib.mla_attention(p["mixer"], cfg, h, positions)
        else:
            h = attn.attention(p["mixer"], cfg, h, positions, kind)
    elif kind == "rglru":
        h = rglru_lib.rglru_block(p["mixer"], cfg, h)
    else:  # ssm
        h = ssm_lib.ssm_block(p["mixer"], cfg, h)
    x = x + h
    if kind != "ssm":
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if use_moe:
            h, aux = moe_lib.moe_ffn(p["ffn"], cfg, h)
        else:
            h = swiglu(p["ffn"], h)
        x = x + h
    return shard_act(x, ("batch", "seq", "embed")), aux


def init_block_cache(cfg, kind: str, batch: int, max_len: int, dtype):
    if kind in ("global", "local"):
        if cfg.attention == "mla":
            return mla_lib.init_mla_cache(cfg, batch, max_len, dtype)
        return attn.init_cache(cfg, batch, max_len, kind, dtype)
    if kind == "rglru":
        return rglru_lib.init_rglru_cache(cfg, batch, dtype)
    return ssm_lib.init_ssm_cache(cfg, batch, dtype)


def apply_block_prefill(p, cfg, kind, use_moe, x, positions, cache):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("global", "local"):
        if cfg.attention == "mla":
            h, cache = mla_lib.mla_prefill(p["mixer"], cfg, h, positions, cache)
        else:
            h, cache = attn.prefill_attention(p["mixer"], cfg, h, positions,
                                              kind, cache)
    elif kind == "rglru":
        h, cache = rglru_lib.rglru_prefill(p["mixer"], cfg, h, cache)
    else:
        h, cache = ssm_lib.ssm_prefill(p["mixer"], cfg, h, cache)
    x = x + h
    if kind != "ssm":
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        h = moe_lib.moe_ffn(p["ffn"], cfg, h)[0] if use_moe else swiglu(p["ffn"], h)
        x = x + h
    return x, cache


def apply_block_decode(p, cfg, kind, use_moe, x, pos, cache):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("global", "local"):
        if cfg.attention == "mla":
            h, cache = mla_lib.mla_decode(p["mixer"], cfg, h, pos, cache)
        else:
            h, cache = attn.decode_attention(p["mixer"], cfg, h, pos, kind,
                                             cache)
    elif kind == "rglru":
        h, cache = rglru_lib.rglru_decode(p["mixer"], cfg, h, cache)
    else:
        h, cache = ssm_lib.ssm_decode(p["mixer"], cfg, h, cache)
    x = x + h
    if kind != "ssm":
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        h = moe_lib.moe_ffn(p["ffn"], cfg, h)[0] if use_moe else swiglu(p["ffn"], h)
        x = x + h
    return x, cache


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def _stack_plan(cfg):
    """(prefix_kinds, group_kinds, n_groups, tail_kinds)."""
    kinds = list(cfg.pattern_layers)
    nprefix = cfg.first_k_dense if _uses_moe(cfg) else 0
    prefix = tuple(kinds[:nprefix])
    rest = kinds[nprefix:]
    glen = len(cfg.layer_pattern)
    n_groups = len(rest) // glen
    tail = tuple(rest[n_groups * glen:])
    return prefix, tuple(cfg.layer_pattern), n_groups, tail


class DecoderLM:
    """Functional LM: params are plain pytrees; methods are jit-safe."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.prefix_kinds, self.group_kinds, self.n_groups, self.tail_kinds = \
            _stack_plan(cfg)

    # -- init ---------------------------------------------------------------

    def _init_group(self, key):
        moe = _uses_moe(self.cfg)
        keys = jax.random.split(key, len(self.group_kinds))
        return {f"block{i}": init_block(keys[i], self.cfg, kind, moe, self.dtype)
                for i, kind in enumerate(self.group_kinds)}

    def init(self, rng):
        """→ (params, logical_axes) — two same-structure pytrees."""
        return split_params(self.init_tree(rng))

    def init_tree(self, rng):
        """Param-node tree (axes as static pytree aux — eval_shape-safe)."""
        cfg = self.cfg
        kE, kP, kG, kT, kM, kN = jax.random.split(rng, 6)
        tree: dict[str, Any] = {
            "embedding": init_embedding(kE, cfg.padded_vocab, cfg.d_model,
                                        self.dtype),
            "final_norm": init_rms(kN, cfg.d_model),
        }
        if self.prefix_kinds:
            keys = jax.random.split(kP, len(self.prefix_kinds))
            tree["prefix"] = {
                f"block{i}": init_block(keys[i], cfg, kind, False, self.dtype)
                for i, kind in enumerate(self.prefix_kinds)}
        if self.n_groups:
            gkeys = jax.random.split(kG, self.n_groups)
            stacked = jax.vmap(self._init_group)(gkeys)
            # prepend the scanned "layers" axis to every logical-axes tuple
            stacked = jax.tree.map(
                lambda p: Param(p.value, ("layers",) + p.axes),
                stacked, is_leaf=lambda x: isinstance(x, Param))
            tree["groups"] = stacked
        if self.tail_kinds:
            keys = jax.random.split(kT, len(self.tail_kinds))
            tree["tail"] = {
                f"block{i}": init_block(keys[i], cfg, kind, _uses_moe(cfg),
                                        self.dtype)
                for i, kind in enumerate(self.tail_kinds)}
        if cfg.mtp_depth:
            km1, km2, km3 = jax.random.split(kM, 3)
            tree["mtp"] = {
                "proj": param(km1, (2 * cfg.d_model, cfg.d_model),
                              ("embed", "embed"), dtype=self.dtype),
                "block": init_block(km2, cfg, "global", _uses_moe(cfg),
                                    self.dtype),
                "norm": init_rms(km3, cfg.d_model),
            }
        return tree

    # -- forward (train) ------------------------------------------------------

    def _inputs(self, params, batch):
        """Token (+ optional patch) embeddings and positions."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = embed(params["embedding"], tokens) * jnp.asarray(
            cfg.embed_scale, self.dtype)
        if cfg.num_patches and "patches" in batch:
            patches = batch["patches"].astype(self.dtype)
            x = jnp.concatenate([patches, x], axis=1)
        if cfg.pos_embedding == "absolute":
            from .layers import sinusoidal_positions
            pe = jnp.asarray(sinusoidal_positions(x.shape[1], cfg.d_model),
                             self.dtype)
            x = x + pe[None]
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                     (x.shape[0], x.shape[1]))
        return x, positions

    def hidden_states(self, params, batch):
        """Full stack forward → (h (B,S,D), aux_loss)."""
        cfg = self.cfg
        x, positions = self._inputs(params, batch)
        aux = jnp.zeros((), jnp.float32)
        moe = _uses_moe(cfg)
        for i, kind in enumerate(self.prefix_kinds):
            x, a = apply_block(params["prefix"][f"block{i}"], cfg, kind, False,
                               x, positions)
            aux += a
        if self.n_groups:
            def group_fn(x, gp):
                a_g = jnp.zeros((), jnp.float32)
                for i, kind in enumerate(self.group_kinds):
                    x, a = apply_block(gp[f"block{i}"], cfg, kind, moe, x,
                                       positions)
                    a_g += a
                return x, a_g

            if cfg.remat:
                group_fn = jax.checkpoint(
                    group_fn, policy=jax.checkpoint_policies.nothing_saveable)

            def body(carry, gp):
                x, aux = carry
                x, a_g = group_fn(x, gp)
                return (x, aux + a_g), None

            (x, aux), _ = jax.lax.scan(body, (x, aux), params["groups"])
        for i, kind in enumerate(self.tail_kinds):
            x, a = apply_block(params["tail"][f"block{i}"], cfg, kind, moe, x,
                               positions)
            aux += a
        return rms_norm(x, params["final_norm"], cfg.norm_eps), aux

    def logits(self, params, batch):
        h, aux = self.hidden_states(params, batch)
        return logits_from_tied(params["embedding"], h,
                                self.cfg.vocab_size), aux

    def loss(self, params, batch):
        """batch: tokens (B,S), labels (B,S) [-1 = pad] (+ patches for VLM).

        Returns (loss, metrics-dict).  VLM: labels cover text positions only;
        patch positions are prepended and excluded automatically.
        """
        cfg = self.cfg
        h, aux = self.hidden_states(params, batch)
        labels = batch["labels"]
        if cfg.num_patches and "patches" in batch:
            h_text = h[:, -labels.shape[1]:]
        else:
            h_text = h
        logits = logits_from_tied(params["embedding"], h_text, cfg.vocab_size)
        ce = cross_entropy(logits, labels)
        total = ce + aux
        metrics = {"ce": ce, "aux": aux}
        if cfg.mtp_depth and "labels_mtp" in batch:
            mtp = params["mtp"]
            # combine h_t with the embedding of token_{t+1} (= the main label)
            emb_next = embed(params["embedding"],
                             jnp.maximum(batch["labels"], 0))
            hin = jnp.concatenate(
                [rms_norm(h_text, mtp["norm"], cfg.norm_eps),
                 emb_next.astype(h_text.dtype)], axis=-1) @ mtp["proj"]
            positions = jnp.broadcast_to(
                jnp.arange(hin.shape[1]), hin.shape[:2])
            h_mtp, _ = apply_block(mtp["block"], cfg, "global", _uses_moe(cfg),
                                   hin, positions)
            logits_mtp = logits_from_tied(params["embedding"], h_mtp, cfg.vocab_size)
            ce_mtp = cross_entropy(logits_mtp, batch["labels_mtp"])
            total = total + cfg.mtp_weight * ce_mtp
            metrics["ce_mtp"] = ce_mtp
        metrics["loss"] = total
        return total, metrics

    # -- serving ----------------------------------------------------------------

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        cache: dict[str, Any] = {}
        if self.prefix_kinds:
            cache["prefix"] = {
                f"block{i}": init_block_cache(cfg, kind, batch, max_len,
                                              self.dtype)
                for i, kind in enumerate(self.prefix_kinds)}
        if self.n_groups:
            def one(_):
                return {f"block{i}": init_block_cache(cfg, kind, batch,
                                                      max_len, self.dtype)
                        for i, kind in enumerate(self.group_kinds)}
            cache["groups"] = jax.vmap(one)(jnp.arange(self.n_groups))
        if self.tail_kinds:
            cache["tail"] = {
                f"block{i}": init_block_cache(cfg, kind, batch, max_len,
                                              self.dtype)
                for i, kind in enumerate(self.tail_kinds)}
        return cache

    def prefill(self, params, batch, cache):
        """Consume the prompt; → (last-position logits, cache)."""
        cfg = self.cfg
        x, positions = self._inputs(params, batch)
        moe = _uses_moe(cfg)
        for i, kind in enumerate(self.prefix_kinds):
            x, cache["prefix"][f"block{i}"] = apply_block_prefill(
                params["prefix"][f"block{i}"], cfg, kind, False, x, positions,
                cache["prefix"][f"block{i}"])
        if self.n_groups:
            def body(x, gp_gc):
                gp, gc = gp_gc
                newc = {}
                for i, kind in enumerate(self.group_kinds):
                    x, newc[f"block{i}"] = apply_block_prefill(
                        gp[f"block{i}"], cfg, kind, moe, x, positions,
                        gc[f"block{i}"])
                return x, newc
            x, cache["groups"] = jax.lax.scan(
                body, x, (params["groups"], cache["groups"]))
        for i, kind in enumerate(self.tail_kinds):
            x, cache["tail"][f"block{i}"] = apply_block_prefill(
                params["tail"][f"block{i}"], cfg, kind, moe, x, positions,
                cache["tail"][f"block{i}"])
        h = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        return logits_from_tied(params["embedding"], h, cfg.vocab_size), cache

    def decode_step(self, params, cache, token: Array, pos):
        """One token for the whole batch.  token: (B, 1) int32, pos: () int32."""
        cfg = self.cfg
        x = embed(params["embedding"], token) * jnp.asarray(
            cfg.embed_scale, self.dtype)
        moe = _uses_moe(cfg)
        for i, kind in enumerate(self.prefix_kinds):
            x, cache["prefix"][f"block{i}"] = apply_block_decode(
                params["prefix"][f"block{i}"], cfg, kind, False, x, pos,
                cache["prefix"][f"block{i}"])
        if self.n_groups:
            def body(x, gp_gc):
                gp, gc = gp_gc
                newc = {}
                for i, kind in enumerate(self.group_kinds):
                    x, newc[f"block{i}"] = apply_block_decode(
                        gp[f"block{i}"], cfg, kind, moe, x, pos,
                        gc[f"block{i}"])
                return x, newc
            x, cache["groups"] = jax.lax.scan(
                body, x, (params["groups"], cache["groups"]))
        for i, kind in enumerate(self.tail_kinds):
            x, cache["tail"][f"block{i}"] = apply_block_decode(
                params["tail"][f"block{i}"], cfg, kind, moe, x, pos,
                cache["tail"][f"block{i}"])
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return logits_from_tied(params["embedding"], h, cfg.vocab_size), cache

    # -- mask extraction (MaskSearch integration) ------------------------------

    def attention_maps(self, params, batch):
        """Post-softmax attention of the *last* attention layer, for the mask
        DB (small models / examples; recomputes the stack).  Returns
        (B, heads, S, S) or None for attention-free stacks."""
        cfg = self.cfg
        kinds = (list(self.prefix_kinds) +
                 list(self.group_kinds) * self.n_groups +
                 list(self.tail_kinds))
        if not any(k in ("global", "local") for k in kinds):
            return None
        if cfg.attention == "mla":
            return None  # examples use GQA archs for attention masks
        x, positions = self._inputs(params, batch)
        # run blocks sequentially (examples-only path, small models) so the
        # last attention block sees its true input
        last = max(i for i, k in enumerate(kinds) if k in ("global", "local"))
        moe = _uses_moe(cfg)
        for i in range(last):
            dense_prefix = i < len(self.prefix_kinds)
            x, _ = apply_block(self._block_params(params, i), cfg, kinds[i],
                               moe and not dense_prefix, x, positions)
        p_block = self._block_params(params, last)
        hn = rms_norm(x, p_block["ln1"], cfg.norm_eps)
        q, k, v = attn._qkv(p_block["mixer"], cfg, hn, positions,
                            kinds[last])
        del v
        b, s, hq, d = q.shape
        hkv = k.shape[2]
        q = q.reshape(b, s, hkv, hq // hkv, d)
        scores = jnp.einsum("bshgd,bthd->bhgst", q, k) / jnp.sqrt(d)
        mask = attn.causal_mask(s, s, 0,
                                cfg.local_window if kinds[last] == "local"
                                else 0)
        scores = jnp.where(mask, scores.astype(jnp.float32), attn.NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        return probs.reshape(b, hkv * (hq // hkv), s, s)

    def _block_params(self, params, flat_idx: int):
        np_, ng, gl = (len(self.prefix_kinds), self.n_groups,
                       len(self.group_kinds))
        if flat_idx < np_:
            return params["prefix"][f"block{flat_idx}"]
        flat_idx -= np_
        if flat_idx < ng * gl:
            g, i = divmod(flat_idx, gl)
            return jax.tree.map(lambda x: x[g], params["groups"])[f"block{i}"]
        return params["tail"][f"block{flat_idx - ng * gl}"]
