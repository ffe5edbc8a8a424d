"""Per-architecture smoke tests (reduced configs, CPU).

For every assigned arch: one forward/loss, one SGD-free grad step, one
prefill + two decode steps.  Asserts output shapes and finiteness; the
full configs are not exercised.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, load_smoke
from repro.models import build_model

B, S = 2, 32


def _batch(cfg, rng):
    kt, kp, ka = jax.random.split(rng, 3)
    if cfg.is_encoder_decoder:
        dec = min(S, cfg.max_decode_len)
        return {
            "audio_feats": jax.random.normal(ka, (B, S, cfg.d_model),
                                             jnp.float32),
            "tokens": jax.random.randint(kt, (B, dec), 0, cfg.vocab_size),
            "labels": jax.random.randint(kp, (B, dec), 0, cfg.vocab_size),
        }
    batch = {
        "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(kp, (B, S), 0, cfg.vocab_size),
    }
    if cfg.num_patches:
        batch["patches"] = jax.random.normal(
            ka, (B, cfg.num_patches, cfg.d_model), jnp.float32)
    if cfg.mtp_depth:
        batch["labels_mtp"] = jax.random.randint(kp, (B, S), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_grad(arch):
    cfg = load_smoke(arch)
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    batch = _batch(cfg, jax.random.PRNGKey(1))

    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: loss not finite"
    assert float(loss) > 0

    grads = jax.jit(jax.grad(lambda p, b: model.loss(p, b)[0]))(params, batch)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, f"{arch}: bad grads"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode(arch):
    cfg = load_smoke(arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))

    if cfg.is_encoder_decoder:
        cache = model.init_cache(B, enc_len=S)
        prompt = {"audio_feats": batch["audio_feats"],
                  "tokens": batch["tokens"][:, :8]}
        logits, cache = jax.jit(model.prefill)(params, prompt, cache)
        pos0 = 8
    else:
        max_len = S + 8
        cache = model.init_cache(B, max_len)
        prompt = {k: (v[:, :8] if k == "tokens" else v)
                  for k, v in batch.items() if k in ("tokens", "patches")}
        logits, cache = jax.jit(model.prefill)(params, prompt, cache)
        pos0 = 8 + (cfg.num_patches or 0)

    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    step = jax.jit(model.decode_step)
    token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    for i in range(2):
        logits, cache = step(params, cache, token, jnp.int32(pos0 + i))
        assert logits.shape == (B, 1, cfg.vocab_size)
        assert np.all(np.isfinite(np.asarray(logits, np.float32))), \
            f"{arch}: decode step {i} not finite"
        token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)


def test_prefill_decode_consistency_dense():
    """Decode logits must match teacher-forced forward logits (granite).
    Run in f32: this test checks cache logic, not bf16 noise."""
    import dataclasses
    cfg = dataclasses.replace(load_smoke("granite_3_2b"), dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, 12), 0,
                                cfg.vocab_size)
    full_logits, _ = model.logits(params, {"tokens": tokens})

    cache = model.init_cache(B, 16)
    logits_p, cache = model.prefill(params, {"tokens": tokens[:, :8]}, cache)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, 0], np.float32),
        np.asarray(full_logits[:, 7], np.float32), rtol=2e-2, atol=2e-2)
    # decode positions 8..11 must reproduce teacher forcing
    for pos in range(8, 12):
        logits_d, cache = model.decode_step(
            params, cache, tokens[:, pos:pos + 1], jnp.int32(pos))
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32),
            np.asarray(full_logits[:, pos], np.float32), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ["mamba2_13b", "recurrentgemma_2b"])
def test_prefill_decode_consistency_recurrent(arch):
    """SSM/RG-LRU decode must continue the prefill state correctly.
    Run in f32: this test checks recurrence logic, not bf16 noise."""
    import dataclasses
    cfg = dataclasses.replace(load_smoke(arch), dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, 12), 0,
                                cfg.vocab_size)
    full_logits, _ = model.logits(params, {"tokens": tokens})
    cache = model.init_cache(B, 16)
    logits_p, cache = model.prefill(params, {"tokens": tokens[:, :8]}, cache)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, 0], np.float32),
        np.asarray(full_logits[:, 7], np.float32), rtol=5e-2, atol=5e-2)
    for pos in range(8, 12):
        logits_d, cache = model.decode_step(
            params, cache, tokens[:, pos:pos + 1], jnp.int32(pos))
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32),
            np.asarray(full_logits[:, pos], np.float32), rtol=5e-2, atol=5e-2)


def test_attention_maps_for_masksearch():
    cfg = load_smoke("granite_3_2b")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    maps = model.attention_maps(params, batch)
    assert maps.shape == (B, cfg.num_heads, S, S)
    rows = np.asarray(maps, np.float32).sum(-1)
    np.testing.assert_allclose(rows, 1.0, atol=1e-3)


def test_exact_configs_are_assigned_geometry():
    """Spot-check the full configs against the assignment table."""
    from repro.configs import load_arch
    v3 = load_arch("deepseek_v3_671b")
    assert (v3.num_layers, v3.d_model, v3.num_heads) == (61, 7168, 128)
    assert (v3.num_experts, v3.top_k, v3.vocab_size) == (256, 8, 129280)
    g = load_arch("gemma3_27b")
    assert g.pattern_layers.count("global") == 10       # 10 whole 5L+1G groups
    assert g.pattern_layers.count("local") == 52        # 50 in groups + 2 tail
    assert g.num_layers == 62 and g.vocab_size == 262144
    m = load_arch("mamba2_13b")
    assert m.ssm_state == 128 and m.num_layers == 48 and m.d_ff == 0
    w = load_arch("whisper_large_v3")
    assert w.is_encoder_decoder and w.d_model == 1280 and w.vocab_size == 51866
