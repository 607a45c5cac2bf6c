"""Per-architecture smoke tests (assignment requirement).

Each assigned arch is instantiated as a REDUCED variant of the same
family (2 layers / d_model<=512 / <=4 experts) and runs one forward +
one LoRA train step + two decode steps on CPU, asserting output shapes
and the absence of NaNs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_ARCH_IDS, get_config, reduce_config
from repro.models import transformer as T
from repro.optim.adamw import adamw_update, init_adamw


def _batch(cfg, key, b=2, s=32):
    batch = {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.fold_in(key, 1), (b, s), 0,
                                     cfg.vocab),
    }
    if cfg.frontend == "vision":
        batch["vision_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2), (b, cfg.n_frontend_tokens,
                                         cfg.d_model))
    if cfg.frontend == "audio":
        batch["audio_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 3), (b, cfg.n_frontend_tokens,
                                         cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ALL_ARCH_IDS)
def test_reduced_forward_train_decode(arch, rng, test_spec):
    cfg = reduce_config(get_config(arch), test_spec)
    # a hybrid keeps one whole interleave period (Granite 4.0-H: 10)
    assert cfg.d_model <= 512 and cfg.n_layers <= max(8, cfg.attn_period)
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    params = T.init_params(cfg, rng, jnp.float32)
    lora = T.init_lora(cfg, rng, rank=4)
    batch = _batch(cfg, rng)

    # ---- forward + shapes ------------------------------------------
    loss, metrics = T.loss_fn(cfg, params, lora, batch)
    assert jnp.isfinite(loss), arch
    assert jnp.isfinite(metrics["acc"])

    # ---- one LoRA train step (grads only wrt lora) ------------------
    def lfn(lo):
        return T.loss_fn(cfg, params, lo, batch)

    (_t, _m), grads = jax.value_and_grad(lfn, has_aux=True)(lora)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    assert jnp.isfinite(gnorm) and gnorm > 0, arch
    opt = init_adamw(lora)
    lora2, _ = adamw_update(grads, opt, lora, 1e-3)
    loss2, _ = T.loss_fn(cfg, params, lora2, batch)
    assert jnp.isfinite(loss2)

    # ---- decode ------------------------------------------------------
    cache = T.init_cache(cfg, 2, 16, jnp.float32)
    tok = jnp.zeros((2, 1), jnp.int32)
    logits, cache = T.decode_step(cfg, params, lora, tok, cache)
    assert logits.shape == (2, 1, cfg.padded_vocab)
    assert jnp.isfinite(logits).all(), arch
    logits2, cache = T.decode_step(cfg, params, lora, tok, cache)
    assert jnp.isfinite(logits2).all()
    assert int(cache["pos"][0]) == 2

    # vocab padding must never win greedy decode
    assert int(jnp.argmax(logits[0, 0])) < cfg.vocab


@pytest.mark.parametrize("arch", ["qwen2-7b", "qwen3-32b", "mamba2-2.7b",
                                  "deepseek-v3-671b"])
def test_full_config_eval_shape_only(arch):
    """Full configs are exercised via eval_shape (no allocation)."""
    import math
    cfg = get_config(arch)
    p = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0))
    n = sum(math.prod(l.shape) for l in jax.tree.leaves(p))
    lo = jax.eval_shape(lambda k: T.init_lora(cfg, k, 32),
                        jax.random.PRNGKey(0))
    n_lo = sum(math.prod(l.shape) for l in jax.tree.leaves(lo))
    assert n > 1e9, (arch, n)          # these really are LLM-scale trees
    assert n_lo < n * 0.02             # LoRA is a tiny fraction
