"""Prefill-vs-decode consistency: teacher-forced decode through the KV /
SSM caches must reproduce the full-sequence forward logits. This is the
strongest correctness check on the serving path (ring buffers, absorbed
MLA, recurrent mamba state)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models import transformer as T


@pytest.mark.parametrize("arch", ["qwen2-7b", "qwen3-32b", "minicpm-2b",
                                  "granite-moe-1b-a400m", "mamba2-2.7b",
                                  "deepseek-v3-671b", "jamba-v0.1-52b",
                                  "granite-4.0-h-micro"])
def test_decode_matches_forward(arch, rng, test_spec):
    cfg = reduce_config(get_config(arch), test_spec)
    if cfg.moe is not None:
        # capacity dropping legitimately differs between a 24-token prefill
        # and a 2-token decode batch; use a no-drop factor so the paths are
        # mathematically comparable (inference MoE is usually no-drop)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=float(
                cfg.moe.n_experts)))
    params = T.init_params(cfg, rng, jnp.float32)
    lora = T.init_lora(cfg, rng, rank=2)
    b, s = 2, 12
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab)

    # full forward logits over the sequence
    h, _aux, _np = T.forward_hidden(cfg, params, lora, {"tokens": tokens})
    full_logits = T.logits_from_hidden(cfg, params, h)        # (B,S,V)

    # token-by-token decode with cache
    cache = T.init_cache(cfg, b, s, jnp.float32)
    outs = []
    for t in range(s):
        lg, cache = T.decode_step(cfg, params, lora, tokens[:, t: t + 1],
                                  cache)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)

    # decode masks the vocab padding -> compare the real vocab slice
    np.testing.assert_allclose(
        np.asarray(dec_logits[..., : cfg.vocab]),
        np.asarray(full_logits[..., : cfg.vocab]),
        rtol=2e-3, atol=2e-3)


def test_sliding_window_decode_matches_windowed_forward(rng, test_spec):
    """Ring-buffer decode == full forward with the same sliding window."""
    cfg = reduce_config(get_config("qwen2-7b"), test_spec)
    params = T.init_params(cfg, rng, jnp.float32)
    b, s, w = 2, 10, 4
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab)
    h, _aux, _np2 = T.forward_hidden(cfg, params, None, {"tokens": tokens},
                                     window=w)
    full_logits = T.logits_from_hidden(cfg, params, h)
    cache = T.init_cache(cfg, b, w, jnp.float32)   # capacity == window
    outs = []
    for t in range(s):
        lg, cache = T.decode_step(cfg, params, None, tokens[:, t: t + 1],
                                  cache)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec[..., : cfg.vocab]),
                               np.asarray(full_logits[..., : cfg.vocab]),
                               rtol=2e-3, atol=2e-3)


def test_mrope_reduces_to_rope_for_text():
    """M-RoPE with identical position streams == plain RoPE (Qwen2-VL
    guarantee our vlm config relies on)."""
    from repro.models.layers import (apply_rope, mrope_cos_sin,
                                     rope_cos_sin, text_positions)
    b, s, h, hd = 2, 8, 2, 32
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (b, s, h, hd))
    pos = text_positions(b, s)
    c1, s1 = rope_cos_sin(pos, hd, 1e4)
    pos3 = jnp.broadcast_to(pos[None], (3, b, s))
    c2, s2 = mrope_cos_sin(pos3, (4, 6, 6), hd, 1e4)
    np.testing.assert_allclose(np.asarray(apply_rope(x, c1, s1)),
                               np.asarray(apply_rope(x, c2, s2)),
                               rtol=1e-5, atol=1e-5)
