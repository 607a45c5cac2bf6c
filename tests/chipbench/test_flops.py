"""The benchmark's operation counts against XLA's own count of the
program's reference path, at a tiny size on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops


def _model(moe: bool):
    m = {"hidden_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 128, "vocab_size": 512,
         "num_hidden_layers": 2}
    if moe:
        m.update(num_local_experts=4, num_experts_per_tok=2,
                 intermediate_size=256)
    else:
        m.update(intermediate_size=1024)
    return m


def _program_cfg(m, moe):
    from repro.configs import get_config

    cfg = get_config("granite-moe-1b-a400m" if moe else "qwen2-7b")
    kw = dict(n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
              n_heads=m["num_attention_heads"],
              n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
              d_ff=m["intermediate_size"], vocab=m["vocab_size"],
              qkv_bias=False, kernel_backend="reference", dtype="float32")
    if moe:
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=2, d_ff_expert=256,
            capacity_factor=1.0)
    return dataclasses.replace(cfg, **kw)


@pytest.mark.parametrize("moe", [False, True])
def test_forward_count_matches_cost_analysis(moe, monkeypatch):
    from repro.models import transformer as T

    monkeypatch.setattr(T, "FORCE_UNROLL", True)   # count every layer
    m = _model(moe)
    cfg = _program_cfg(m, moe)
    b, s = 2, 64
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: T.init_params(cfg, key, jnp.float32))
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}

    def fwd(p, bt):
        h, _, _ = T.forward_hidden(cfg, p, None, bt)
        return T.logits_from_hidden(cfg, p, h)

    cost = jax.jit(fwd).lower(params, batch).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    # the reference path scores every key (the mask comes after), and
    # the MoE path multiplies every capacity slot: with capacity factor 1
    # the slots number exactly the routed pairs
    want = b * s * flops.forward_flops(m, m["num_hidden_layers"], kv_len=s)
    assert cost["flops"] == pytest.approx(want, rel=0.03)


def test_train_count_has_no_weight_gradient():
    m = _model(False)
    fwd = flops.forward_flops(m, 2, 257.0 / 2, rank=8)
    train = flops.train_flops(m, 2, 256, rank=8)
    # a frozen base: the backward pass is the activation gradients (the
    # forward's matmuls once more, attention and adapters twice), so a
    # training token costs about two forwards, not three
    assert 2.0 * fwd < train < 2.2 * fwd


def test_kernel_counts():
    f, b = flops.lora_matmul(rows=8, din=4, dout=6, rank=2)
    assert f == 2 * 8 * (4 * 6 + 4 * 2 + 2 * 6)
    assert b == 2 * (8 * 4 + 4 * 6 + 8 * 6 + 4 * 2 + 2 * 6)
    f, b = flops.flash_decode(kv_tokens=100, n_seqs=3, n_heads=4,
                              n_kv_heads=2, head_dim=8)
    assert f == 4 * 4 * 8 * 100
    assert b == 2 * (2 * 2 * 8 * 100 + 2 * 3 * 4 * 8)
