"""The hybrid block and Granite's options in the program: where the
attention layers go, which stacks a hybrid has, and that the options are
no-ops at their defaults.

The programs of a model that uses neither NoPE nor Granite's multipliers
lower to the same text as before those options existed:
the hashes below are of qwen2-7b-l4's round program (DevFT's fused
round of 4 layers, two clients of 2 x 512 tokens) and eval program as
the program lowered them before the options were added. A change that
means to alter those programs updates the hashes with it.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduce_config
from repro.core.stages import allocate_stack_capacities
from repro.models import transformer as T

#: sha256 of the lowered text (round program, eval program) per backend
QWEN2_L4_HLO = {
    "reference": (
        "033945edb565d359dbb603ff323eae8427e204e255f2e092d281d5449b1fd2fa",
        "ba65d9c0e6d52442dc40f50a0f4a35abb3a9b230246dda302ceed0707d2f6408"),
    "pallas": (
        "9ef236e84aef96e51de05c8130af37a10fc969fd45f316369cbdba5461c4c189",
        "aa6e1771b55730e460c36668045ab5f0c441538005becc87c0024bec12394eeb"),
}


def _attention_at(cfg, sizes=None):
    return [i for i, (name, _) in enumerate(T.execution_order(cfg, sizes))
            if name == "attn_mlp"]


def test_attention_sits_at_the_configs_period_and_offset():
    granite = get_config("granite-4.0-h-micro")
    assert _attention_at(granite) == [5, 15, 25, 35]
    jamba = get_config("jamba-v0.1-52b")
    assert _attention_at(jamba) == [4, 12, 20, 28]
    # a DevFT submodel: one attention layer per period of total / n
    # layers, at the offset scaled to that period
    assert _attention_at(granite, {"mamba_mlp": 9, "attn_mlp": 1}) == [5]
    assert _attention_at(granite, {"mamba_mlp": 18, "attn_mlp": 2}) == \
        [5, 15]
    assert _attention_at(granite, {"mamba_mlp": 4, "attn_mlp": 1}) == [2]
    # an offset other than half the period is kept, not replaced by it
    other = dataclasses.replace(granite, attn_offset=2)
    assert _attention_at(other) == [2, 12, 22, 32]


def test_a_hybrid_without_experts_has_no_moe_stack():
    granite = get_config("granite-4.0-h-micro")
    assert granite.layer_stacks() == [("mamba_mlp", 36), ("attn_mlp", 4)]
    params = jax.eval_shape(lambda: T.init_params(
        reduce_config(granite), jax.random.PRNGKey(0)))
    assert set(params["blocks"]) == {"mamba_mlp", "attn_mlp"}
    assert allocate_stack_capacities(dict(granite.layer_stacks()), 10) == \
        {"mamba_mlp": 9, "attn_mlp": 1}
    jamba = get_config("jamba-v0.1-52b")
    assert jamba.layer_stacks() == [("mamba_mlp", 12), ("mamba_moe", 16),
                                    ("attn_mlp", 4)]


def test_runs_of_one_layer_kind_scan_as_the_unrolled_loop(monkeypatch,
                                                           rng, test_spec):
    """The hybrid's runs of consecutive layers of one stack are scans
    over layer indices; unrolled, the same layers give the same loss."""
    cfg = dataclasses.replace(reduce_config(get_config(
        "granite-4.0-h-micro"), test_spec), dtype="float32")
    params = T.init_params(cfg, rng, jnp.float32)
    lora = T.init_lora(cfg, rng, rank=2)
    tok = jax.random.randint(rng, (2, 16), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": tok}
    scanned = T.loss_fn(cfg, params, lora, batch)[0]
    monkeypatch.setattr(T, "FORCE_UNROLL", True)
    unrolled = T.loss_fn(cfg, params, lora, batch)[0]
    assert jnp.allclose(scanned, unrolled, rtol=1e-6)


@pytest.mark.parametrize("backend", sorted(QWEN2_L4_HLO))
def test_identity_defaults_leave_qwen2_programs_unchanged(backend):
    from repro.federated.methods import make_strategy
    from repro.federated.simulator import FedConfig, make_round_program

    cfg = dataclasses.replace(get_config("qwen2-7b"), n_layers=4,
                              kernel_backend=backend)
    assert (cfg.nope, cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == \
        (False, 1.0, None, 1.0, 1.0)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: T.init_params(cfg, key, jnp.bfloat16))
    lora = jax.eval_shape(lambda: T.init_lora(cfg, key, rank=32))
    fed = FedConfig(method="devft", n_clients=20, sample_frac=0.1,
                    k_local=10, local_batch=2, seq=512, lora_rank=32,
                    rounds=4, n_stages=2)
    round_fn, _ = make_round_program(make_strategy("devft", cfg, fed), None,
                                     cfg, 2, hetero=False)
    tok = jax.ShapeDtypeStruct((2, 10, 2, 512), jnp.int32)
    round_text = jax.jit(round_fn).lower(
        params, lora, {"tokens": tok, "labels": tok},
        jax.ShapeDtypeStruct((), jnp.float32)).as_text()
    ev = jax.ShapeDtypeStruct((16, 512), jnp.int32)
    eval_text = jax.jit(lambda p, lo, b: T.loss_fn(cfg, p, lo, b)[1]["loss"]) \
        .lower(params, lora, {"tokens": ev, "labels": ev}).as_text()
    got = tuple(hashlib.sha256(t.encode()).hexdigest()
                for t in (round_text, eval_text))
    assert got == QWEN2_L4_HLO[backend]
