"""The Pallas kernels compile for a TPU v5e at real model widths.

Each test lowers one kernel through its registered ``pallas`` entry with
``interpret=False`` for one chip of a *described* ``v5e:2x2`` topology
(no chip needed: the TPU compiler is installed with jax) and asserts the
compiled program holds a ``tpu_custom_call`` — the kernel itself, not a
fallback. Interpret-mode tests cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, VMEM overflow. The last
test pins the instruction name each kernel has in the programs that run
it, which is the name a device trace shows.

Widths: qwen2-7b (d_model 3584, 28 query / 4 KV heads of 128, d_ff
18944) for the attention, LoRA and decode kernels; mamba2-2.7b (80 heads
of 64, state 128, chunk 256) for the SSD scan; granite-moe-1b-a400m
(32 experts, d_model 1024, expert d_ff 512) for the grouped GEMM.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and the test
workers all import this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.models import transformer as T

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without the chip: keep it off here
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile_text(one_chip, name, shapes, **kwargs):
    kernel = dispatch.get_kernel(name, "pallas")
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    fn = jax.jit(lambda *a: kernel(*a, interpret=False, **kwargs))
    return fn.lower(*args).compile().as_text()


def test_flash_attention_compiles(one_chip):
    text = _compile_text(one_chip, "flash_attention",
                         [((1, 4096, 28, 128), BF16),
                          ((1, 4096, 4, 128), BF16),
                          ((1, 4096, 4, 128), BF16)], causal=True)
    assert "tpu_custom_call" in text


def test_lora_matmul_compiles(one_chip):
    text = _compile_text(one_chip, "lora_matmul",
                         [((4096, 3584), BF16), ((3584, 18944), BF16),
                          ((3584, 32), BF16), ((32, 18944), BF16)],
                         scaling=2.0)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,n", [(1024, 3584), (8192, 512)])
def test_lora_matmul_compiles_with_derived_tiles(one_chip, m, n):
    """The tiles ``lora_layout`` derives at the round cell's shapes (one
    client's q projection, the eval's v projection) fit the chip."""
    text = _compile_text(one_chip, "lora_matmul",
                         [((m, 3584), BF16), ((3584, n), BF16),
                          ((3584, 32), BF16), ((32, n), BF16)],
                         scaling=2.0)
    assert "tpu_custom_call" in text


def test_flash_decode_compiles(one_chip):
    kernel = dispatch.get_kernel("flash_decode", "pallas")
    q = jax.ShapeDtypeStruct((8, 1, 28, 128), BF16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 4096, 4, 128), BF16, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda q_, k_, v_, n_: kernel(q_, k_, v_, kv_valid_len=n_,
                                               interpret=False))
    text = fn.lower(q, kv, kv, valid).compile().as_text()
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles(one_chip):
    b, s, h, p, n = 1, 2048, 80, 64, 128
    text = _compile_text(one_chip, "ssd_scan",
                         [((b, s, h, p), BF16), ((b, s, h), BF16),
                          ((h,), jnp.float32), ((b, s, 1, n), BF16),
                          ((b, s, 1, n), BF16), ((h,), jnp.float32)],
                         chunk=256)
    assert "tpu_custom_call" in text


def test_moe_expert_ffn_compiles(one_chip):
    e, c, d, ff = 32, 1280, 1024, 512
    text = _compile_text(one_chip, "moe_expert_ffn",
                         [((e, c, d), BF16), ((e, d, ff), BF16),
                          ((e, d, ff), BF16), ((e, ff, d), BF16)])
    assert "tpu_custom_call" in text


def _kernel_names(text):
    """The instruction names of the compiled program's Pallas kernels,
    without their ``.N`` suffix: the names the device trace shows."""
    return {re.match(r"(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", line.strip())[1]
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


def test_kernel_names_in_the_round_and_decode_programs(one_chip,
                                                       monkeypatch):
    """Each kernel keeps its own name inside the programs that run it
    (a local training step of an MoE model, vmapped over two clients,
    and the engine's decode step); an unnamed kernel would show as the
    call around it (``closed_call``)."""
    from repro.analysis.contracts.serving import _step_fn
    from repro.configs import get_config
    from repro.federated.client import make_local_train

    # compile the kernels for the chip, not for the interpreter
    monkeypatch.setattr(dispatch, "interpret_default",
                        lambda platform=None: False)
    cfg = get_config("granite-moe-1b-a400m")
    cfg = dataclasses.replace(
        cfg, n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
        head_dim=64, d_ff=256, vocab=1024, kernel_backend="pallas",
        moe=dataclasses.replace(cfg.moe, n_experts=4, top_k=2,
                                d_ff_expert=256))

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    key = jax.random.PRNGKey(0)
    params = shapes(jax.eval_shape(lambda: T.init_params(cfg, key, BF16)))
    lora = shapes(jax.eval_shape(lambda: T.init_lora(cfg, key, rank=8)))
    tok = jax.ShapeDtypeStruct((2, 2, 2, 128), jnp.int32, sharding=one_chip)
    local = make_local_train(cfg)
    train = jax.jit(lambda p, l, b: jax.vmap(
        lambda bt: local(p, l, bt, 1e-4))(b))
    text = train.lower(params, lora, {"tokens": tok, "labels": tok}) \
        .compile().as_text()
    assert _kernel_names(text) == {"flash_attention", "lora_matmul",
                                   "moe_expert_ffn"}

    n = 4
    cache = shapes(jax.eval_shape(lambda: T.init_cache(cfg, n, 256, BF16)))
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    text = jax.jit(_step_fn(cfg, multi=False)).lower(
        params, lora, i32,
        jax.ShapeDtypeStruct((n, 1), jnp.int32, sharding=one_chip), cache,
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)) \
        .compile().as_text()
    assert _kernel_names(text) == {"flash_decode", "moe_expert_ffn"}


def test_kernel_names_in_the_hybrid_round_program(one_chip, monkeypatch):
    """The hybrid cell's local training step at its widths (Granite
    4.0-H: Mamba-2 of 64 heads of 64, state 128, chunk 256; NoPE GQA of
    32/8 heads of 64; one whole period of 10 layers, each rematerialised;
    two clients of 2 x 512 tokens, vmapped as the round program runs
    them) holds the SSD kernel under its own name, ``ssd_scan``, beside
    ``lora_matmul`` and ``flash_attention``."""
    from repro.configs import get_config
    from repro.federated.client import make_local_train

    monkeypatch.setattr(dispatch, "interpret_default",
                        lambda platform=None: False)
    cfg = dataclasses.replace(get_config("granite-4.0-h-micro"), n_layers=10,
                              kernel_backend="pallas")

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    key = jax.random.PRNGKey(0)
    params = shapes(jax.eval_shape(lambda: T.init_params(cfg, key, BF16)))
    lora = shapes(jax.eval_shape(lambda: T.init_lora(cfg, key, rank=32)))
    tok = jax.ShapeDtypeStruct((2, 1, 2, 512), jnp.int32, sharding=one_chip)
    local = make_local_train(cfg, remat=True)
    train = jax.jit(lambda p, l, b: jax.vmap(
        lambda bt: local(p, l, bt, 1e-4))(b))
    text = train.lower(params, lora, {"tokens": tok, "labels": tok}) \
        .compile().as_text()
    assert _kernel_names(text) == {"flash_attention", "lora_matmul",
                                   "ssd_scan"}
