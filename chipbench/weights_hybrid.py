"""Seeded weights and adapters of the Mamba-2 / attention hybrid, made on
the device by the benchmark, a leaf at a time (one program per leaf, so
that no program holds the float32 draws of every leaf at once).

Each stack (the Mamba layers, the attention layers) has the depth
structure ``chipbench/weights.py`` gives its stack: layer ``l`` of every
leaf sums normal fields shared by its pair (``l // 2``), quad (``l //
4``) and octet (``l // 8``) within the stack and one of its own. Scales
follow the model's own init (``1/sqrt(fan_in)`` for projections and the
depthwise convolution, ``0.02`` for the embedding and the conv bias,
ones for the norms). The Mamba-2 parameters the configuration gives no
value for take Mamba-2's initial ranges (listed under ``assumed`` in the
configuration file): ``A`` in [1, 16] and ``dt`` in [0.001, 0.1]
(log-uniform), each drawn through the normal CDF of a layered field so
that neighbouring layers are alike, ``dt_bias`` the inverse softplus of
``dt``, and ``D`` one. They are float32, as the program keeps them.

The trees use the layout ``repro.models.transformer`` reads for a hybrid
without experts; the harness checks them against ``jax.eval_shape`` of
the program's own init.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtr

from chipbench.weights import LEVELS, OWN, root_key

#: Mamba-2's initial ranges of the decay rate and the step
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _layered(key, n_layers, shape, scale, dtype):
    """``(n_layers, *shape)`` with the depth structure of the module
    docstring, drawn a layer at a time: layer ``l`` sums its own draw and
    the draws of its pair, quad and octet (keyed by ``l // g``, so that
    the layers of a group share them)."""
    keys = jax.random.split(key, len(LEVELS) + 1)

    def layer(i):
        out = math.sqrt(OWN) * jax.random.normal(
            jax.random.fold_in(keys[0], i), shape, jnp.float32)
        for k, (g, share) in zip(keys[1:], LEVELS):
            out = out + math.sqrt(share) * jax.random.normal(
                jax.random.fold_in(k, i // g), shape, jnp.float32)
        return (scale * out).astype(dtype)

    return jax.lax.map(layer, jnp.arange(n_layers))


def mamba_sizes(m: dict):
    """``(d_inner, conv channels, in_proj width)`` of a Mamba-2 layer."""
    din = m["mamba_n_heads"] * m["mamba_d_head"]
    ch = din + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
    return din, ch, din + ch + m["mamba_n_heads"]


def stack_depths(m: dict) -> dict:
    types = m["layer_types"]
    return {"mamba_mlp": types.count("mamba"),
            "attn_mlp": types.count("attention")}


def _uniform(key, n, shape):
    """A layered field pushed through the normal CDF: uniform on (0, 1)
    at each element, alike in neighbouring layers."""
    return ndtr(_layered(key, n, shape, 1.0, jnp.float32))


def _stacks(m: dict, key):
    dtype = jnp.dtype(m["dtype"])
    d, f = m["hidden_size"], m["shared_intermediate_size"]
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    mh, k = m["mamba_n_heads"], m["mamba_d_conv"]
    din, ch, width = mamba_sizes(m)
    depth = stack_depths(m)
    keys = iter(jax.random.split(key, 32))

    def mat(n, shape, fan):
        return _layered(next(keys), n, shape, 1.0 / math.sqrt(fan), dtype)

    def common(n):
        return {"ln1": jnp.ones((n, d), dtype), "ln2": jnp.ones((n, d), dtype),
                "ffn": {"wg": mat(n, (d, f), d), "wu": mat(n, (d, f), d),
                        "wd": mat(n, (f, d), f)}}

    n = depth["mamba_mlp"]
    lo, hi = A_RANGE
    a = lo + (hi - lo) * _uniform(next(keys), n, (mh,))
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * _uniform(next(keys), n, (mh,)))
    mamba = dict(common(n), mixer={
        "in_proj": mat(n, (d, width), d),
        "conv_w": mat(n, (k, ch), k),
        "conv_b": _layered(next(keys), n, (ch,), 0.02, dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(a),
        "D": jnp.ones((n, mh), jnp.float32),
        "out_norm": jnp.ones((n, din), dtype),
        "out_proj": mat(n, (din, d), din)})
    n = depth["attn_mlp"]
    attn = dict(common(n), mixer={
        "wq": mat(n, (d, h * hd), d), "wk": mat(n, (d, kv * hd), d),
        "wv": mat(n, (d, kv * hd), d), "wo": mat(n, (h * hd, d), h * hd)})
    return {"mamba_mlp": mamba, "attn_mlp": attn}


def _base(m: dict, key):
    dtype = jnp.dtype(m["dtype"])
    ke, kb = jax.random.split(key)
    d, v = m["hidden_size"], m["vocab_size"]
    vp = -(-v // 128) * 128
    embed = 0.02 * jax.random.normal(ke, (vp, d), jnp.float32)
    embed = jnp.where(jnp.arange(vp)[:, None] < v, embed, 0.0)
    return {"embed": embed.astype(dtype),
            "final_norm": jnp.ones((d,), dtype),
            "blocks": _stacks(m, kb)}


def base_shapes(m: dict):
    """The base's tree of ``ShapeDtypeStruct``s."""
    return jax.eval_shape(lambda k: _base(m, k), root_key(0, "base"))


def base_params(m: dict, seed: int, shardings=None):
    """The base model for ``seed``, made on the device a leaf at a time
    (the hybrid's cell runs on one chip)."""
    if shardings is not None:
        raise ValueError("the hybrid's weights are made on one device")
    return _base(m, root_key(seed, "base"))


def lora_targets(m: dict) -> dict:
    """stack -> adapted projection -> (din, dout): the Mamba layers'
    ``in_proj`` and ``out_proj``, the attention layers' q and v."""
    d, h, kv, hd = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"], m["head_dim"]
    din, _, width = mamba_sizes(m)
    return {"mamba_mlp": {"in_proj": (d, width), "out_proj": (din, d)},
            "attn_mlp": {"wq": (d, h * hd), "wv": (d, kv * hd)}}


def _lora(m: dict, key, rank: int):
    depth = stack_depths(m)
    out = {}
    for i, (stack, targets) in enumerate(sorted(lora_targets(m).items())):
        n = depth[stack]
        out[stack] = {}
        for j, (name, (din, dout)) in enumerate(sorted(targets.items())):
            ka = jax.random.fold_in(jax.random.fold_in(key, i), j)
            out[stack][name] = {
                "a": jax.random.normal(ka, (n, din, rank), jnp.float32)
                / math.sqrt(din),
                "b": jnp.zeros((n, rank, dout), jnp.float32)}
    return out


def init_lora(m: dict, seed: int, rank: int, shardings=None):
    """The adapter a federated run starts from: ``a`` random, ``b`` zero
    (standard LoRA init)."""
    if shardings is not None:
        raise ValueError("the hybrid's adapters are made on one device")
    return jax.jit(lambda k: _lora(m, k, rank))(root_key(seed, "lora"))

