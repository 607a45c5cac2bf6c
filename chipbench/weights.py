"""Seeded weights and adapters, made on the device by the benchmark.

The base is drawn in one jitted call, in the dtype it is served in. Its
layer stacks carry a depth structure: layer ``l`` of every leaf is a sum
of independent normal fields shared by the layers of its pair
(``l // 2``), its quad (``l // 4``) and its octet (``l // 8``), plus one
of its own, so neighbouring layers are alike as in a pretrained model.
DevFT's grouping (spectral clustering of layer similarity) is then a
well-posed decision at every stage capacity rather than a tie broken by
rounding. Scales follow the model's own init: ``1/sqrt(fan_in)`` for
projections, ``0.02`` for the embedding, ones for the norms.

The trees use the layout ``repro.models.transformer`` reads; the harness
checks them against ``jax.eval_shape`` of the program's own init.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: share of each leaf's variance held in common by a layer's pair,
#: quad and octet, and its own share (sums to 1)
LEVELS = ((2, 0.2), (4, 0.2), (8, 0.2))
OWN = 0.4


def root_key(seed: int, label: str):
    """A PRNG key for ``(seed, label)``; any size of integer seed."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), int(seed) >> 64,
                                 int.from_bytes(label.encode(), "big")])
    return jax.random.PRNGKey(int(ss.generate_state(1, np.uint32)[0]))


def _layered(key, n_layers, shape, scale, dtype):
    """``(n_layers, *shape)`` with the depth structure of the module
    docstring."""
    keys = jax.random.split(key, len(LEVELS) + 1)
    out = math.sqrt(OWN) * jax.random.normal(keys[0], (n_layers,) + shape,
                                             jnp.float32)
    for k, (g, share) in zip(keys[1:], LEVELS):
        z = jax.random.normal(k, (-(-n_layers // g),) + shape, jnp.float32)
        out = out + math.sqrt(share) * z[np.arange(n_layers) // g]
    return (scale * out).astype(dtype)


def block_shapes(m: dict) -> dict:
    """``name -> (shape, fan_in or None for ones, dtype tag)`` of one
    layer, in the program's layout."""
    d, h, kv, hd = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"], m["head_dim"]
    mixer = {"wq": ((d, h * hd), d), "wk": ((d, kv * hd), d),
             "wv": ((d, kv * hd), d), "wo": ((h * hd, d), h * hd)}
    if m.get("qkv_bias"):
        mixer.update({"bq": ((h * hd,), "bias"), "bk": ((kv * hd,), "bias"),
                      "bv": ((kv * hd,), "bias")})
    if m.get("num_local_experts"):
        e, f = m["num_local_experts"], m["intermediate_size"]
        ffn = {"router": ((d, e), d), "wg": ((e, d, f), d),
               "wu": ((e, d, f), d), "wd": ((e, f, d), f)}
    else:
        f = m["intermediate_size"]
        ffn = {"wg": ((d, f), d), "wu": ((d, f), d), "wd": ((f, d), f)}
    return {"ln1": ((d,), None), "ln2": ((d,), None), "mixer": mixer,
            "ffn": ffn}


def padded_vocab(m: dict) -> int:
    return -(-m["vocab_size"] // 128) * 128


def _base(m: dict, key):
    dtype = jnp.dtype(m["dtype"])
    d, n_layers, v, vp = m["hidden_size"], m["num_hidden_layers"], \
        m["vocab_size"], padded_vocab(m)
    keys = iter(jax.random.split(key, 64))
    embed = 0.02 * jax.random.normal(next(keys), (vp, d), jnp.float32)
    embed = jnp.where(jnp.arange(vp)[:, None] < v, embed, 0.0)
    params = {"embed": embed.astype(dtype), "final_norm": jnp.ones((d,), dtype)}
    if not m.get("tie_word_embeddings"):
        params["lm_head"] = (jax.random.normal(next(keys), (d, vp),
                                               jnp.float32)
                             / math.sqrt(d)).astype(dtype)

    def leaf(spec, name):
        shape, fan = spec
        if fan is None:
            return jnp.ones((n_layers,) + shape, dtype)
        scale = 0.02 if fan == "bias" else 1.0 / math.sqrt(fan)
        # the router is kept in float32, as the program keeps it
        dt = jnp.float32 if name == "router" else dtype
        return _layered(next(keys), n_layers, shape, scale, dt)

    def build(tree):
        return {k: build(v) if isinstance(v, dict) else leaf(v, k)
                for k, v in tree.items()}

    params["blocks"] = {"layers": build(block_shapes(m))}
    return params


def base_params(m: dict, seed: int):
    """The base model for ``seed``, made on the device in one call."""
    return jax.jit(lambda k: _base(m, k))(root_key(seed, "base"))


def lora_targets(m: dict) -> dict:
    d, h, kv, hd = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"], m["head_dim"]
    return {"wq": (d, h * hd), "wv": (d, kv * hd)}


def _lora(m: dict, key, rank: int, b_scale: float):
    n = m["num_hidden_layers"]
    out = {}
    for j, (name, (din, dout)) in enumerate(sorted(lora_targets(m).items())):
        ka, kb = jax.random.split(jax.random.fold_in(key, j))
        a = jax.random.normal(ka, (n, din, rank), jnp.float32) \
            / math.sqrt(din)
        b = b_scale * jax.random.normal(kb, (n, rank, dout), jnp.float32)
        out[name] = {"a": a, "b": b}
    return {"layers": out}


def init_lora(m: dict, seed: int, rank: int):
    """The adapter a federated run starts from: ``a`` random, ``b`` zero
    (standard LoRA init)."""
    return jax.jit(lambda k: _lora(m, k, rank, 0.0))(root_key(seed, "lora"))


def tenant_adapters(m: dict, seed: int, rank: int, n: int,
                    b_scale: float = 0.05):
    """``n`` trained-looking adapters (both factors random, so each moves
    the logits), stacked on a leading ``(n, ...)`` axis in one call."""
    keys = jax.random.split(root_key(seed, "adapters"), n)
    return jax.jit(jax.vmap(lambda k: _lora(m, k, rank, b_scale)))(keys)
