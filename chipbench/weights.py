"""Seeded weights and adapters, made on the device by the benchmark.

The base is drawn in the dtype it is served in: on one device in one
jitted call; on a mesh leaf by leaf in the shardings the program places
it with, a layer at a time (``base_params``). Its layer stacks carry a
depth structure: layer ``l`` of every leaf is a sum of independent
normal fields shared by the layers of its pair
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
from jax.extend.random import threefry2x32_p

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


def _base_leaves(m: dict):
    """``[(path, key index or None, make(key) -> array, layered)]`` of the
    base, in the order the keys are drawn: index ``i`` is the ``i``-th key
    of ``jax.random.split(root, 64)``; leaves of ones take none.
    ``layered`` is ``_layered``'s ``(n_layers, shape, scale, dtype)`` for
    the leaves it makes, else None."""
    dtype = jnp.dtype(m["dtype"])
    d, n_layers, v, vp = m["hidden_size"], m["num_hidden_layers"], \
        m["vocab_size"], padded_vocab(m)
    out = []

    def add(path, make, keyed=True, layered=None):
        n = sum(leaf[1] is not None for leaf in out)
        out.append((path, n if keyed else None, make, layered))

    def embed(k):
        e = 0.02 * jax.random.normal(k, (vp, d), jnp.float32)
        return jnp.where(jnp.arange(vp)[:, None] < v, e, 0.0).astype(dtype)

    add(("embed",), embed)
    add(("final_norm",), lambda _: jnp.ones((d,), dtype), keyed=False)
    if not m.get("tie_word_embeddings"):
        add(("lm_head",), lambda k: (jax.random.normal(
            k, (d, vp), jnp.float32) / math.sqrt(d)).astype(dtype))

    def walk(tree, path):
        for name, spec in tree.items():
            if isinstance(spec, dict):
                walk(spec, path + (name,))
                continue
            shape, fan = spec
            if fan is None:
                add(path + (name,), lambda _, shape=shape: jnp.ones(
                    (n_layers,) + shape, dtype), keyed=False)
                continue
            scale = 0.02 if fan == "bias" else 1.0 / math.sqrt(fan)
            # the router is kept in float32, as the program keeps it
            dt = jnp.float32 if name == "router" else dtype
            spec = (n_layers, shape, scale, dt)
            add(path + (name,), lambda k, spec=spec: _layered(k, *spec),
                layered=spec)

    walk(block_shapes(m), ("blocks", "layers"))
    return out


def _nest(items):
    """``[(path, value)]`` -> nested dicts."""
    tree = {}
    for path, val in items:
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = val
    return tree


def _base(m: dict, key):
    keys = jax.random.split(key, 64)
    return _nest([(path, make(None if i is None else keys[i]))
                  for path, i, make, _ in _base_leaves(m)])


def base_shapes(m: dict):
    """The base's tree of ``ShapeDtypeStruct``s."""
    return jax.eval_shape(lambda k: _base(m, k), root_key(0, "base"))


def base_params(m: dict, seed: int, shardings=None):
    """The base model for ``seed``, made on the device. Without
    ``shardings``, in one call. With a tree of shardings (a mesh), each
    leaf is made in its sharding with the key it gets in the one call,
    and a layered leaf one layer at a time, so that only one layer's
    float32 fields are alive. A seed gives the same bits however the
    leaves are sharded (on one device too); the one call adds the same
    fields in a program that XLA fuses otherwise, and a few elements in
    ten thousand round the other way in their last bit."""
    key = root_key(seed, "base")
    if shardings is None:
        return jax.jit(lambda k: _base(m, k))(key)
    keys = jax.random.split(key, 64)
    sh = dict(jax.tree_util.tree_flatten_with_path(shardings)[0])
    out = []
    for path, i, make, layered in _base_leaves(m):
        s = sh[tuple(jax.tree_util.DictKey(k) for k in path)]
        if layered is None:
            x = jax.jit(make, out_shardings=s)(None if i is None else keys[i])
        else:
            x = _layered_in_place(keys[i], *layered, s)
        out.append((path, x))
    return _nest(out)


def _layered_in_place(key, n_layers, shape, scale, dtype, sharding):
    """``_layered(key, n_layers, shape, scale, dtype)`` in ``sharding``,
    made one layer at a time into a donated stack: layer ``l`` sums row
    ``l`` of the own field and row ``l // g`` of each level's."""
    keys = jax.random.split(key, len(LEVELS) + 1)
    per = math.prod(shape)
    step = _layer_step(shape, scale, dtype, sharding)
    out = jax.jit(lambda: jnp.zeros((n_layers,) + shape, dtype),
                  out_shardings=sharding)()
    for layer in range(n_layers):
        first = [layer * per] + [layer // g * per for g, _ in LEVELS]
        starts = np.array([[i >> 32, i & 0xFFFFFFFF] for i in first],
                          np.uint32)
        out = step(out, keys, starts, layer)
    return out


def _layer_step(shape, scale, dtype, sharding):
    """``(stack, keys, starts, layer) -> stack`` with that layer made."""
    def set_layer(out, keys, starts, layer):
        x = math.sqrt(OWN) * _normal_rows(keys[0], shape, starts[0])
        for j, (_, share) in enumerate(LEVELS):
            x = x + math.sqrt(share) * _normal_rows(keys[j + 1], shape,
                                                    starts[j + 1])
        return jax.lax.dynamic_update_index_in_dim(
            out, (scale * x).astype(dtype), layer, 0)

    return jax.jit(set_layer, out_shardings=sharding, donate_argnums=0)


def _normal_rows(key, shape: tuple, start):
    """``prod(shape)`` consecutive elements of a float32
    ``jax.random.normal(key, ...)`` of any larger shape, from element
    ``start`` (its index as two 32-bit words, high and low) on, shaped
    ``shape`` (fewer than 2**32 elements). Under the partitionable threefry (JAX's default,
    ``jax_threefry_partitionable``) element ``i`` of such an array hashes
    the two words of ``i``; the hash's two words are xor-ed into its bits,
    which become a uniform float on [nextafter(-1, 0), 1) and then
    ``sqrt(2) * erfinv`` of it."""
    u32, f32 = jnp.uint32, jnp.float32
    idx = sum(jax.lax.broadcasted_iota(u32, shape, d)
              * u32(math.prod(shape[d + 1:])) for d in range(len(shape)))
    lo = start[1] + idx
    hi = start[0] + (lo < start[1]).astype(u32)      # the carry
    b1, b2 = threefry2x32_p.bind(key[0], key[1], hi, lo)
    fbits = jax.lax.shift_right_logical(b1 ^ b2, u32(9)) \
        | u32(np.array(1.0, np.float32).view(np.uint32))
    floats = jax.lax.bitcast_convert_type(fbits, f32) - jnp.array(1.0, f32)
    minval = jnp.asarray(np.nextafter(np.float32(-1), np.float32(0)), f32)
    maxval = jnp.asarray(1.0, f32)
    u = jax.lax.max(minval, floats * (maxval - minval) + minval)
    return jax.lax.mul(np.array(np.sqrt(2), np.float32), jax.lax.erf_inv(u))


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


def init_lora(m: dict, seed: int, rank: int, shardings=None):
    """The adapter a federated run starts from: ``a`` random, ``b`` zero
    (standard LoRA init); made in ``shardings`` where given."""
    place = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(lambda k: _lora(m, k, rank, 0.0),
                   **place)(root_key(seed, "lora"))


def tenant_adapters(m: dict, seed: int, rank: int, n: int,
                    b_scale: float = 0.05):
    """``n`` trained-looking adapters (both factors random, so each moves
    the logits), stacked on a leading ``(n, ...)`` axis in one call."""
    keys = jax.random.split(root_key(seed, "adapters"), n)
    return jax.jit(jax.vmap(lambda k: _lora(m, k, rank, b_scale)))(keys)
