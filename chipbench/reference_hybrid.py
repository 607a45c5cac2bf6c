"""Plain reference of the Mamba-2 / attention hybrid (Granite 4.0-H),
written from the configuration file, the Mamba-2 paper and Granite's
published equations, importing nothing of the program.

Everything runs in float32 with ``highest`` matmul precision; weights
stored in a lower precision are upcast a layer at a time. Training steps
run the model a period of layers at a time (``adamw_steps``): each
period's program is compiled once for every capacity of a schedule, and
its backward runs its forward again, each layer rematerialised, so that
the gradients of the whole model fit. The model (``layer_types`` gives the order of the two kinds
of layer):

- embeddings times ``embedding_multiplier``;
- pre-norm residual blocks, ``x + residual_multiplier * mixer(norm(x))``
  then ``x + residual_multiplier * mlp(norm(x))``, RMSNorm with
  ``rms_norm_eps``, a SwiGLU MLP of ``shared_intermediate_size`` in
  every layer;
- attention layers: grouped-query attention with no rotary embedding
  (``position_embedding_type`` "nope"), scores scaled by
  ``attention_multiplier``, no bias, LoRA on the query and value
  projections;
- Mamba-2 layers: ``in_proj`` (with LoRA) split into ``[z, x, B, C,
  dt]``, a causal depthwise convolution with bias over ``[x, B, C]``
  and SiLU, ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, the
  state-space dual (SSD) in its quadratic form over the whole sequence,

      y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s
            + D x_t,

  the gated RMSNorm ``norm(y * silu(z))`` and ``out_proj`` (with LoRA);
- a final norm and the tied embedding as the output head, logits divided
  by ``logits_scaling``.

The quadratic form is independent of the program's chunked algorithm
(chunks, carried state, kernel); ``ssd_recurrence`` is the token-by-token
recurrence it is tested against. A DevFT submodel keeps the layer order
of the configuration's period: with ``n`` attention layers among ``T``,
each period of ``T / n`` layers holds one attention layer at the
configuration's offset scaled to that period.

DevFT's grouping, fusion and transfer are those of ``reference.py``,
applied to each stack of layers on its own (``stack_capacities``). Where
the layers' similarity has near-equal eigenvalues at the number of
groups, its spectral embedding is any rotation of their eigenvectors,
and the program's grouping and this one can both be sound and still
differ: ``group_gap`` judges the program's grouping by a measure that
does not depend on the embedding.
"""
from __future__ import annotations

import functools
import json
import types
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference import (  # noqa: F401 (DevFT's steps, reused)
    F32, HIGHEST, broadcast, capacities, fuse, layer_gram, quantize,
    spectral_groups, stage_lr)


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def lora_delta(x, lo, scale):
    return _mm(_mm(x, lo["a"]), lo["b"]) * scale


# ---------------------------------------------------------------------------
# the layer order
# ---------------------------------------------------------------------------


def period_offset(m: dict):
    """The configuration's attention period and offset, read off
    ``layer_types``: attention at ``offset + k * period``."""
    at = [i for i, t in enumerate(m["layer_types"]) if t == "attention"]
    period = len(m["layer_types"]) // len(at)
    if at != [at[0] + k * period for k in range(len(at))] or \
            at[0] >= period:
        raise harness.BenchError("layer_types is not one attention layer "
                                 "per period")
    return period, at[0]


def stack_capacities(m: dict, capacity: int) -> Dict[str, int]:
    """A stage's layers split over the stacks in proportion to their
    depth: each non-empty stack keeps at least one layer, the largest
    stack takes up the rounding, and the total is ``capacity``."""
    sizes = {"mamba_mlp": m["layer_types"].count("mamba"),
             "attn_mlp": m["layer_types"].count("attention")}
    total = sum(sizes.values())
    caps = {k: min(v, max(1, round(capacity * v / total)))
            for k, v in sizes.items()}
    caps["mamba_mlp"] += min(capacity, total) - sum(caps.values())
    return caps


def _order(m: dict, n_mamba: int, n_attn: int):
    """``(mamba layers per period, attention's place in the period)`` of
    a (sub)model with these stack depths."""
    period, offset = period_offset(m)
    total = n_mamba + n_attn
    if total % n_attn:
        raise harness.BenchError(f"{n_mamba} Mamba and {n_attn} attention "
                                 f"layers make no whole periods")
    per = total // n_attn
    return per - 1, offset * per // period


def spread(w: np.ndarray, groups) -> float:
    """How far the layers lie from their groups' means: the k-means
    objective of the unit layer vectors, read off their cosine
    similarity ``w`` as ``sum_g |g| - sum_{i, j in g} w_ij / |g|``."""
    return float(sum(len(g) - w[np.ix_(g, g)].sum() / len(g)
                     for g in groups))


def group_gap(w: np.ndarray, groups, k: int):
    """The spread of ``groups`` over that of this module's own grouping
    into ``k`` groups, less one: 0 where the two are as tight, above 0
    where ``groups`` is looser. None where ``groups`` is no partition of
    the layers into ``k`` non-empty sorted groups ordered by their first
    layer (the order the submodel takes its layers in)."""
    groups = [list(g) for g in groups]
    if len(groups) != k or any(not g or g != sorted(g) for g in groups) \
            or sorted(i for g in groups for i in g) != list(range(len(w))) \
            or [g[0] for g in groups] != sorted(g[0] for g in groups):
        return None
    return spread(w, groups) / spread(w, spectral_groups(w, k)) - 1.0


# ---------------------------------------------------------------------------
# the mixers
# ---------------------------------------------------------------------------


def attention(m, p, lo, x):
    """NoPE grouped-query attention, scores times
    ``attention_multiplier``."""
    b, s, _ = x.shape
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    scale = m["lora_alpha"] / m["lora_rank"]
    q = (_mm(x, p["wq"]) + lora_delta(x, lo["wq"], scale)).reshape(
        b, s, h, hd)
    k = _mm(x, p["wk"]).reshape(b, s, kv, hd)
    v = (_mm(x, p["wv"]) + lora_delta(x, lo["wv"], scale)).reshape(
        b, s, kv, hd)
    rep = h // kv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        * m["attention_multiplier"]
    pos = jnp.arange(s)
    sc = jnp.where((pos[None, :] <= pos[:, None])[None, None], sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST)
    return _mm(o.reshape(b, s, h * hd), p["wo"])


def segment_sums(da):
    """``da`` (..., S) -> (..., S, S) with ``[t, s] = sum_{r=s+1..t}
    da_r`` for ``s <= t`` and -inf above the diagonal: each sum taken
    directly, not as a difference of prefix sums."""
    s = da.shape[-1]
    strict = jnp.tril(jnp.ones((s, s), bool), -1)
    terms = jnp.where(strict, da[..., :, None], 0.0)      # [t', s] = da_t'
    sums = jnp.cumsum(terms, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((s, s), bool)), sums, -jnp.inf)


def ssd(x, dt, a, b, c, d):
    """The SSD in its quadratic form over the whole sequence. x: (B, S,
    H, P); dt: (B, S, H) (after softplus); a: (H,) (negative); b, c: (B,
    S, G, N); d: (H,). Returns y like x. Heads are taken eight at a
    time, so that their (S, S) decay matrices fit beside the model."""
    h, g = x.shape[2], b.shape[2]
    cb = jnp.einsum("btgn,bsgn->bgts", c, b, precision=HIGHEST)

    def head(i):
        decay = jnp.exp(segment_sums(dt[:, :, i] * a[i]))  # (B, T, S)
        w = cb[:, i // (h // g)] * decay * dt[:, None, :, i]
        return jnp.einsum("bts,bsp->btp", w, x[:, :, i], precision=HIGHEST)

    y = jnp.moveaxis(jax.lax.map(head, jnp.arange(h), batch_size=8), 0, 2)
    return y + x * d[:, None]


def ssd_recurrence(x, dt, a, b, c, d):
    """The same map token by token, in float64 numpy: ``h_t = exp(dt_t
    a) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``."""
    x, dt, a, b, c, d = (np.asarray(v, np.float64)
                         for v in (x, dt, a, b, c, d))
    bs, s, h, p = x.shape
    rep = h // b.shape[2]
    bh, ch = np.repeat(b, rep, axis=2), np.repeat(c, rep, axis=2)
    state = np.zeros((bs, h, p, b.shape[-1]))
    y = np.zeros_like(x)
    for t in range(s):
        state = state * np.exp(dt[:, t] * a)[..., None, None] + np.einsum(
            "bh,bhp,bhn->bhpn", dt[:, t], x[:, t], bh[:, t])
        y[:, t] = np.einsum("bhpn,bhn->bhp", state, ch[:, t]) \
            + d[:, None] * x[:, t]
    return y


def mamba(m, p, lo, u):
    """The Mamba-2 mixer over the whole sequence."""
    bs, s, _ = u.shape
    h, hp, n, g = m["mamba_n_heads"], m["mamba_d_head"], \
        m["mamba_d_state"], m["mamba_n_groups"]
    din = h * hp
    scale = m["lora_alpha"] / m["lora_rank"]
    proj = _mm(u, p["in_proj"]) + lora_delta(u, lo["in_proj"], scale)
    z, xbc, dt = proj[..., :din], proj[..., din:2 * din + 2 * g * n], \
        proj[..., 2 * din + 2 * g * n:]
    w = p["conv_w"].astype(F32)                             # (K, channels)
    k = w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + s] * w[i] for i in range(k)) \
        + p["conv_b"].astype(F32)
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :din].reshape(bs, s, h, hp)
    b = xbc[..., din:din + g * n].reshape(bs, s, g, n)
    c = xbc[..., din + g * n:].reshape(bs, s, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))
    y = ssd(x, dt, a, b, c, p["D"].astype(F32)).reshape(bs, s, din)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"], m["rms_norm_eps"])
    return _mm(y, p["out_proj"]) + lora_delta(y, lo["out_proj"], scale)


def mlp(p, x):
    return _mm(jax.nn.silu(_mm(x, p["wg"])) * _mm(x, p["wu"]), p["wd"])


def block(m, mixer, p, lo, x):
    """One pre-norm residual layer with Granite's residual multiplier."""
    eps, r = m["rms_norm_eps"], m["residual_multiplier"]
    x = x + r * mixer(m, p["mixer"], lo, rms_norm(x, p["ln1"], eps))
    return x + r * mlp(p["ffn"], rms_norm(x, p["ln2"], eps))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _period(m, off, mb, at, lm, la, x):
    """One period of layers: the Mamba layers before the attention
    layer (the first ``off`` of ``mb``), the attention layer (``at``
    holds one), then the rest of ``mb``; ``lm`` and ``la`` are their
    adapters. Each layer reads its weights out of the period's stack by
    index and is rematerialised in the backward, so that no layer's
    activations are kept."""
    def at_index(t, i):
        return jax.tree.map(lambda a: a[i], t)

    mamba_layer = jax.checkpoint(
        lambda x, i: block(m, mamba, at_index(mb, i), at_index(lm, i), x))
    attn_layer = jax.checkpoint(
        lambda x: block(m, attention, at_index(at, 0), at_index(la, 0), x))

    def run(x, js):
        return jax.lax.scan(lambda x, j: (mamba_layer(x, j), None), x,
                            js)[0]
    n = jax.tree.leaves(mb)[0].shape[0]
    return run(attn_layer(run(x, jnp.arange(off))), jnp.arange(off, n))


def _layout(m, params, lora):
    """``(periods, off, per, mamba, attention)``: the (sub)model's number
    of periods, the attention layer's place in a period, its Mamba
    layers per period, and each stack with its adapter."""
    mb, at = params["blocks"]["mamba_mlp"], params["blocks"]["attn_mlp"]
    n_attn = jax.tree.leaves(at)[0].shape[0]
    per, off = _order(m, jax.tree.leaves(mb)[0].shape[0], n_attn)
    return n_attn, off, per, (mb, lora["mamba_mlp"]), \
        (at, lora["attn_mlp"])


def _take(tree, k, n):
    """Layers ``k * n`` to ``k * n + n - 1`` of every leaf."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, k * n, n), tree)


def embed(m, table, tokens):
    return table[tokens].astype(F32) * m["embedding_multiplier"]


def head_nll(m, final_norm, table, h, labels):
    """Mean next-token cross-entropy of the last hidden states ``h``
    over every label, the softmax over every row of the tied
    embedding."""
    h = rms_norm(h, final_norm, m["rms_norm_eps"])
    lg = _mm(h, table.T) / m["logits_scaling"]
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def hidden(m, params, lora, tokens):
    """The last layer's hidden states (B, S, d), before the final norm:
    the periods in order (a DevFT submodel keeps the configuration's
    period, ``_order``)."""
    n_attn, off, per, mamba_, attn = _layout(m, params, lora)
    x = embed(m, params["embed"], tokens)
    for k in range(n_attn):
        (mb, lm), (at, la) = _take(mamba_, k, per), _take(attn, k, 1)
        x = _period(m, off, mb, at, lm, la, x)
    return x


def loss(m, params, lora, batch):
    """Mean next-token cross-entropy. Returns ``(total, nll)``."""
    nll = head_nll(m, params["final_norm"], params["embed"],
                   hidden(m, params, lora, batch["tokens"]),
                   batch["labels"])
    return nll, nll


@functools.lru_cache(maxsize=8)
def _programs(key: str):
    """The jitted pieces a step is run from, for the model ``key`` (the
    model block as JSON): each compiles once for all the capacities of a
    schedule, since a period's shapes do not depend on how many periods
    a submodel has."""
    m = json.loads(key)

    def period(off, *a):
        return _period(m, off, *a)

    def period_vjp(off, mb, at, lm, la, x, g):
        _, back = jax.vjp(lambda lm, la, x: period(off, mb, at, lm, la, x),
                          lm, la, x)
        return back(g)

    def update(lo, mu, nu, c, grads, lr):
        """One AdamW step (b1 .9, b2 .999, eps 1e-8, no weight decay) on
        the per-period gradients laid end to end; returns the state and
        each layer's gradient norm."""
        b1, b2, eps = 0.9, 0.999, 1e-8
        g = {"mamba_mlp": jax.tree.map(lambda *a: jnp.concatenate(a),
                                       *[gm for gm, _ in grads]),
             "attn_mlp": jax.tree.map(lambda *a: jnp.concatenate(a),
                                      *[ga for _, ga in grads])}
        c = c + 1.0
        mu = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, mu, g)
        nu = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, nu, g)
        lo = jax.tree.map(
            lambda p, a, b: p - lr * (a / (1 - b1 ** c))
            / (jnp.sqrt(b / (1 - b2 ** c)) + eps), lo, mu, nu)
        gn = jax.tree.map(lambda a: jnp.sqrt(jnp.sum(
            a * a, axis=tuple(range(1, a.ndim)))), g)
        return lo, mu, nu, c, gn

    return types.SimpleNamespace(
        take=jax.jit(_take, static_argnums=2),
        embed=jax.jit(lambda t, tok: embed(m, t, tok)),
        period=jax.jit(period, static_argnums=0),
        period_vjp=jax.jit(period_vjp, static_argnums=0),
        nll=jax.jit(lambda *a: head_nll(m, *a)),
        nll_grad=jax.jit(jax.value_and_grad(
            lambda fn, t, h, lab: head_nll(m, fn, t, h, lab), argnums=2)),
        update=jax.jit(update))


def _run(m, params, lora, tokens):
    """The programs, ``take(k)`` (period ``k``'s weights and adapters),
    the attention layer's place in a period, and each period's input
    then the last layer's output."""
    P = _programs(json.dumps(m, sort_keys=True))
    n_attn, off, per, mamba_, attn = _layout(m, params, lora)

    def take(k):
        (mb, lm), (at, la) = P.take(mamba_, k, per), P.take(attn, k, 1)
        return mb, at, lm, la

    xs = [P.embed(params["embed"], tokens)]
    for k in range(n_attn):
        xs.append(P.period(off, *take(k), xs[-1]))
    return P, take, off, xs


def eval_loss(m, params, lora, batch, rows: int = 2):
    """``loss``'s cross-entropy over a large batch, ``rows`` at a time."""
    parts = []
    for i in range(0, batch["tokens"].shape[0], rows):
        P, _, _, xs = _run(m, params, lora, batch["tokens"][i:i + rows])
        parts.append(P.nll(params["final_norm"], params["embed"], xs[-1],
                           batch["labels"][i:i + rows]))
    return float(np.mean([float(x) for x in parts]))


def loss_and_grads(m, params, lora, batch):
    """``loss``'s cross-entropy and its gradient for ``lora``, a period
    at a time: the forward keeps each period's input, the backward runs
    each period's forward again. Returns ``(nll, per-period gradients
    [(mamba, attention), ...])``."""
    P, take, off, xs = _run(m, params, lora, batch["tokens"])
    nll, g = P.nll_grad(params["final_norm"], params["embed"], xs[-1],
                        batch["labels"])
    grads = []
    for k in reversed(range(len(xs) - 1)):
        mb, at, lm, la = take(k)
        g_lm, g_la, g = P.period_vjp(off, mb, at, lm, la, xs[k], g)
        grads.insert(0, (g_lm, g_la))
    return nll, grads


def adamw_steps(m, params, lora, batches, lr):
    """K AdamW steps (``_programs``' ``update``) from a fresh optimizer
    state, one per batch of ``batches``. Returns ``(lora, losses (K,),
    first_grad_norms)``, the last a tree of per-layer norms of the first
    step's gradient."""
    P = _programs(json.dumps(m, sort_keys=True))
    z = jax.tree.map(jnp.zeros_like, lora)
    state, losses, first = (lora, z, z, jnp.zeros((), F32)), [], None
    for t in range(batches["tokens"].shape[0]):
        nll, grads = loss_and_grads(m, params, state[0],
                                    {k: v[t] for k, v in batches.items()})
        *state, gn = P.update(*state, grads, lr)
        losses.append(nll)
        first = gn if first is None else first
    return state[0], jnp.stack(losses), first
