"""Plain reference of what the timed paths compute, written from the
configuration files and the paper, importing nothing of the program.

Everything runs in float32 with ``highest`` matmul precision; weights
stored in a lower precision are upcast one layer at a time inside the
layer scan. The model is a pre-norm decoder: RMSNorm, grouped-query
attention with half-split rotary embeddings and optional QKV bias, LoRA
on the query and value projections (scaling ``alpha / rank``), then a
SwiGLU MLP or a top-k mixture of experts with the capacity rule written
in the configuration file, and an untied or tied output head.

``quantize`` makes the control: the same reference with every base
matrix rounded to float8 (e4m3: 4 exponent and 3 mantissa bits, one
scale per output column), the precision step below the bfloat16 the
configurations state.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HIGHEST)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, pos, theta):
    """x: (B, S, H, hd); pos: (S,). Rotates the two halves of each head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def lora_delta(x, lo, scale):
    return _mm(_mm(x, lo["a"]), lo["b"]) * scale


def attention(m, p, lo, x, pos):
    b, s, _ = x.shape
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    scale = m["lora_alpha"] / m["lora_rank"]

    def proj(name, lname=None):
        y = _mm(x, p["w" + name])
        if "b" + name in p:
            y = y + p["b" + name].astype(F32)
        if lname is not None and lo is not None:
            y = y + lora_delta(x, lo[lname], scale)
        return y

    q = proj("q", "wq").reshape(b, s, h, hd)
    k = proj("k").reshape(b, s, kv, hd)
    v = proj("v", "wv").reshape(b, s, kv, hd)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    rep = h // kv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / math.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HIGHEST)
    return _mm(o.reshape(b, s, h * hd), p["wo"])


def mlp(p, x):
    return _mm(jax.nn.silu(_mm(x, p["wg"])) * _mm(x, p["wu"]), p["wd"])


def moe(m, p, x):
    """Top-k routing over every expert of the layer. Each expert takes at
    most ``capacity`` routed (token, choice) slots, counted in token
    order and then choice order; later slots are dropped. Returns
    ``(y, aux)`` with the Switch load-balance term."""
    b, s, d = x.shape
    t = x.reshape(b * s, d)
    e, k = m["num_local_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.matmul(t, p["router"].astype(F32),
                                      precision=HIGHEST), -1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, -1, keepdims=True)
    n = b * s
    cap = max(8, -(-math.ceil(n * k / e * m["capacity_factor"]) // 8) * 8)
    onehot = jax.nn.one_hot(idx.reshape(-1), e, dtype=jnp.int32)   # (n*k, E)
    pos = jnp.sum((jnp.cumsum(onehot, 0) - 1) * onehot, -1)
    keep = (pos < cap).reshape(n, k)
    gate = jnp.zeros((n, e), F32).at[jnp.arange(n)[:, None], idx].add(
        jnp.where(keep, w, 0.0))
    hg = jnp.einsum("td,edf->tef", t, p["wg"].astype(F32), precision=HIGHEST)
    hu = jnp.einsum("td,edf->tef", t, p["wu"].astype(F32), precision=HIGHEST)
    hh = jax.nn.silu(hg) * hu * gate[:, :, None]
    y = jnp.einsum("tef,efd->td", hh, p["wd"].astype(F32), precision=HIGHEST)
    me = jnp.mean(probs, 0)
    ce = jnp.sum(jax.nn.one_hot(idx.reshape(-1), e, dtype=F32), 0) / (n * k)
    aux = e * jnp.sum(me * ce) * m["router_aux_loss_coef"]
    return y.reshape(b, s, d), aux


def hidden(m, params, lora, tokens, pos=None):
    """Final hidden states (B, S, d) and the summed MoE aux term."""
    x = params["embed"][tokens].astype(F32)
    pos = jnp.arange(tokens.shape[1]) if pos is None else pos
    eps = m["rms_norm_eps"]
    blocks = params["blocks"]["layers"]
    lo_stack = None if lora is None else lora["layers"]

    def body(carry, layer):
        x, aux = carry
        p, lo = layer
        x = x + attention(m, p["mixer"], lo, rms_norm(x, p["ln1"], eps), pos)
        hn = rms_norm(x, p["ln2"], eps)
        if m.get("num_local_experts"):
            y, a = moe(m, p["ffn"], hn)
            aux = aux + a
        else:
            y = mlp(p["ffn"], hn)
        return (x + y, aux), None

    (x, aux), _ = jax.lax.scan(jax.checkpoint(body),
                               (x, jnp.zeros((), F32)),
                               (blocks, lo_stack))
    return rms_norm(x, params["final_norm"], eps), aux


def logits(m, params, h):
    w = params["embed"].T if m.get("tie_word_embeddings") \
        else params["lm_head"]
    return _mm(h, w)


def loss(m, params, lora, batch):
    """Mean next-token cross-entropy over every label, plus the aux term
    (what is differentiated). Returns ``(total, nll)``. The softmax runs
    over every column of the output head, as the program's training
    loss does (padding columns included)."""
    h, aux = hidden(m, params, lora, batch["tokens"])
    lg = logits(m, params, h)
    logp = jax.nn.log_softmax(lg, -1)
    nll = -jnp.mean(jnp.take_along_axis(logp, batch["labels"][..., None],
                                        -1))
    return nll + aux, nll


def eval_loss(m, params, lora, batch, rows: int = 2):
    """``loss``'s cross-entropy over a large batch, ``rows`` at a time,
    so the full-vocabulary float32 logits fit."""
    f = jax.jit(lambda p, lo, bt: loss(m, p, lo, bt)[1])
    n = batch["tokens"].shape[0]
    parts = [f(params, lora, {k: v[i:i + rows] for k, v in batch.items()})
             for i in range(0, n, rows)]
    return float(np.mean([float(x) for x in parts]))


# ---------------------------------------------------------------------------
# local training and aggregation
# ---------------------------------------------------------------------------


def adamw_steps(m, params, lora, batches, lr):
    """K AdamW steps (b1 .9, b2 .999, eps 1e-8, no weight decay) from a
    fresh optimizer state. batches: {'tokens': (K, B, S), ...}. Returns
    ``(lora, losses (K,), first_grad_norms)``, the last as a tree of
    per-layer norms of the first step's gradient."""
    b1, b2, eps = 0.9, 0.999, 1e-8

    def step(carry, batch):
        lo, mu, nu, c = carry
        (_, nll), g = jax.value_and_grad(
            lambda l: loss(m, params, l, batch), has_aux=True)(lo)
        c = c + 1.0
        mu = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, mu, g)
        nu = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, nu, g)
        lo = jax.tree.map(
            lambda p, a, b: p - lr * (a / (1 - b1 ** c))
            / (jnp.sqrt(b / (1 - b2 ** c)) + eps), lo, mu, nu)
        gn = jax.tree.map(lambda a: jnp.sqrt(jnp.sum(
            a * a, axis=tuple(range(1, a.ndim)))), g)
        return (lo, mu, nu, c), (nll, gn)

    z = jax.tree.map(jnp.zeros_like, lora)
    (lora, _, _, _), (losses, gns) = jax.lax.scan(
        step, (lora, z, z, jnp.zeros((), F32)), batches)
    return lora, losses, jax.tree.map(lambda a: a[0], gns)


# ---------------------------------------------------------------------------
# DevFT: schedule, grouping, fusion, transfer (paper §2.2, §3.2-3.4)
# ---------------------------------------------------------------------------


def capacities(n_layers: int, n_stages: int, growth: float = 2.0):
    """L_s = ceil(L / growth^(S - s)), s = 1..S."""
    return [max(1, -(-n_layers // int(growth ** (n_stages - s))))
            for s in range(1, n_stages + 1)]


def stage_lr(lr: float, factor: float, stage: int, n_stages: int) -> float:
    """Paper App. B: the client LR rises x``factor`` per stage to ``lr``."""
    return lr * factor ** (stage - (n_stages - 1))


def layer_gram(stack: Dict, lora_stack: Dict) -> np.ndarray:
    """Cosine similarity (L, L) of the layers' flattened parameters,
    LoRA included (Eq. 1), over every element."""
    leaves = jax.tree.leaves(stack) + jax.tree.leaves(lora_stack)
    n = leaves[0].shape[0]
    g = sum(jnp.einsum("ln,mn->lm", x.reshape(n, -1), x.reshape(n, -1),
                       preferred_element_type=F32, precision=HIGHEST)
            for x in leaves)
    g = np.asarray(g, np.float64)
    nrm = np.sqrt(np.diag(g))
    return np.clip(g / np.outer(nrm, nrm), -1.0, 1.0)


def spectral_groups(w: np.ndarray, k: int) -> List[List[int]]:
    """Eq. 2-3: the graph Laplacian of the similarity, its ``k`` smallest
    eigenvectors, rows normalized, then k-means (farthest-point start).
    Groups are sorted lists, ordered by their first layer."""
    n = w.shape[0]
    if k >= n:
        return [[i] for i in range(n)]
    w = w.copy()
    np.fill_diagonal(w, 0.0)
    lap = np.diag(w.sum(1)) - w
    emb = np.linalg.eigh(lap)[1][:, :k]
    emb = emb / np.linalg.norm(emb, axis=1, keepdims=True)
    centers = [emb[0]]
    for _ in range(1, k):
        d2 = np.min([((emb - c) ** 2).sum(1) for c in centers], axis=0)
        centers.append(emb[int(np.argmax(d2))])
    centers = np.stack(centers)
    labels = None
    for _ in range(100):
        new = np.argmin(((emb[:, None] - centers[None]) ** 2).sum(2), 1)
        if labels is not None and np.array_equal(new, labels):
            break
        labels = new
        centers = np.stack([emb[labels == c].mean(0) for c in range(k)])
    groups = [sorted(np.nonzero(labels == c)[0].tolist()) for c in range(k)]
    return sorted(groups, key=lambda g: g[0])


def fuse(stack, groups, beta: float):
    """Eq. 5: each group becomes anchor + beta * sum(member - anchor),
    the anchor being the group's first layer (in float32)."""
    def one(a):
        out = []
        for g in groups:
            anchor = a[g[0]].astype(F32)
            diff = sum(a[j].astype(F32) - anchor for j in g)
            out.append(anchor + beta * diff)
        return jnp.stack(out)
    return jax.tree.map(one, stack)


def broadcast(sub_stack, groups, n_layers: int):
    """Eq. 12: every layer inherits its group's trained adapter."""
    owner = np.zeros(n_layers, np.int64)
    for gi, g in enumerate(groups):
        owner[g] = gi
    return jax.tree.map(lambda a: a[owner], sub_stack)


# ---------------------------------------------------------------------------
# the control's precision
# ---------------------------------------------------------------------------


def quantize(params):
    """Every base matrix (not the norms or biases) rounded to float8 e4m3
    with one scale per output column, returned dequantized in the stored
    dtype."""
    def q(x, matrix_ndim):
        if x.ndim < matrix_ndim:
            return x
        x32 = x.astype(F32)
        # 240: the largest float8 e4m3 value reduce_precision keeps (it
        # reserves the top exponent as IEEE does; e4m3fn reaches 448)
        s = jnp.max(jnp.abs(x32), axis=-2, keepdims=True) / 240.0
        s = jnp.where(s == 0, 1.0, s)
        # reduce_precision, not a cast to float8 and back: XLA may drop
        # a round trip through a narrower type (excess precision)
        q8 = jax.lax.reduce_precision(x32 / s, exponent_bits=4,
                                      mantissa_bits=3)
        return (q8 * s).astype(x.dtype)

    def run(p):
        out = {k: q(v, 2) if k in ("embed", "lm_head") else v
               for k, v in p.items() if k != "blocks"}
        out["blocks"] = jax.tree.map(lambda x: q(x, 3), p["blocks"])
        return out
    return jax.jit(run)(params)
