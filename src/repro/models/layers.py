"""Shared transformer primitives: norms, RoPE / M-RoPE, GQA & MLA attention,
SwiGLU MLP.

All functions are pure; parameters are plain dict pytrees. Layer functions
take *unstacked* (single-layer) params — stacking over a layer axis and
``lax.scan`` happen in ``repro.models.transformer``.

Shape conventions: activations are ``(B, S, d)``; per-head tensors are
``(B, S, H, hd)``.

Kernel backends: every layer reads ``cfg.kernel_backend`` and routes its
hot ops through ``repro.kernels.dispatch`` — ``attend`` to the Pallas
flash-attention kernel, ``_proj`` (frozen weight + LoRA) to the fused
``lora_matmul`` kernel. The ``reference`` backend is the inline jnp math
below, unchanged, so golden round logs stay bit-identical. Decode
attention over the ragged cache routes to the ``flash_decode`` kernel;
decode projections stay jnp (single-token GEMMs, and per-slot adapters
the fused LoRA kernel does not take).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.common import NEG_INF  # noqa: F401 (shared constant)

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * scale


# ---------------------------------------------------------------------------
# Rotary embeddings (plain + multimodal M-RoPE)
# ---------------------------------------------------------------------------


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float
                 ) -> Tuple[jax.Array, jax.Array]:
    """positions: (B, S) int32 -> cos/sin (B, S, head_dim//2) float32."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # (B,S,half)
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(positions: jax.Array, sections: Tuple[int, ...],
                  head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """Qwen2-VL multimodal RoPE.

    positions: (3, B, S) — temporal / height / width position streams.
    ``sections`` splits the head_dim//2 frequency slots between streams
    (e.g. (16, 24, 24) for head_dim=128). Text tokens carry identical
    positions in all three streams, reducing M-RoPE to 1-D RoPE exactly.
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    # stream id of each frequency slot
    stream = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections),
        total_repeat_length=half)                                # (half,)
    pos = positions.astype(jnp.float32)                          # (3,B,S)
    pos_per_slot = jnp.take(pos, stream, axis=0)                 # (half,B,S)
    ang = jnp.moveaxis(pos_per_slot, 0, -1) * inv_freq           # (B,S,half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2). Half-split rotation."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x1.dtype)
    s = sin[:, :, None, :].astype(x1.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def text_positions(batch: int, seq: int, offset=0) -> jax.Array:
    p = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset
    return jnp.broadcast_to(p, (batch, seq))


def vlm_positions(batch: int, n_vis: int, n_text: int,
                  grid: Optional[Tuple[int, int]] = None) -> jax.Array:
    """(3, B, S) M-RoPE positions: vision tokens get (t=0, h, w) grid
    positions; text tokens get synchronized sequential positions starting
    after the max vision position (Qwen2-VL scheme)."""
    if grid is None:
        side = max(int(math.sqrt(n_vis)), 1)
        grid = (side, max(n_vis // side, 1))
    gh, gw = grid
    idx = jnp.arange(n_vis, dtype=jnp.int32)
    vt = jnp.zeros_like(idx)
    vh = (idx // gw) % gh
    vw = idx % gw
    start = max(gh, gw)
    tpos = jnp.arange(n_text, dtype=jnp.int32) + start
    pos3 = jnp.stack([
        jnp.concatenate([vt, tpos]),
        jnp.concatenate([vh, tpos]),
        jnp.concatenate([vw, tpos]),
    ])                                                           # (3, S)
    return jnp.broadcast_to(pos3[:, None, :], (3, batch, n_vis + n_text))


# ---------------------------------------------------------------------------
# Attention core (shared by GQA and expanded-MLA paths)
# ---------------------------------------------------------------------------


def model_backend(cfg) -> str:
    """The kernel backend a config asks for (``reference`` when absent,
    e.g. hand-built test configs)."""
    return getattr(cfg, "kernel_backend", None) or "reference"


def _flash_eligible(q, k, v, q_offset, kv_valid_len) -> bool:
    """Whether this ``attend`` call fits the flash kernel's contract:
    no ragged-cache masking, zero query offset (prefill/train), square
    q/k lengths, and matching qk/v head dims (MLA's expanded path has
    ``v_head_dim != qk_head_dim`` and falls back to reference)."""
    return (kv_valid_len is None
            and isinstance(q_offset, int) and q_offset == 0
            and q.shape[1] == k.shape[1]
            and v.shape[-1] == q.shape[-1]
            and q.shape[2] % k.shape[2] == 0)


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,Sq,H,hd); k: (B,Sk,Hkv,hd) -> float32 scores
    (B,Hkv,rep,Sq,Sk). Accumulated and returned in float32, as the
    attention kernels do: bf16 scores of magnitude ~10 round by ~0.02,
    which moves the softmax by a few percent."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    qg = q.reshape(b, sq, hkv, rep, hd)
    return jnp.einsum("bqkrd,bskd->bkrqs", qg, k,
                      preferred_element_type=jnp.float32)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
           causal: bool = True,
           window: Optional[int] = None,
           q_offset: jax.Array | int = 0,
           kv_valid_len: Optional[jax.Array] = None,
           scale: Optional[float] = None,
           backend: str = "reference") -> jax.Array:
    """Grouped-query attention with optional sliding window and KV cache.

    q: (B, Sq, H, hd); k/v: (B, Sk, Hkv, hd).
    ``q_offset`` is the absolute position of q[0] (decode: cache length).
    ``kv_valid_len`` masks ragged cache entries (decode ring buffers).
    ``backend`` routes eligible calls to the flash-attention kernel;
    ineligible ones (ragged caches, MLA v-dim, decode offsets) always
    take the reference math below.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if dispatch.use_pallas(backend) and _flash_eligible(
            q, k, v, q_offset, kv_valid_len):
        flash = dispatch.get_kernel("flash_attention", backend)
        return flash(q, k, v, causal=causal, window=window, scale=scale,
                     interpret=dispatch.interpret_default())
    scores = _gqa_scores(q * scale, k)                      # (B,Hkv,rep,Sq,Sk)

    qpos = jnp.arange(sq) + q_offset                         # (Sq,)
    kpos = jnp.arange(sk)                                    # (Sk,)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    if kv_valid_len is not None:
        valid = kpos[None, None, :] < jnp.reshape(kv_valid_len, (-1, 1, 1))
        mask = mask[None] & valid                            # (B,Sq,Sk)
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    else:
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    if window is not None or kv_valid_len is not None:
        # a fully-masked row (window + ragged cache can exclude every
        # key) must emit zeros: softmax over all-NEG_INF logits is
        # *uniform*, which would average garbage cache slots into the
        # output
        alive = jnp.any(mask, axis=-1)                       # (Sq,) | (B,Sq)
        if alive.ndim == 1:
            alive = alive[None]
        probs = jnp.where(alive[:, None, None, :, None], probs, 0.0)
    probs = probs.astype(v.dtype)
    out = jnp.einsum("bkrqs,bskd->bqkrd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])  # v head dim may differ (MLA)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------


def init_gqa(key, cfg, dtype) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    sd = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dtype) * sd,
        "wk": jax.random.normal(ks[1], (d, hkv * hd), dtype) * sd,
        "wv": jax.random.normal(ks[2], (d, hkv * hd), dtype) * sd,
        "wo": jax.random.normal(ks[3], (h * hd, d), dtype) * (1.0 / math.sqrt(h * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _proj(x, w, b=None, lora=None, backend: str = "reference"):
    # per-slot serving stacks adapters with a leading batch axis
    # ((B, din, r) factors); the fused kernel is single-adapter, so
    # batched trees take the jnp path, whose matmuls broadcast natively
    if lora is not None and lora["a"].ndim == 2 and dispatch.use_pallas(backend):
        # fused frozen-weight + LoRA kernel: x read from HBM once; the
        # scaling operand is alpha/r, same formula as the jnp path
        fused = dispatch.get_kernel("lora_matmul", backend)
        y = fused(x, w, lora["a"].astype(x.dtype),
                  lora["b"].astype(x.dtype), scaling=lora_scaling(lora),
                  interpret=dispatch.interpret_default())
    else:
        y = x @ w
        if lora is not None:
            # LoRA params may be f32 while activations are bf16 — keep the
            # activation dtype (adapters are cast at use, standard
            # QLoRA-style)
            a = lora["a"].astype(x.dtype)
            bb = lora["b"].astype(x.dtype)
            y = y + (x @ a) @ bb * lora_scaling(lora)
    if b is not None:
        y = y + b
    return y


def lora_scaling(lora) -> float:
    r = lora["a"].shape[-1]
    return lora.get("alpha", float(2 * r)) / r if isinstance(lora, dict) else 1.0


def gqa_qkv(params: dict, cfg, x: jax.Array, cos, sin, lora=None,
            backend: str = "reference"):
    """Project to q, k, v, rotated unless the config is NoPE (``cos`` and
    ``sin`` are then unused). lora: optional {'wq': {a,b}, 'wv': {a,b}}."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    lq = lora.get("wq") if lora else None
    lv = lora.get("wv") if lora else None
    q = _proj(x, params["wq"], params.get("bq"), lq,
              backend=backend).reshape(b, s, h, hd)
    k = _proj(x, params["wk"], params.get("bk")).reshape(b, s, hkv, hd)
    v = _proj(x, params["wv"], params.get("bv"), lv,
              backend=backend).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if not cfg.nope:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def gqa_attention(params: dict, cfg, x: jax.Array, cos, sin, *,
                  window=None, lora=None, causal=True) -> jax.Array:
    backend = model_backend(cfg)
    q, k, v = gqa_qkv(params, cfg, x, cos, sin, lora=lora, backend=backend)
    out = attend(q, k, v, causal=causal, window=window,
                 scale=cfg.attention_multiplier, backend=backend)
    b, s, _, _ = q.shape
    return out.reshape(b, s, -1) @ params["wo"]


def gqa_decode(params: dict, cfg, x: jax.Array, cache: dict, pos, cos, sin, *,
               lora=None):
    """Single-token decode against a (ring-buffer) KV cache.

    cache: {'k': (B, C, Hkv, hd), 'v': ...}; pos: (B,) int32 abs position.
    For full caches C == max_seq; for sliding-window C == window.
    """
    q, k_new, v_new = gqa_qkv(params, cfg, x, cos, sin, lora=lora)
    cap = cache["k"].shape[1]
    # ragged per-slot write cursors: each batch row advances independently
    # (serving slots admit/finish at different times)
    rows = jnp.arange(pos.shape[0])
    slots = pos % cap
    k = cache["k"].at[rows, slots].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[rows, slots].set(v_new[:, 0].astype(cache["v"].dtype))
    # ring buffer holds the last `cap` tokens -> all slots valid once full
    valid = jnp.minimum(pos + 1, cap)
    fd = dispatch.get_kernel("flash_decode", model_backend(cfg))
    out = fd(q, k, v, kv_valid_len=valid, scale=cfg.attention_multiplier,
             interpret=dispatch.interpret_default())
    b, s = x.shape[:2]
    y = out.reshape(b, s, -1) @ params["wo"]
    return y, {"k": k, "v": v}


def init_gqa_cache(cfg, batch: int, capacity: int, dtype) -> dict:
    hkv, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, capacity, hkv, hd), dtype),
        "v": jnp.zeros((batch, capacity, hkv, hd), dtype),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(key, cfg, dtype) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 5)
    sd = 1.0 / math.sqrt(d)
    return {
        "wq_a": jax.random.normal(ks[0], (d, m.q_lora_rank), dtype) * sd,
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": jax.random.normal(ks[1], (m.q_lora_rank, h * qh), dtype)
                * (1.0 / math.sqrt(m.q_lora_rank)),
        "wkv_a": jax.random.normal(
            ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype) * sd,
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": jax.random.normal(
            ks[3], (m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)),
            dtype) * (1.0 / math.sqrt(m.kv_lora_rank)),
        "wo": jax.random.normal(ks[4], (h * m.v_head_dim, d), dtype)
              * (1.0 / math.sqrt(h * m.v_head_dim)),
    }


def _mla_q(params, cfg, x, cos, sin, lora=None, backend: str = "reference"):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    lq = lora.get("wq_b") if lora else None
    qc = rms_norm(x @ params["wq_a"], params["q_norm"], cfg.norm_eps)
    q = _proj(qc, params["wq_b"], None, lq, backend=backend)
    q = q.reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _mla_ckv(params, cfg, x, cos, sin):
    m = cfg.mla
    ckv = x @ params["wkv_a"]                           # (B,S,rank+rope)
    c, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    c = rms_norm(c, params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]  # shared
    return c, k_rope


def mla_attention(params: dict, cfg, x: jax.Array, cos, sin, *,
                  lora=None, causal=True, window=None) -> jax.Array:
    """Train/prefill MLA: expand k/v from the compressed latent (faithful
    to the training-time formulation)."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    backend = model_backend(cfg)
    q_nope, q_rope = _mla_q(params, cfg, x, cos, sin, lora, backend=backend)
    c, k_rope = _mla_ckv(params, cfg, x, cos, sin)
    lkv = lora.get("wkv_b") if lora else None
    kv = _proj(c, params["wkv_b"], None, lkv, backend=backend)
    kv = kv.reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, m.qk_rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # v_head_dim != qk head dim -> attend's eligibility check sends MLA
    # to the reference path; the backend still covers the LoRA projs above
    out = attend(q, k, v, causal=causal, window=window,
                 scale=1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim),
                 backend=backend)
    return out.reshape(b, s, -1) @ params["wo"]


def mla_decode(params: dict, cfg, x: jax.Array, cache: dict, pos, cos, sin, *,
               lora=None):
    """Absorbed-matrix MLA decode (DeepSeek inference formulation).

    The KV cache stores ONLY the compressed latent ``c`` (kv_lora_rank) and
    the shared rotary key — the whole point of MLA. Query up-projections
    are absorbed into the latent space so scores are computed directly
    against ``c``:  score = (q_nope · W_uk) · c + q_rope · k_rope.
    cache: {'c': (B, C, rank), 'k_rope': (B, C, rope_hd)}; pos: (B,).
    """
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _mla_q(params, cfg, x, cos, sin, lora)   # (B,1,H,*)
    c_new, k_rope_new = _mla_ckv(params, cfg, x, cos, sin)
    cap = cache["c"].shape[1]
    # ragged per-slot write cursors (see gqa_decode)
    rows = jnp.arange(pos.shape[0])
    slots = pos % cap
    c = cache["c"].at[rows, slots].set(c_new[:, 0].astype(cache["c"].dtype))
    kr = cache["k_rope"].at[rows, slots].set(
        k_rope_new[:, 0].astype(cache["k_rope"].dtype))

    wkv_b = params["wkv_b"]
    if lora and "wkv_b" in lora:
        la = lora["wkv_b"]
        # batched (per-slot) adapters make the effective up-projection
        # per-row: (B, rank, H*(nope+v))
        wkv_b = wkv_b + (la["a"].astype(wkv_b.dtype)
                         @ la["b"].astype(wkv_b.dtype)) * lora_scaling(la)
    hd_kv = m.qk_nope_head_dim + m.v_head_dim
    if wkv_b.ndim == 3:
        w_uk = wkv_b.reshape(b, m.kv_lora_rank, h, hd_kv)
        w_uk_k = w_uk[..., : m.qk_nope_head_dim]        # (B,rank,H,nope)
        w_uv = w_uk[..., m.qk_nope_head_dim:]           # (B,rank,H,v)
        q_abs = jnp.einsum("bqhn,brhn->bqhr", q_nope, w_uk_k)
    else:
        w_uk = wkv_b.reshape(m.kv_lora_rank, h, hd_kv)
        w_uk_k = w_uk[:, :, : m.qk_nope_head_dim]       # (rank,H,nope)
        w_uv = w_uk[:, :, m.qk_nope_head_dim:]          # (rank,H,v)
        q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk_k)  # (B,1,H,rank)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    valid = jnp.minimum(pos + 1, cap)
    # the absorbed formulation IS flash_decode's "q^v" shape: qk over
    # rank+rope against the latent cache (one shared kv head), v over
    # the latent alone — ctx comes back (B,1,H,rank)
    q_full = jnp.concatenate([q_abs, q_rope], axis=-1)   # (B,1,H,rank+rope)
    kv_lat = jnp.concatenate([c, kr], axis=-1)[:, :, None, :]
    v_lat = c[:, :, None, :]                             # (B,C,1,rank)
    fd = dispatch.get_kernel("flash_decode", model_backend(cfg))
    ctx = fd(q_full, kv_lat, v_lat, kv_valid_len=valid, scale=scale,
             interpret=dispatch.interpret_default())
    if wkv_b.ndim == 3:
        out = jnp.einsum("bqhr,brhv->bqhv", ctx, w_uv)
    else:
        out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv)    # (B,1,H,v)
    y = out.reshape(b, s, -1) @ params["wo"]
    return y, {"c": c, "k_rope": kr}


def init_mla_cache(cfg, batch: int, capacity: int, dtype) -> dict:
    m = cfg.mla
    return {
        "c": jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, capacity, m.qk_rope_head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    si, so = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    return {
        "wg": jax.random.normal(ks[0], (d_model, d_ff), dtype) * si,
        "wu": jax.random.normal(ks[1], (d_model, d_ff), dtype) * si,
        "wd": jax.random.normal(ks[2], (d_ff, d_model), dtype) * so,
    }


def mlp(params: dict, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ params["wg"]) * (x @ params["wu"])) @ params["wd"]
