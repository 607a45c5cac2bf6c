"""Mamba-2 block (SSD — state-space duality, arXiv:2405.21060).

Forward over a sequence uses the *chunked SSD* algorithm: within a chunk
the recurrence is materialized as a (masked, decay-weighted) attention-like
quadratic form; across chunks a small ``lax.scan`` carries the SSM state
(B, H, hd, N). Decode is the O(1) recurrent state update.

The per-chunk inner computation is also available as a Pallas TPU kernel
(``repro.kernels.ssd_scan``): ``cfg.kernel_backend`` selects it through
``repro.kernels.dispatch`` (the ``reference`` backend is the pure-jnp
chunked path below — XLA fuses it well and it is what the dry-run
lowers). The in/out LoRA projections route through ``layers._proj`` so
they share the fused lora_matmul kernel and the alpha/r scaling rule.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.common import NEG_INF
from repro.models.layers import _proj, model_backend, rms_norm


def d_inner(cfg) -> int:
    return cfg.mamba.expand * cfg.d_model


def n_heads(cfg) -> int:
    return d_inner(cfg) // cfg.mamba.head_dim


def conv_dim(cfg) -> int:
    mb = cfg.mamba
    return d_inner(cfg) + 2 * mb.n_groups * mb.d_state


def init_mamba(key, cfg, dtype) -> dict:
    mb = cfg.mamba
    d = cfg.d_model
    din, h, cd = d_inner(cfg), n_heads(cfg), conv_dim(cfg)
    ks = jax.random.split(key, 4)
    sd = 1.0 / math.sqrt(d)
    # in_proj -> [z (din), x (din), B (G*N), C (G*N), dt (H)]
    return {
        "in_proj": jax.random.normal(ks[0], (d, 2 * din + 2 * mb.n_groups * mb.d_state + h), dtype) * sd,
        "conv_w": jax.random.normal(ks[1], (mb.conv_width, cd), dtype) * 0.1,
        "conv_b": jnp.zeros((cd,), dtype),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "out_norm": jnp.ones((din,), dtype),
        "out_proj": jax.random.normal(ks[3], (din, d), dtype) * (1.0 / math.sqrt(din)),
    }


def _split_proj(cfg, zxbcdt):
    mb = cfg.mamba
    din, h = d_inner(cfg), n_heads(cfg)
    gn = mb.n_groups * mb.d_state
    z, x, B, C, dt = jnp.split(zxbcdt, [din, 2 * din, 2 * din + gn,
                                        2 * din + 2 * gn], axis=-1)
    return z, x, B, C, dt


def _causal_conv(x, w, b):
    """x: (B, S, C); w: (K, C) depthwise causal conv."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i: i + x.shape[1]] * w[i] for i in range(k))
    return out + b


def ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """Chunked SSD forward.

    x: (b, S, H, P); dt: (b, S, H) (already softplus'd, >0);
    A: (H,) negative decay rates; B, C: (b, S, G, N); D: (H,).
    Returns y: (b, S, H, P).
    """
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    nc = S // chunk
    assert S % chunk == 0, (S, chunk)
    rep = H // G

    xr = x.reshape(b, nc, chunk, H, P)
    dtr = dt.reshape(b, nc, chunk, H)
    Br = jnp.repeat(B.reshape(b, nc, chunk, G, N), rep, axis=3)   # (b,nc,c,H,N)
    Cr = jnp.repeat(C.reshape(b, nc, chunk, G, N), rep, axis=3)

    dA = dtr * A[None, None, None, :]                             # (b,nc,c,H) <0
    cum = jnp.cumsum(dA, axis=2)                                  # within-chunk
    # ---- intra-chunk (quadratic) term --------------------------------
    # L[i,j] = exp(cum[i]-cum[j]) for i>=j. Masked (i<j) entries have
    # POSITIVE diff that can overflow exp and poison gradients through
    # jnp.where — clamp to NEG_INF (exp underflows to exactly 0.0 in
    # f32, same as any other large-negative literal) before
    # exponentiating.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]          # (b,nc,c,c,H)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    L = jnp.exp(jnp.where(mask, diff, NEG_INF))
    scores = jnp.einsum("bnihd,bnjhd->bnijh", Cr, Br)             # (b,nc,c,c,H)
    y_intra = jnp.einsum("bnijh,bnjh,bnjhp->bnihp",
                         (scores * L).astype(x.dtype),
                         dtr.astype(x.dtype), xr)
    # ---- chunk states -------------------------------------------------
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)               # (b,nc,c,H)
    states = jnp.einsum("bnchs,bnch,bnchp->bnhps",
                        Br.astype(jnp.float32),
                        (dtr * decay_to_end), xr.astype(jnp.float32))
    # ---- inter-chunk recurrence (scan over chunks) --------------------
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))                    # (b,nc,H)

    def step(carry, inp):
        st, dec = inp                                             # (b,H,P,N),(b,H)
        new = carry * dec[:, :, None, None] + st
        return new, carry                                         # emit prev state

    init = jnp.zeros((b, H, P, N), jnp.float32)
    _, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)                 # (b,nc,H,P,N)
    # ---- inter-chunk output term --------------------------------------
    decay_from_start = jnp.exp(cum)                               # (b,nc,c,H)
    y_inter = jnp.einsum("bnchs,bnhps,bnch->bnchp",
                         Cr.astype(jnp.float32), prev_states,
                         decay_from_start).astype(x.dtype)
    y = y_intra + y_inter + xr * D[None, None, None, :, None].astype(x.dtype)
    return y.reshape(b, S, H, P)


@jax.named_scope("mamba_mixer")
def mamba_forward(params: dict, cfg, u: jax.Array, *, lora=None) -> jax.Array:
    """Full-sequence forward. u: (B, S, d_model). Its operations carry
    the device-side name ``mamba_mixer`` in the HLO metadata."""
    mb = cfg.mamba
    din, h = d_inner(cfg), n_heads(cfg)
    backend = model_backend(cfg)
    proj = _proj(u, params["in_proj"],
                 lora=lora.get("in_proj") if lora else None, backend=backend)
    z, x, B, C, dt = _split_proj(cfg, proj)
    xbc = jnp.concatenate([x, B, C], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, params["conv_w"], params["conv_b"]))
    x, B, C = jnp.split(xbc, [din, din + mb.n_groups * mb.d_state], axis=-1)
    b_, S = u.shape[0], u.shape[1]
    x = x.reshape(b_, S, h, mb.head_dim)
    B = B.reshape(b_, S, mb.n_groups, mb.d_state)
    C = C.reshape(b_, S, mb.n_groups, mb.d_state)
    dt_ = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    if dispatch.use_pallas(backend):
        # kernel handles chunk clamping + seq padding internally
        ssd = dispatch.get_kernel("ssd_scan", backend)
        y = ssd(x, dt_, A, B, C, params["D"], chunk=mb.chunk,
                interpret=dispatch.interpret_default())
    else:
        # pad sequence to a chunk multiple
        chunk = min(mb.chunk, S) if S % mb.chunk else mb.chunk
        if S % chunk:
            pad = chunk - S % chunk
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
            C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt_ = jnp.pad(dt_, ((0, 0), (0, pad), (0, 0)))
        y = ssd_chunked(x, dt_, A, B, C, params["D"], chunk)[:, :S]
    y = y.reshape(b_, S, din)
    # gated RMSNorm (Mamba-2 norm-before-out_proj)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, params["out_norm"], cfg.norm_eps)
    return _proj(y, params["out_proj"],
                 lora=lora.get("out_proj") if lora else None, backend=backend)


def init_mamba_cache(cfg, batch: int, dtype) -> dict:
    mb = cfg.mamba
    return {
        "conv": jnp.zeros((batch, mb.conv_width - 1, conv_dim(cfg)), dtype),
        "ssm": jnp.zeros((batch, n_heads(cfg), mb.head_dim, mb.d_state),
                         jnp.float32),
    }


def mamba_decode(params: dict, cfg, u: jax.Array, cache: dict, *, lora=None):
    """Single-token recurrent step. u: (B, 1, d_model).

    Stays on the reference path regardless of ``cfg.kernel_backend``:
    one-token GEMMs are bandwidth-bound (see ``layers`` docstring).
    """
    mb = cfg.mamba
    din, h = d_inner(cfg), n_heads(cfg)
    proj = _proj(u, params["in_proj"],
                 lora=lora.get("in_proj") if lora else None)
    z, x, B, C, dt = _split_proj(cfg, proj)
    xbc = jnp.concatenate([x, B, C], axis=-1)[:, 0]               # (B, cd)
    conv_in = jnp.concatenate([cache["conv"], xbc[:, None]], axis=1)
    conv_out = jnp.sum(conv_in * params["conv_w"][None], axis=1) + params["conv_b"]
    xbc_t = jax.nn.silu(conv_out)                                 # (B, cd)
    # store in the cache's own dtype: conv_in promotes to the activation
    # dtype, and returning that would drift the cache aval step-over-step
    # (breaking donation and retracing the serving step)
    new_conv = conv_in[:, 1:].astype(cache["conv"].dtype)
    x_t, B_t, C_t = jnp.split(
        xbc_t, [din, din + mb.n_groups * mb.d_state], axis=-1)
    bsz = u.shape[0]
    x_t = x_t.reshape(bsz, h, mb.head_dim)
    B_t = jnp.repeat(B_t.reshape(bsz, mb.n_groups, mb.d_state),
                     h // mb.n_groups, axis=1)                    # (B,H,N)
    C_t = jnp.repeat(C_t.reshape(bsz, mb.n_groups, mb.d_state),
                     h // mb.n_groups, axis=1)
    dt_t = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    dA = jnp.exp(dt_t * A[None])                                  # (B,H)
    ssm = cache["ssm"] * dA[:, :, None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dt_t, x_t.astype(jnp.float32),
        B_t.astype(jnp.float32))
    y = jnp.einsum("bhpn,bhn->bhp", ssm, C_t.astype(jnp.float32))
    y = y + x_t.astype(jnp.float32) * params["D"][None, :, None]
    y = y.reshape(bsz, 1, din).astype(u.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, params["out_norm"], cfg.norm_eps)
    out = _proj(y, params["out_proj"],
                lora=lora.get("out_proj") if lora else None)
    return out, {"conv": new_conv, "ssm": ssm}
