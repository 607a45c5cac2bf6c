"""jit'd public wrappers around the Pallas kernels.

These adapt model-layout tensors to kernel layouts ((B,S,H,D) <->
(B,H,S,D) transposes, chunk padding), expose an ``interpret`` flag so
CPU tests execute the kernel bodies in Python, and — because the model
hot path is *training* — attach a ``custom_vjp`` to every op: the
forward runs the Pallas kernel, the backward differentiates the
matching jnp reference in ``repro.kernels.ref`` (Pallas bodies have no
autodiff rules). Backward Pallas kernels are future work; see
DESIGN.md §10.

GQA K/V heads are NOT repeated here — ``flash_attention_bhsd`` indexes
kv heads inside its grid, so (B,S,Hkv,D) tensors go to the kernel
as-is and repeated heads never touch HBM. The SSD scan likewise takes
the model layout whole: B/C stay (B,S,G,N), indexed by group.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd, flash_layout
from repro.kernels.flash_decode import decode_layout, flash_decode_bhrd
from repro.kernels.lora_matmul import lora_layout
from repro.kernels.lora_matmul import lora_matmul as _lora_matmul
from repro.kernels.moe_ffn import moe_expert_ffn_ecd, moe_ffn_layout
from repro.kernels.ssd_scan import ssd_layout, ssd_scan_bshp


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, block_q, block_k, interpret):
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               scale=scale, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret):
    return _flash(q, k, v, causal, window, scale, block_q, block_k,
                  interpret), (q, k, v)


def _flash_bwd(causal, window, scale, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention_bshd_ref(
            q_, k_, v_, causal=causal, window=window, scale=scale),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Model layout: q (B,S,H,D); k/v (B,S,Hkv,D). Returns (B,S,H,D)."""
    return _flash(q, k, v, causal, window, scale, block_q, block_k,
                  interpret)


# ---------------------------------------------------------------------------
# SSD scan (Mamba-2)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, a, b, c, d, chunk, interpret):
    # the kernel reads the model layout: B/C by group, chunk capping and
    # ragged-seq padding in ssd_scan_bshp (it owns the block layout; see
    # ssd_layout)
    return ssd_scan_bshp(x, dt, a, b, c, d, chunk=chunk,
                         interpret=interpret)


def _ssd_fwd(x, dt, a, b, c, d, chunk, interpret):
    return _ssd(x, dt, a, b, c, d, chunk, interpret), (x, dt, a, b, c, d)


def _ssd_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(
        lambda *args: ref.ssd_scan_bshp_chunked_ref(*args, chunk=chunk),
        *res)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, d, *, chunk: int = 128,
             interpret: bool = False):
    """Model layout: x (B,S,H,P); dt (B,S,H); b/c (B,S,G,N); a/d (H,)."""
    return _ssd(x, dt, a, b, c, d, chunk, interpret)


# ---------------------------------------------------------------------------
# flash decode (single-token ragged-cache attention)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scale", "block_k",
                                             "interpret"))
def flash_decode(q, k, v, *, kv_valid_len, scale: Optional[float] = None,
                 block_k: int = 128, interpret: bool = False):
    """q: (B,1,H,hd); k/v: (B,C,Hkv,hd|vd) cache-resident;
    kv_valid_len (B,) masks each slot's dead cache entries.

    Inference-only (the serving/decode hot step) — no ``custom_vjp``:
    training attention goes through ``flash_attention``/``attend``.
    The v head dim may differ from the qk head dim (absorbed-MLA decode
    attends latents), so the output is (B, 1, H, vd)."""
    return flash_decode_bhrd(q, k, v, kv_valid_len=kv_valid_len,
                             scale=scale, block_k=block_k,
                             interpret=interpret)


# ---------------------------------------------------------------------------
# MoE grouped GEMM (batched expert SwiGLU)
# ---------------------------------------------------------------------------


def _moe_ref(buf, wg, wu, wd):
    # lazy: kernels -> models only at call time (no import cycle)
    from repro.models.moe import expert_ffn_reference
    return expert_ffn_reference(buf, wg, wu, wd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _moe(buf, wg, wu, wd, block_c, block_f, interpret):
    return moe_expert_ffn_ecd(buf, wg, wu, wd, block_c=block_c,
                              block_f=block_f, interpret=interpret)


def _moe_fwd(buf, wg, wu, wd, block_c, block_f, interpret):
    return _moe(buf, wg, wu, wd, block_c, block_f,
                interpret), (buf, wg, wu, wd)


def _moe_bwd(block_c, block_f, interpret, res, g):
    _, vjp = jax.vjp(_moe_ref, *res)
    return vjp(g)


_moe.defvjp(_moe_fwd, _moe_bwd)


def moe_expert_ffn(buf, wg, wu, wd, *, constrain=None,
                   block_c: int = 128, block_f: int = 256,
                   interpret: bool = False):
    """buf: (E,C,d); wg/wu: (E,d,ff); wd: (E,ff,d) -> (E,C,d).

    ``constrain`` (the reference path's hidden-activation sharding hook)
    is accepted and ignored: the grouped GEMM never materializes the
    (E,C,ff) hidden in HBM, so there is nothing to constrain. Lives in
    the *training* path (moe_block), so the Pallas forward pairs with
    the jnp reference backward. Not top-level jitted — ``constrain`` is
    an unhashable lambda at the call sites, which all sit inside jit
    already."""
    del constrain
    return _moe(buf, wg, wu, wd, block_c, block_f, interpret)


# ---------------------------------------------------------------------------
# fused frozen-weight + LoRA matmul
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _lora(x, w, a, b, scaling, block_m, block_n, block_k, interpret):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = _lora_matmul(x2, w, a, b, scaling=scaling, block_m=block_m,
                     block_n=block_n, block_k=block_k, interpret=interpret)
    return y.reshape(*lead, w.shape[1])


def _lora_fwd(x, w, a, b, scaling, block_m, block_n, block_k, interpret):
    return _lora(x, w, a, b, scaling, block_m, block_n, block_k,
                 interpret), (x, w, a, b, scaling)


def _lora_bwd(block_m, block_n, block_k, interpret, res, g):
    x, w, a, b, scaling = res
    _, vjp = jax.vjp(
        lambda x_, w_, a_, b_, s_: ref.lora_matmul_ref(
            x_, w_, a_, b_, scaling=s_),
        x, w, a, b, scaling)
    return vjp(g)


_lora.defvjp(_lora_fwd, _lora_bwd)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k", "interpret"))
def lora_matmul(x, w, a, b, *, scaling=1.0, block_m: Optional[int] = None,
                block_n: Optional[int] = None,
                block_k: Optional[int] = None, interpret: bool = False):
    """x: (..., K) any leading dims; w (K,N); a (K,r); b (r,N).

    ``scaling`` = alpha/r (``lora_scaling``). It is a traced operand —
    runs differing only in alpha share one compiled kernel. Blocks left
    ``None`` are derived from the shape (``lora_layout``).
    """
    scaling = jnp.asarray(scaling, jnp.float32)
    return _lora(x, w, a, b, scaling, block_m, block_n, block_k, interpret)


# ---------------------------------------------------------------------------
# Layout adapters (L003 lint): map each kernel's MODEL-layout call
# signature — the same named avals the kernel contracts trace — to its
# declared BlockLayout. Registered via dispatch.declare_kernel_layout.
# ---------------------------------------------------------------------------


def flash_attention_layout(q, k, v, **kwargs):
    """BlockLayout of ``flash_attention`` for model-layout avals."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    return flash_layout(b, h, hkv, s, d, q.dtype,
                        block_q=kwargs.get("block_q", 128),
                        block_k=kwargs.get("block_k", 128))


def lora_matmul_layout(x, w, a, b, **kwargs):
    """BlockLayout of ``lora_matmul`` for model-layout avals."""
    m = math.prod(x.shape[:-1])
    return lora_layout(m, x.shape[-1], w.shape[1], a.shape[1], x.dtype,
                       block_m=kwargs.get("block_m"),
                       block_n=kwargs.get("block_n"),
                       block_k=kwargs.get("block_k"))


def ssd_scan_layout(x, dt, a, b, c, d, **kwargs):
    """BlockLayout of ``ssd_scan`` for model-layout avals."""
    bsz, s, h, p = x.shape
    return ssd_layout(bsz, s, h, p, b.shape[2], b.shape[3], x.dtype,
                      chunk=kwargs.get("chunk", 128))


def flash_decode_layout(q, k, v, **kwargs):
    """BlockLayout of ``flash_decode`` for model-layout avals
    (``kv_valid_len`` is an operand, not a layout input)."""
    b, _, h, hd = q.shape
    cap, hkv = k.shape[1], k.shape[2]
    return decode_layout(b, h, hkv, cap, hd, v.shape[-1], q.dtype,
                         block_k=kwargs.get("block_k", 128))


def moe_expert_ffn_layout(buf, wg, wu, wd, **kwargs):
    """BlockLayout of ``moe_expert_ffn`` for model-layout avals."""
    e, c, d = buf.shape
    return moe_ffn_layout(e, c, d, wg.shape[-1], buf.dtype,
                          block_c=kwargs.get("block_c", 128),
                          block_f=kwargs.get("block_f", 256))
