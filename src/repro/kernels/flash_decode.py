"""Pallas TPU flash decode: single-token attention over ragged per-slot
KV caches (the serving engine's hot step).

TARGET: TPU v5e. Validated on CPU via ``interpret=True`` against
``repro.kernels.ref.flash_decode_ref`` (= ``attend`` with
``kv_valid_len``).

Layout: q is (B, 1, H, hd) — one new token per serving slot; k/v are
(B, C, Hkv, hd) cache-resident with Hkv dividing H. The wrapper folds
the GQA mapping into the *grid*: q is reshaped to (B, Hkv, rep, hd)
with ``rep = H // Hkv`` padded up to the sublane granule, so the kv
head of every query row is the grid's head index — repeated K/V heads
never touch HBM, and the rep axis gives the single query token a real
sublane extent (a (1, hd) q block would waste a full (8, 128) tile per
head).

Raggedness: each slot's live prefix length arrives as ``kv_valid_len``
(B,) — one whole-array SMEM operand inside the kernel, indexed by the
slot's grid position (the TPU compiler refuses a (1, 1) block of a
(B, 1) array: a block's last two dims must be tile multiples or span
the array). Dead cache slots are
masked out of the softmax *probability* (not just the logit): a slot
with ``valid == 0`` keeps a zero denominator and emits exactly zeros,
matching ``attend``'s fully-masked-row rule rather than averaging
garbage cache entries.

The cache-block loop is the innermost grid dim; the running max /
denominator / accumulator live in VMEM scratch across grid steps
(split-K flash pattern). The v head dim may differ from the qk head
dim (absorbed-MLA decode attends latents: qk over rank+rope, v over
rank) — the accumulator is sized by v.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    NEG_INF,
    BlockLayout,
    OperandLayout,
    round_up,
    sublane,
    tile_block_cap,
)


def decode_layout(b: int, h: int, hkv: int, cap: int, hd: int,
                  vd: Optional[int] = None, dtype=jnp.float32, *,
                  block_k: int = 128) -> BlockLayout:
    """Declared block layout of ``flash_decode_bhrd`` at one shape.

    Single source of truth: the wrapper derives grid / padding /
    BlockSpecs from this and the L003 lint checks it. ``block_k`` (the
    cache-axis block) is capped to the granule-rounded capacity; the
    rep axis (= H // Hkv query rows per kv head) is padded to the
    sublane granule so the q block is tile-aligned."""
    vd = vd if vd is not None else hd
    g = sublane(dtype)
    rep_p = round_up(h // hkv, g)
    block_k = tile_block_cap(block_k, cap, g)
    cap_p = round_up(cap, block_k)
    name = jnp.dtype(dtype).name
    return BlockLayout(
        kernel="flash_decode",
        grid=(b, hkv, cap_p // block_k),
        operands={
            "q": OperandLayout((b, hkv, rep_p, hd), (1, 1, rep_p, hd), name),
            "k": OperandLayout((b, hkv, cap_p, hd), (1, 1, block_k, hd),
                               name),
            "v": OperandLayout((b, hkv, cap_p, vd), (1, 1, block_k, vd),
                               name),
            "kv_valid_len": OperandLayout((b,), (b,), "int32",
                                          memory="smem"),
        },
        outputs={"o": OperandLayout((b, hkv, rep_p, vd),
                                    (1, 1, rep_p, vd), name)},
        scratch=(OperandLayout((rep_p, 1), (rep_p, 1), "float32"),
                 OperandLayout((rep_p, 1), (rep_p, 1), "float32"),
                 OperandLayout((rep_p, vd), (rep_p, vd), "float32")))


def _decode_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, block_k: int):
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = valid_ref[bi]                                # this slot's length
    k_start = ki * block_k

    # skip cache blocks entirely past this slot's live prefix
    @pl.when(k_start < valid)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (rep, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, vd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (rep, bk)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kpos < valid
        # NEG_INF (not -inf): the shared finite masking constant keeps
        # exp(s - m_new) well-defined when a block is fully masked, and
        # the probability masking below zeroes those slots regardless
        m_prev = m_ref[...]                              # (rep, 1)
        m_cur = jnp.max(jnp.where(mask, s, NEG_INF), axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # mask the *probability*, not the logit: a fully-dead slot keeps
        # l == 0 (exp(NEG_INF - NEG_INF) == 1 would average garbage)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)     # (rep, bk)
        alpha = jnp.exp(m_prev - m_new)                  # (rep, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        # valid == 0 -> zero output (attend's fully-masked-row rule)
        o_ref[0, 0] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


def flash_decode_bhrd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      kv_valid_len: jax.Array,
                      scale: Optional[float] = None,
                      block_k: int = 128,
                      interpret: bool = False) -> jax.Array:
    """q: (B, 1, H, hd); k/v: (B, C, Hkv, hd|vd); kv_valid_len: (B,).

    Returns (B, 1, H, vd). The NEG_INF running-max init is private to
    the kernel (never survives into the output): dead slots are zeroed
    via the probability mask, not the logit value.
    """
    b, sq, h, hd = q.shape
    assert sq == 1, "flash_decode is single-token (one new token per slot)"
    cap, hkv = k.shape[1], k.shape[2]
    vd = v.shape[-1]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    lay = decode_layout(b, h, hkv, cap, hd, vd, q.dtype, block_k=block_k)
    block_k = lay.operands["k"].block[2]
    rep_p = lay.operands["q"].block[2]
    cap_p = lay.operands["k"].shape[2]

    # (B, 1, H, hd) -> (B, Hkv, rep, hd): query head h = kv*rep + r, so
    # the reshape groups each kv head's queries and the kv head becomes
    # a grid dim (same h // rep mapping as flash_attention, no repeat)
    qg = q.reshape(b, 1, hkv, rep, hd)[:, 0]
    if rep_p != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_p - rep), (0, 0)))
    kt = jnp.swapaxes(k, 1, 2)                           # (B, Hkv, C, hd)
    vt = jnp.swapaxes(v, 1, 2)                           # (B, Hkv, C, vd)
    if cap_p != cap:
        pad = ((0, 0), (0, 0), (0, cap_p - cap), (0, 0))
        kt, vt = jnp.pad(kt, pad), jnp.pad(vt, pad)
    valid = kv_valid_len.reshape(b).astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=lay.grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # whole (B,) array
            pl.BlockSpec((1, 1, rep_p, hd), lambda b_, h_, k_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b_, h_, k_: (b_, h_, k_, 0)),
            pl.BlockSpec((1, 1, block_k, vd),
                         lambda b_, h_, k_: (b_, h_, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep_p, vd),
                               lambda b_, h_, k_: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep_p, vd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep_p, 1), jnp.float32),
            pltpu.VMEM((rep_p, 1), jnp.float32),
            pltpu.VMEM((rep_p, vd), jnp.float32),
        ],
        interpret=interpret,
    )(valid, qg, kt, vt)
    return out[:, :, :rep].reshape(b, 1, h, vd)
