"""Pallas TPU grouped GEMM for the MoE expert FFN: batched SwiGLU over
per-expert capacity buffers, (E, C, d) -> (E, C, d).

TARGET: TPU v5e. Validated on CPU via ``interpret=True`` against
``repro.models.moe.expert_ffn_reference``.

The expert axis is a grid dim — each grid step multiplies one expert's
capacity block against that expert's weight slices, so the batched
einsum becomes E independent GEMMs with no one-hot dispatch FLOPs
(matching the gather/scatter dispatch path this kernel slots under).
The FFN axis is the innermost grid dim: the (block_c, d) output
accumulator lives in VMEM scratch across ff blocks, gate and up
projections are computed per ff-block and immediately contracted with
the matching down-projection slice — the (C, ff) hidden activation is
never materialized in HBM.

Empty expert groups (zero-filled capacity rows) stay exactly zero:
``silu(0) * 0 @ wd == 0``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    BlockLayout,
    OperandLayout,
    round_up,
    sublane,
    tile_block_cap,
)


def moe_ffn_layout(e: int, c: int, d: int, ff: int, dtype=jnp.float32, *,
                   block_c: int = 128, block_f: int = 256) -> BlockLayout:
    """Declared block layout of ``moe_expert_ffn_ecd`` at one shape.

    Single source of truth: the wrapper derives grid / padding /
    BlockSpecs from this and the L003 lint checks it. ``block_c`` (the
    capacity tile) caps to the granule-rounded capacity; ``block_f``
    (the FFN tile) caps to the LANE-rounded FFN width so the hidden
    blocks stay lane-aligned. d (the model width) is padded to the
    sublane granule — it is the *sublane* dim of the weight blocks and
    the (full) lane dim of the activation blocks."""
    g = sublane(dtype)
    block_c = tile_block_cap(block_c, c, g)
    block_f = tile_block_cap(block_f, ff, 128)
    c_p = round_up(c, block_c)
    f_p = round_up(ff, block_f)
    d_p = round_up(d, g)
    name = jnp.dtype(dtype).name
    wgate = OperandLayout((e, d_p, f_p), (1, d_p, block_f), name)
    return BlockLayout(
        kernel="moe_expert_ffn",
        grid=(e, c_p // block_c, f_p // block_f),
        operands={
            "buf": OperandLayout((e, c_p, d_p), (1, block_c, d_p), name),
            "wg": wgate,
            "wu": wgate,
            "wd": OperandLayout((e, f_p, d_p), (1, block_f, d_p), name),
        },
        outputs={"o": OperandLayout((e, c_p, d_p), (1, block_c, d_p), name)},
        scratch=(OperandLayout((block_c, d_p), (block_c, d_p), "float32"),))


def _moe_ffn_kernel(buf_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref):
    fi = pl.program_id(2)
    nf = pl.num_programs(2)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = buf_ref[0].astype(jnp.float32)               # (bc, d)
    wg = wg_ref[0].astype(jnp.float32)               # (d, bf)
    wu = wu_ref[0].astype(jnp.float32)               # (d, bf)
    wd = wd_ref[0].astype(jnp.float32)               # (bf, d)
    gate = jax.lax.dot(x, wg, preferred_element_type=jnp.float32)
    up = jax.lax.dot(x, wu, preferred_element_type=jnp.float32)
    h = jax.nn.silu(gate) * up                       # (bc, bf)
    acc_ref[...] += jax.lax.dot(h, wd, preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_expert_ffn_ecd(buf: jax.Array, wg: jax.Array, wu: jax.Array,
                       wd: jax.Array, *, block_c: int = 128,
                       block_f: int = 256,
                       interpret: bool = False) -> jax.Array:
    """buf: (E, C, d); wg/wu: (E, d, ff); wd: (E, ff, d) -> (E, C, d).

    Ragged C / d / ff are zero-padded to the layout's padded dims (zero
    rows and columns contribute nothing through the SwiGLU chain) and
    sliced off."""
    e, c, d = buf.shape
    ff = wg.shape[-1]
    lay = moe_ffn_layout(e, c, d, ff, buf.dtype,
                         block_c=block_c, block_f=block_f)
    block_c = lay.operands["buf"].block[1]
    block_f = lay.operands["wg"].block[2]
    c_p, d_p = lay.operands["buf"].shape[1:]
    f_p = lay.operands["wg"].shape[2]
    if (c_p, d_p) != (c, d):
        buf = jnp.pad(buf, ((0, 0), (0, c_p - c), (0, d_p - d)))
    if (d_p, f_p) != (d, ff):
        wpad = ((0, 0), (0, d_p - d), (0, f_p - ff))
        wg, wu = jnp.pad(wg, wpad), jnp.pad(wu, wpad)
        wd = jnp.pad(wd, ((0, 0), (0, f_p - ff), (0, d_p - d)))

    out = pl.pallas_call(
        _moe_ffn_kernel, name="moe_expert_ffn",
        grid=lay.grid,
        in_specs=[
            pl.BlockSpec((1, block_c, d_p), lambda e_, c_, f_: (e_, c_, 0)),
            pl.BlockSpec((1, d_p, block_f), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, d_p, block_f), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, block_f, d_p), lambda e_, c_, f_: (e_, f_, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, d_p),
                               lambda e_, c_, f_: (e_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((e, c_p, d_p), buf.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, d_p), jnp.float32)],
        interpret=interpret,
    )(buf, wg, wu, wd)
    return out[:, :c, :d]
