"""Pallas TPU kernel: fused frozen-weight + LoRA matmul.

    y = x @ W + ((x @ A) @ B) * scaling

The serving/training hot spot of LoRA fine-tuning (paper's setting: every
W_q/W_v matmul carries an adapter). Fusing the rank-r bypass into the
main matmul's k-loop means x is read from HBM **once** — the adapter adds
2·r·(m+n) FLOPs per tile but zero extra activation traffic, instead of a
second kernel launch + extra read of x in the naive two-pass form.

Grid: (nm, nn, nk), k innermost. The main (bm × bn) product accumulates
in a float32 VMEM scratch over k. The (bm × r) x@A partial accumulates
in a second scratch over k on the first column block only, and every
later column block of the same rows reuses it; the B-side rank
contraction happens on each block's final k step.

Blocks are derived from the call's shape unless the caller or the tuning
cache names them (``lora_layout``): the fewest sublane-aligned row blocks
of at most 1024 and lane-aligned column and contraction blocks of at most
1024 and 512, within the VMEM budget. At qwen2-7b's q projection (1024 ×
3584 → 3584) that is 28 grid steps of (1024, 896, 512), where 128³ tiles
took 6272.

``scaling`` (alpha/r — see ``repro.models.layers.lora_scaling``) is a
**traced operand** carried as a (1, 1) SMEM scalar, not a compile-time
constant: runs with different alpha values share one compiled kernel.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    LANE,
    VMEM_BUDGET_BYTES,
    BlockLayout,
    OperandLayout,
    fewest_blocks,
    round_up,
    sublane,
    tile_block_cap,
)


#: Caps of the derived blocks (rows, output columns, contraction). A
#: (1024, 896, 512) bf16 step does 940 MFLOP over 1.9 MiB of x and w
#: tiles, 478 FLOP/byte, above v5e's ridge of 197 TFLOP/s / 819 GB/s =
#: 240; a 128^3 step reaches 57 FLOP/byte, and its fixed per-step cost
#: outweighs its 21 ns of MXU work. Of the tilings that fit the default scoped VMEM,
#: this one timed fastest on a TPU v5e at 1024 x 3584 -> 3584 (161.7 us,
#: against 2282 us at 128^3).
M_CAP = 1024
N_CAP = 1024
K_CAP = 512


def _layout(m, k, n, r, dtype, block_m, block_n, block_k) -> BlockLayout:
    mp = round_up(m, block_m)
    kp = round_up(k, block_k)
    np_ = round_up(n, block_n)
    name = jnp.dtype(dtype).name
    return BlockLayout(
        kernel="lora_matmul",
        grid=(mp // block_m, np_ // block_n, kp // block_k),
        operands={
            "x": OperandLayout((mp, kp), (block_m, block_k), name),
            "w": OperandLayout((kp, np_), (block_k, block_n), name),
            "a": OperandLayout((kp, r), (block_k, r), name),
            "b": OperandLayout((r, np_), (r, block_n), name),
            "scaling": OperandLayout((1, 1), (1, 1), "float32",
                                     memory="smem"),
        },
        outputs={"o": OperandLayout((mp, np_), (block_m, block_n), name)},
        scratch=(OperandLayout((block_m, block_n), (block_m, block_n),
                               "float32"),
                 OperandLayout((block_m, r), (block_m, r), "float32")))


def lora_layout(m: int, k: int, n: int, r: int, dtype=jnp.float32, *,
                block_m: Optional[int] = None,
                block_n: Optional[int] = None,
                block_k: Optional[int] = None) -> BlockLayout:
    """Declared block layout of ``lora_matmul`` at one shape (the
    wrapper derives grid/padding/blocks from this; L003 lints it).

    A block the caller names is capped to its dim: ``block_m`` is only
    ever a sublane (x and out rows) so it caps to the sublane granule;
    ``block_k``/``block_n`` each appear as a lane dim (x cols / w+b+out
    cols) so they cap to LANE multiples. A block left ``None`` is
    derived from the shape: the fewest blocks of at most ``M_CAP``,
    ``N_CAP`` or ``K_CAP`` that cover the dim, with the row block halved
    until the footprint fits ``VMEM_BUDGET_BYTES``."""
    g = sublane(dtype)
    block_n = (tile_block_cap(block_n, n, LANE) if block_n
               else fewest_blocks(n, N_CAP, LANE))
    block_k = (tile_block_cap(block_k, k, LANE) if block_k
               else fewest_blocks(k, K_CAP, LANE))
    if block_m:
        return _layout(m, k, n, r, dtype, tile_block_cap(block_m, m, g),
                       block_n, block_k)
    cap = M_CAP
    while True:
        lay = _layout(m, k, n, r, dtype, fewest_blocks(m, cap, g),
                      block_n, block_k)
        if cap <= g or lay.vmem_bytes() <= VMEM_BUDGET_BYTES:
            return lay
        cap = round_up(cap // 2, g)


def _lora_kernel(x_ref, w_ref, a_ref, b_ref, s_ref, o_ref, acc_ref, xa_ref):
    # the grid runs (i, j, k) in order, j and k sequential (the default
    # dimension semantics): x@A depends on the row block i alone, so it
    # accumulates over k on the first column block and every later j
    # reuses it
    j = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((j == 0) & (ki == 0))
    def _init_xa():
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]
    acc_ref[...] += jax.lax.dot(x, w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _xa():
        xa_ref[...] += jax.lax.dot(x, a_ref[...],
                                   preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        lora = jax.lax.dot(xa_ref[...].astype(b_ref.dtype), b_ref[...],
                           preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + s_ref[0, 0] * lora).astype(o_ref.dtype)


def lora_matmul(x: jax.Array, w: jax.Array, a: jax.Array, b: jax.Array, *,
                scaling=1.0, block_m: Optional[int] = None,
                block_n: Optional[int] = None,
                block_k: Optional[int] = None,
                interpret: bool = False) -> jax.Array:
    """x: (M, K); w: (K, N); a: (K, r); b: (r, N) -> (M, N).

    ``scaling`` may be a Python float or a traced scalar (alpha/r).
    Blocks left ``None`` are derived from the shape (``lora_layout``).
    """
    m, k = x.shape
    _, n = w.shape
    r = a.shape[1]
    lay = lora_layout(m, k, n, r, x.dtype, block_m=block_m,
                      block_n=block_n, block_k=block_k)
    block_m, block_k = lay.operands["x"].block
    block_n = lay.operands["w"].block[1]

    def pad_to(arr, ax, mult):
        sz = arr.shape[ax]
        pad = (-sz) % mult
        if not pad:
            return arr
        width = [(0, 0)] * arr.ndim
        width[ax] = (0, pad)
        return jnp.pad(arr, width)

    xp = pad_to(pad_to(x, 0, block_m), 1, block_k)
    wp = pad_to(pad_to(w, 0, block_k), 1, block_n)
    ap = pad_to(a, 0, block_k)
    bp = pad_to(b, 1, block_n)
    mp, np_ = lay.outputs["o"].shape
    sc = jnp.asarray(scaling, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        _lora_kernel,
        grid=lay.grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k_: (i, k_)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k_: (k_, j)),
            pl.BlockSpec((block_k, r), lambda i, j, k_: (k_, 0)),
            pl.BlockSpec((r, block_n), lambda i, j, k_: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, k_: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k_: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, r), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wp, ap, bp, sc)
    return out[:m, :n]
