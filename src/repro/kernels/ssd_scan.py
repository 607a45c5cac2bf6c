"""Pallas TPU kernel for the Mamba-2 chunked SSD forward (arXiv:2405.21060).

TPU adaptation of the SSD algorithm: the per-chunk quadratic term runs on
the MXU ((chunk × N) @ (N × chunk) and (chunk × chunk) @ (chunk × P)
matmuls); the cross-chunk recurrence exploits the TPU's *sequential* grid
execution — the running SSM state (P × N) lives in VMEM scratch and is
carried across grid steps along the chunk axis, so no HBM round-trip for
the state and no separate scan pass.

Layout: x (B, H, S, P); dt (B, H, S); B̃/C̃ (B, H, S, N) (kv-group
repeated by the caller); A (H,); D (H,). chunk must divide S.
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


def ssd_layout(bsz: int, h: int, s: int, p: int, n: int,
               dtype=jnp.float32, *, chunk: int = 128) -> BlockLayout:
    """Declared block layout of ``ssd_scan_bhsp`` at one shape (the
    wrapper derives grid/padding/blocks from this; L003 lints it).

    The per-head decay/skip scalars a and d ride as whole (h,) float32
    arrays in SMEM, indexed by the head's grid position: they are
    scalars inside the kernel body, a (1, 1, 1, 1) VMEM block would
    burn a full (8, 128) tile per head, and the TPU compiler refuses a
    (1, 1) block of an (h, 1) array (a block's last two dims must be
    tile multiples or span the array). The chunk is capped to the
    granule-rounded sequence so ragged sequences pad instead of
    asserting."""
    g = sublane(dtype)
    chunk = tile_block_cap(chunk, s, g)
    s_pad = round_up(s, chunk)
    name = jnp.dtype(dtype).name
    scalar = OperandLayout((h,), (h,), "float32", memory="smem")
    return BlockLayout(
        kernel="ssd_scan",
        grid=(bsz, h, s_pad // chunk),
        operands={
            "x": OperandLayout((bsz, h, s_pad, p), (1, 1, chunk, p), name),
            "dt": OperandLayout((bsz, h, s_pad, 1), (1, 1, chunk, 1), name),
            "b": OperandLayout((bsz, h, s_pad, n), (1, 1, chunk, n), name),
            "c": OperandLayout((bsz, h, s_pad, n), (1, 1, chunk, n), name),
            "a": scalar,
            "d": scalar,
        },
        outputs={"y": OperandLayout((bsz, h, s_pad, p), (1, 1, chunk, p),
                                    name)},
        scratch=(OperandLayout((p, n), (p, n), "float32"),))


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref,
                state_ref, *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (c, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (c, 1)
    bb = b_ref[0, 0].astype(jnp.float32)         # (c, N)
    cc = c_ref[0, 0].astype(jnp.float32)         # (c, N)
    a = a_ref[hi]                                # SMEM scalar of this head
    dd = d_ref[hi]

    da = dt * a                                  # (c,1), negative
    # prefix sums and row views by masked reductions: Mosaic lowers
    # neither cumsum nor the transpose of a (c, 1) column
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = ii == jj
    cum_row = jnp.sum(jnp.where(ii <= jj, da, 0.0), axis=0,
                      keepdims=True)             # (1,c) = cum_j
    cum = jnp.sum(jnp.where(eye, cum_row, 0.0), axis=1,
                  keepdims=True)                 # (c,1) = cum_i
    dt_row = jnp.sum(jnp.where(eye, dt, 0.0), axis=0, keepdims=True)
    # ---- intra-chunk quadratic term (MXU) ----------------------------
    diff = cum - cum_row                         # (c, c) = cum_i - cum_j
    l_mat = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(
        cc, bb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # (c, c)
    w = scores * l_mat * dt_row                  # weight by dt_j
    y = jax.lax.dot(w, x, preferred_element_type=jnp.float32)
    # ---- inter-chunk: contract cached state --------------------------
    y += jnp.exp(cum) * jax.lax.dot_general(
        cc, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # (c,N)@(P,N)^T -> (c,P)
    y += x * dd
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # ---- state update -------------------------------------------------
    cum_end = jnp.sum(da, axis=0, keepdims=True)  # (1,1) = cum_{c-1}
    total = jnp.exp(cum_end)
    decay_to_end = jnp.exp(cum_end - cum)        # (c,1)
    xw = x * (dt * decay_to_end)                 # (c,P)
    state_ref[...] = state_ref[...] * total + jax.lax.dot_general(
        xw, bb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (P,N)


def ssd_scan_bhsp(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                  c: jax.Array, d: jax.Array, *, chunk: int = 128,
                  interpret: bool = False) -> jax.Array:
    """x: (B,H,S,P); dt: (B,H,S); a,d: (H,); b,c: (B,H,S,N) -> y like x.

    S need not divide ``chunk``: ragged sequences are zero-padded to the
    layout's padded length (dt = 0 rows contribute nothing to either the
    intra-chunk term or the state update) and the pad is sliced off."""
    bsz, h, s, p = x.shape
    n = b.shape[-1]
    lay = ssd_layout(bsz, h, s, p, n, x.dtype, chunk=chunk)
    chunk = lay.operands["x"].block[2]
    s_pad = lay.operands["x"].shape[2]
    dt2 = dt[..., None]                              # (B,H,S,1)
    if s_pad != s:
        pad = ((0, 0), (0, 0), (0, s_pad - s), (0, 0))
        x, dt2, b, c = (jnp.pad(t, pad) for t in (x, dt2, b, c))
    # per-head scalars as whole (H,) f32 SMEM operands — see ssd_layout
    # (the kernel computes in f32, so the cast changes no value)
    a2 = a.reshape(h).astype(jnp.float32)
    d2 = d.reshape(h).astype(jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=lay.grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),     # whole (H,) a
            pl.BlockSpec(memory_space=pltpu.SMEM),     # whole (H,) d
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda b_, h_, c_: (b_, h_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, s_pad, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, dt2, b, c, a2, d2)
    return y[:, :, :s]
