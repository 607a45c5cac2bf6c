"""Pallas TPU kernel for the Mamba-2 chunked SSD forward (arXiv:2405.21060).

TPU adaptation of the SSD algorithm: the per-chunk quadratic term runs on
the MXU ((chunk × N) @ (N × chunk) and (chunk × chunk) @ (chunk × lanes)
matmuls); the cross-chunk recurrence exploits the TPU's *sequential* grid
execution — the running SSM states of the step's heads live in VMEM
scratch and are carried across grid steps along the chunk axis, so no
HBM round-trip for the state and no separate scan pass.

Layout: the model's own. x and y are (B, S, H·P) (a free reshape of
(B, S, H, P)), dt (B, S, H), B̃ and C̃ (B, S, G·N); A and D (H,). A grid
step takes a block of ``hb`` heads of one group (``ssd_layout`` derives
it from the shape), reads that group's B̃ and C̃ once, and computes the
chunk's C̃·B̃ᵀ once for all of them: every head of a group shares it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    LANE,
    VMEM_BUDGET_BYTES,
    BlockLayout,
    OperandLayout,
    round_up,
    sublane,
    tile_block_cap,
)

#: Widest head block, in lanes (hb·P). At Granite's 64 heads of 64 and
#: a 256-token chunk it is 32 heads: a 1-MiB bf16 x block, two head
#: blocks of the group per chunk. On a TPU v5e the kernel takes the same
#: time at 512 to 4,096 lanes; the cap keeps the block's VMEM small.
HEAD_LANES_CAP = 2048


def ssd_block_group(hi, hb: int, hpg: int):
    """The group of head block ``hi``: blocks of ``hb`` heads divide a
    group's ``hpg``, so a block never straddles two groups."""
    return hi * hb // hpg


def _slab(hb: int, p: int) -> int:
    """Lanes the kernel body handles at a time: the fewest whole heads
    that fill whole 128-lane tiles (two heads of 64), within the block."""
    return min(math.lcm(p, LANE), hb * p)


def _layout(bsz, s_pad, h, p, g, n, name, chunk, hb) -> BlockLayout:
    if (hb * p) % LANE == 0 or hb == h:
        x = OperandLayout((bsz, s_pad, h * p), (1, chunk, hb * p), name)
    else:    # one head a step: head-major, a block narrower than a tile
        x = OperandLayout((bsz, h, s_pad, p), (1, 1, chunk, p), name)
    if n % LANE == 0 or g == 1:
        bc = OperandLayout((bsz, s_pad, g * n), (1, chunk, n), name)
    else:    # a group's N columns narrower than a tile: group-major
        bc = OperandLayout((bsz, g, s_pad, n), (1, 1, chunk, n), name)
    slab = _slab(hb, p)
    scalar = OperandLayout((h,), (h,), "float32", memory="smem")
    return BlockLayout(
        kernel="ssd_scan",
        grid=(bsz, h // hb, s_pad // chunk),
        operands={
            "x": x,
            "dt": OperandLayout((bsz, s_pad, h), (1, chunk, h), "float32"),
            "b": bc,
            "c": bc,
            "a": scalar,
            "d": scalar,
        },
        outputs={"y": x},
        scratch=(OperandLayout((hb * p // slab, n, slab),
                               (hb * p // slab, n, slab), "float32"),))


def ssd_layout(bsz: int, s: int, h: int, p: int, g: int, n: int,
               dtype=jnp.float32, *, chunk: int = 128) -> BlockLayout:
    """Declared block layout of ``ssd_scan_bshp`` at one model-layout
    shape (the wrapper derives grid/padding/blocks from this; L003 lints
    it). The grid is (B, H/hb, S/chunk), the chunk axis last and
    sequential.

    The head block hb is the largest divisor of a group's H/G whose hb·P
    is a multiple of 128 lanes (or spans every head), at most
    ``HEAD_LANES_CAP`` lanes and within the VMEM budget (the least such
    divisor where none is). Where no divisor is lane-aligned (G = H, or
    a group too narrow to reach 128 lanes) a step takes one head, and x
    and y go head-major, (B, H, S, P); B̃ and C̃ go group-major,
    (B, G, S, N), where a group's N columns are narrower than a tile.

    dt rides whole across the heads, (chunk, H) a step: an hb-wide block
    of it would be narrower than a lane tile. The per-head decay/skip
    scalars a and d ride as whole (h,) float32 arrays in SMEM, indexed
    by the head inside the kernel body (a (1, 1) VMEM block would burn a
    full (8, 128) tile per head, and the TPU compiler refuses a (1, 1)
    block of an (h, 1) array). The chunk is capped to the granule-rounded
    sequence so ragged sequences pad instead of asserting."""
    chunk = tile_block_cap(chunk, s, sublane(dtype))
    s_pad = round_up(s, chunk)
    name = jnp.dtype(dtype).name
    hpg = h // g
    aligned = [hb for hb in range(hpg, 0, -1)
               if hpg % hb == 0 and ((hb * p) % LANE == 0 or hb == h)]
    for hb in aligned:
        lay = _layout(bsz, s_pad, h, p, g, n, name, chunk, hb)
        if (hb * p <= HEAD_LANES_CAP
                and lay.vmem_bytes() <= VMEM_BUDGET_BYTES):
            return lay
    return _layout(bsz, s_pad, h, p, g, n, name, chunk,
                   aligned[-1] if aligned else 1)


def _rows(ref, cols=slice(None)):
    """Index of a block's (rows, lanes) past its leading unit dims."""
    return (0,) * (ref.ndim - 2) + (slice(None), cols)


#: bfloat16 terms a float32 operand is split into where the other operand
#: of a matmul is exactly bfloat16 (x, B̃, C̃ or a 0/1 mask): the products
#: of the split terms are exact in the float32 accumulator, so 2 terms
#: carry 16 bits of the float32 operand and 3 carry all 24.
SPLIT = 2


def _split(v, parts):
    """float32 ``v`` as ``parts`` bfloat16 terms that sum to it (exactly
    at 3)."""
    out = []
    for _ in range(parts - 1):
        hi = v.astype(jnp.bfloat16)
        out.append(hi)
        v = v - hi.astype(jnp.float32)
    return out + [v.astype(jnp.bfloat16)]


def _dot(lhs, rhs, dims=(((1,), (0,)), ((), ())), parts=SPLIT):
    """``lhs`` · ``rhs`` in float32. Where one side is bfloat16 the other
    is split into ``parts`` bfloat16 terms, one MXU pass each."""
    f32 = jnp.float32
    if lhs.dtype == rhs.dtype:
        return jax.lax.dot_general(lhs, rhs, dims, preferred_element_type=f32)
    if lhs.dtype == jnp.bfloat16:
        terms = [(lhs, t) for t in _split(rhs, parts)]
    else:
        terms = [(t, rhs) for t in _split(lhs, parts)]
    out = None
    for l_, r_ in terms:
        t = jax.lax.dot_general(l_, r_, dims, preferred_element_type=f32)
        out = t if out is None else out + t
    return out


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref,
                state_ref, *, chunk: int, p: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)
    n_slabs, _, slab = state_ref.shape
    q = slab // p                                # heads per slab
    hb = n_slabs * q
    f32 = jnp.float32
    # the matmuls' bfloat16 side: x, B̃ and C̃ where they are bfloat16,
    # else float32 throughout
    low = jnp.bfloat16 if x_ref.dtype == jnp.bfloat16 else f32
    t_dims = (((0,), (0,)), ((), ()))            # lhsᵀ · rhs

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    dt = dt_ref[0]                               # (c, H) f32
    nh = dt.shape[1]
    bb = b_ref[_rows(b_ref)]                     # (c, N): this group's
    cc = c_ref[_rows(c_ref)]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    def mask(m):
        return jnp.where(m, 1.0, 0.0).astype(jnp.bfloat16)
    # every head's prefix sums of dt (Mosaic lowers no cumsum) and dt
    # transposed, exact: cdt (c, H) = cum_i, cdt_t (H, c) = cum_j, dt_t
    cdt = _dot(mask(ii >= jj), dt, parts=3)
    cdt_t = _dot(dt, mask(ii <= jj), t_dims, parts=3)
    dt_t = _dot(dt, mask(ii == jj), t_dims, parts=3)
    dt_end = jnp.sum(dt, axis=0, keepdims=True)  # (1, H) = cum_{c-1}
    # one C·Bᵀ for the whole block: its heads share the group's B and C
    scores = jnp.where(ii >= jj, _dot(cc, bb, (((1,), (1,)), ((), ()))),
                       0.0)                      # (c, c), causal
    col_head = jax.lax.broadcasted_iota(jnp.int32, (chunk, nh), 1)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (nh, chunk), 0)
    end_head = jax.lax.broadcasted_iota(jnp.int32, (1, nh), 1)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, slab), 1) // p

    def col(m, h):
        """Head ``h``'s column of a (c, H) array, (c, 1)."""
        return jnp.sum(jnp.where(col_head == h, m, 0.0), axis=1,
                       keepdims=True)

    def row(m, h):
        """Head ``h``'s row of an (H, c) array, (1, c)."""
        return jnp.sum(jnp.where(row_head == h, m, 0.0), axis=0,
                       keepdims=True)

    def per_lane(vals):
        """Each slab head's value on its own lanes."""
        out = vals[0]
        for j in range(1, q):
            out = jnp.where(lane_head == j, vals[j], out)
        return out

    def one_slab(k, carry):
        heads = [hi * hb + k * q + j for j in range(q)]
        a = [a_ref[h] for h in heads]            # SMEM scalars
        cum = [a[j] * col(cdt, h) for j, h in enumerate(heads)]     # (c, 1)
        cum_t = [a[j] * row(cdt_t, h) for j, h in enumerate(heads)]  # (1, c)
        cum_end = [a[j] * jnp.sum(jnp.where(end_head == h, dt_end, 0.0),
                                  axis=1, keepdims=True)
                   for j, h in enumerate(heads)]                    # (1, 1)
        # whole where one slab spans the block (maybe narrower than a tile)
        cols = slice(None) if n_slabs == 1 else \
            pl.ds(pl.multiple_of(k * slab, slab), slab)
        xs = x_ref[_rows(x_ref, cols)]           # (c, slab)
        x = xs.astype(f32)
        xl = xs.astype(low)
        st = state_ref[k]                        # (N, slab)
        # inter-chunk: the carried state, decayed to each position
        y = per_lane([jnp.exp(v) for v in cum]) * _dot(cc.astype(low), st)
        y += x * per_lane([d_ref[h] for h in heads])
        # intra-chunk: the shared scores, decayed per head and weighted by
        # its dt_j, times x (MXU); a head's product stays on its own lanes
        for j, h in enumerate(heads):
            w = scores * jnp.exp(jnp.minimum(cum[j] - cum_t[j], 0.0)) \
                * row(dt_t, h)                   # (c, c)
            yj = _dot(w, xl)
            y = y + yj if q == 1 else jnp.where(lane_head == j, y + yj, y)
        y_ref[_rows(y_ref, cols)] = y.astype(y_ref.dtype)
        # state update: decay the carried state over the chunk, add the
        # chunk's own contribution, x_j weighted by dt_j and its decay
        xw = x * per_lane([col(dt, h) * jnp.exp(e - v)
                           for h, e, v in zip(heads, cum_end, cum)])
        state_ref[k] = st * per_lane([jnp.exp(e) for e in cum_end]) \
            + _dot(bb.astype(low), xw, t_dims)   # (N, slab)
        return carry

    jax.lax.fori_loop(0, n_slabs, one_slab, 0)


def ssd_scan_bshp(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                  c: jax.Array, d: jax.Array, *, chunk: int = 128,
                  interpret: bool = False) -> jax.Array:
    """x: (B,S,H,P); dt: (B,S,H); a,d: (H,); b,c: (B,S,G,N) -> y like x.

    S need not divide ``chunk``: ragged sequences are zero-padded to the
    layout's padded length (dt = 0 rows contribute nothing to either the
    intra-chunk term or the state update) and the pad is sliced off."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    lay = ssd_layout(bsz, s, h, p, g, n, x.dtype, chunk=chunk)
    xl, bl = lay.operands["x"], lay.operands["b"]
    chunk, s_pad = xl.block[-2], xl.shape[-2]
    hb, hpg = h // lay.grid[1], h // g

    def operand(t, lo):
        """(B, S, k, m) padded to the layout's length, in its layout."""
        t = jnp.pad(t, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        return t.reshape(lo.shape) if len(lo.shape) == 3 \
            else jnp.swapaxes(t, 1, 2)

    def spec(lo, col):
        """Block ``col(head block)`` along the heads or groups."""
        if len(lo.shape) == 3:
            return pl.BlockSpec(lo.block,
                                lambda b_, h_, c_: (b_, c_, col(h_)))
        return pl.BlockSpec(lo.block,
                            lambda b_, h_, c_: (b_, col(h_), c_, 0))

    heads = spec(xl, lambda h_: h_)
    group = spec(bl, lambda h_: ssd_block_group(h_, hb, hpg))
    dt = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, s_pad - s), (0, 0)))
    # per-head scalars as whole (H,) f32 SMEM operands — see ssd_layout
    # (the kernel computes in f32, so the cast changes no value)
    a2 = a.reshape(h).astype(jnp.float32)
    d2 = d.reshape(h).astype(jnp.float32)

    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, p=p),
        grid=lay.grid,
        in_specs=[
            heads,
            pl.BlockSpec(lay.operands["dt"].block,
                         lambda b_, h_, c_: (b_, c_, 0)),
            group,
            group,
            pl.BlockSpec(memory_space=pltpu.SMEM),     # whole (H,) a
            pl.BlockSpec(memory_space=pltpu.SMEM),     # whole (H,) d
        ],
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct(xl.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM(lay.scratch[0].shape, jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(operand(x, xl), dt, operand(b, bl), operand(c, bl), a2, d2)
    if len(xl.shape) == 3:
        return y[:, :s].reshape(bsz, s, h, p)
    return jnp.swapaxes(y[:, :, :s], 1, 2)
