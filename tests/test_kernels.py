"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies in Python on CPU)."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.lowered.layout_lint import lint_layout
from repro.kernels import ops, ref
from repro.kernels.common import VMEM_BUDGET_BYTES
from repro.kernels.lora_matmul import lora_layout
from repro.kernels.ssd_scan import (SPLIT, _split, ssd_block_group,
                                     ssd_layout)


def _assert_close(got, want, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,h,hkv,d,blk", [
    (64, 4, 4, 32, 32),     # MHA (h/hkv = 1)
    (96, 4, 2, 32, 32),     # GQA, non-multiple of block
    (64, 4, 1, 32, 32),     # GQA h/hkv = 4 (in-grid kv-head indexing)
    (128, 2, 1, 64, 64),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 16),
                                           (False, None)])
def test_flash_attention(s, h, hkv, d, blk, dtype, causal, window):
    key = jax.random.PRNGKey(s + h)
    b = 2
    q = jax.random.normal(key, (b, s, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=blk, block_k=blk, interpret=True)
    kk = jnp.repeat(k, h // hkv, 2)
    vv = jnp.repeat(v, h // hkv, 2)
    want = ref.flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(kk, 1, 2),
        jnp.swapaxes(vv, 1, 2), causal=causal, window=window)
    _assert_close(got, jnp.swapaxes(want, 1, 2), dtype)


def test_flash_attention_unequal_blocks_keep_all_keys():
    """block_q != block_k with ragged s: padding must cover a common
    multiple of both blocks (padding to only the larger one used to
    truncate the kv grid and silently drop trailing keys)."""
    key = jax.random.PRNGKey(11)
    b, s, h, d = 1, 40, 2, 32
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d))
    # block_q clamps to 40, block_k stays 16: old padding logic gave
    # nk = 40 // 16 = 2 and never visited keys 32..39
    got = ops.flash_attention(q, k, v, causal=True, block_q=64,
                              block_k=16, interpret=True)
    want = ref.attention_bshd_ref(q, k, v, causal=True)
    _assert_close(got, want, jnp.float32)


# ---------------------------------------------------------------------------
# SSD scan (Mamba-2)
# ---------------------------------------------------------------------------

def _ssd_operands(b, s, hh, p, n, g, dtype=jnp.float32, seed=0):
    """Model-layout SSD operands: x (B,S,H,P), dt (B,S,H), a (H,),
    B/C (B,S,G,N), d (H,)."""
    key = jax.random.PRNGKey(seed)
    x = (jax.random.normal(key, (b, s, hh, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, s, hh)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (hh,)) * 0.3)
    bb = (jax.random.normal(jax.random.fold_in(key, 3), (b, s, g, n)) * 0.5
          ).astype(dtype)
    cc = (jax.random.normal(jax.random.fold_in(key, 4), (b, s, g, n)) * 0.5
          ).astype(dtype)
    d = jax.random.normal(jax.random.fold_in(key, 5), (hh,))
    return x, dt, a, bb, cc, d


@pytest.mark.parametrize("s,hh,p,n,g,chunk", [
    (64, 4, 16, 8, 2, 16),
    (64, 2, 32, 16, 1, 32),
    (48, 4, 16, 8, 4, 16),   # padding path (48 % 16 == 0 but chunk=16)
    (50, 2, 16, 8, 2, 16),   # ragged seq -> pad
    (50, 8, 64, 16, 1, 16),  # G=1, H=8: one block of all 8 heads
    (40, 64, 64, 16, 1, 16),  # G=1, H=64 (Granite's heads): 2 blocks of 32
    (50, 8, 64, 128, 2, 16),  # G=2: a block of 4 heads per group
    (50, 4, 64, 8, 4, 16),   # G=H: one head a step, group-major B/C
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(s, hh, p, n, g, chunk, dtype):
    x, dt, a, bb, cc, d = _ssd_operands(2, s, hh, p, n, g, dtype, s * hh)
    got = ops.ssd_scan(x, dt, a, bb, cc, d, chunk=chunk, interpret=True)
    bt = jnp.repeat(jnp.swapaxes(bb, 1, 2), hh // g, 1)
    ct = jnp.repeat(jnp.swapaxes(cc, 1, 2), hh // g, 1)
    want = ref.ssd_scan_ref(jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2),
                            a, bt, ct, d)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(jnp.swapaxes(want, 1, 2),
                                          np.float32), rtol=tol, atol=tol)
    # and the chunked form the model's reference backend runs
    f32 = [t.astype(jnp.float32) for t in (x, bb, cc)]
    chunked = ref.ssd_scan_bshp_chunked_ref(f32[0], dt, a, f32[1], f32[2], d,
                                            chunk=chunk)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(chunked), rtol=tol, atol=tol)


@pytest.mark.parametrize("s,hh,p,n,g", [
    (50, 8, 64, 16, 1),
    (50, 8, 64, 128, 2),
    (50, 4, 64, 8, 4),
])
def test_ssd_scan_grad_is_the_chunked_reference_vjp(s, hh, p, n, g):
    """The kernel's backward is the jnp chunked form's VJP on the same
    residuals: under a cotangent that does not depend on y, every
    input's gradient equals the chunked reference's."""
    args = _ssd_operands(2, s, hh, p, n, g, seed=3)
    w = jax.random.normal(jax.random.PRNGKey(4), args[0].shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a, chunk=16) * w)

    kernel = functools.partial(ops.ssd_scan, interpret=True)
    got = jax.grad(loss(kernel), argnums=range(6))(*args)
    want = jax.grad(loss(ref.ssd_scan_bshp_chunked_ref),
                    argnums=range(6))(*args)
    for gk, gr in zip(got, want):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


def test_ssd_layout_at_granite_shape():
    """Granite 4.0-H Micro's round call (2 clients x 2 x 512 tokens, 64
    heads of 64, one group of state 128, chunk 256): blocks of 32 heads
    (2048 lanes), two a chunk, B and C read by group and never
    repeated."""
    lay = ssd_layout(4, 512, 64, 64, 1, 128, jnp.bfloat16, chunk=256)
    assert lay.grid == (4, 2, 2)
    assert lay.operands["x"].shape == (4, 512, 64 * 64)
    assert lay.operands["x"].block == (1, 256, 32 * 64)
    assert lay.outputs["y"].block == (1, 256, 32 * 64)
    assert lay.operands["dt"].block == (1, 256, 64)
    for name in ("b", "c"):
        assert lay.operands[name].shape == (4, 512, 128)
        assert lay.operands[name].block == (1, 256, 128)
    assert [ssd_block_group(hi, 32, 64) for hi in range(2)] == [0, 0]
    assert lint_layout(lay) == []


@pytest.mark.parametrize("h,p,g,n", [
    (64, 64, 1, 128),   # granite-4.0-h-micro
    (80, 64, 1, 128),   # mamba2-2.7b
    (128, 64, 1, 16),   # jamba-v0.1's Mamba widths
    (8, 32, 1, 16),     # the reduced configs
    (64, 64, 8, 128),   # 8 heads a group
    (64, 64, 64, 128),  # G = H
    (64, 16, 4, 64),    # hb·P reaches 128 only at 8 of a group's 16
    (4, 16, 4, 8),      # G = H below a lane tile: one head a step
    (6, 32, 2, 16),     # 3 heads a group: no divisor fills 128 lanes
])
@pytest.mark.parametrize("s,chunk", [(512, 256), (2048, 256), (50, 16)])
def test_ssd_layout_derives_the_head_block_from_the_shape(h, p, g, n, s,
                                                          chunk):
    """The head block divides a group's heads (a block never straddles
    two groups) and fills whole 128-lane tiles in the model layout;
    where no divisor can, a step takes one head, head-major. Every
    layout passes the L003 lint."""
    hpg = h // g
    can_fill = any(hpg % k == 0 and (k * p % 128 == 0 or k == h)
                   for k in range(1, hpg + 1))
    for dtype in (jnp.float32, jnp.bfloat16):
        lay = ssd_layout(2, s, h, p, g, n, dtype, chunk=chunk)
        x = lay.operands["x"]
        hb = h // lay.grid[1]
        assert hpg % hb == 0
        if can_fill:
            assert x.shape[1:] == (x.shape[1], h * p)
            assert x.block[2] == hb * p and (hb * p % 128 == 0 or hb == h)
        else:
            assert hb == 1 and x.shape == (2, h, x.shape[2], p)
        assert lay.grid == (2, h // hb, x.shape[-2] // x.block[-2])
        for hi in range(h // hb):
            assert {k // hpg for k in range(hi * hb, (hi + 1) * hb)} == \
                {ssd_block_group(hi, hb, hpg)}
        assert lint_layout(lay) == [], lay


@pytest.mark.parametrize("parts", [SPLIT, 3])
def test_ssd_split_terms_sum_to_the_float32_operand(parts):
    """The kernel puts a float32 matmul operand through the MXU as
    bfloat16 terms: three sum to it exactly (the exact selections of dt
    and its prefix sums), the kernel's ``SPLIT`` keep 16 bits of it, from
    1e-6 to 1e6."""
    v = jax.random.normal(jax.random.PRNGKey(0), (256, 512)) \
        * jnp.logspace(-6, 6, 512)
    terms = _split(v, parts)
    assert [t.dtype for t in terms] == [jnp.bfloat16] * parts
    total = terms[0].astype(jnp.float32)
    for t in terms[1:]:
        total = total + t.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(total), np.asarray(v),
                               rtol=0 if parts == 3 else 2.0 ** -16, atol=0)


def test_ssd_model_layout_chunked_matches_sequential_oracle():
    """The registry's reference entry (chunked, what the model runs and
    what the kernel's VJP differentiates) equals the sequential
    recurrence oracle in model layout."""
    key = jax.random.PRNGKey(13)
    b, s, hh, p, n, g = 2, 50, 4, 16, 8, 2
    x = jax.random.normal(key, (b, s, hh, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, s, hh)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (hh,)) * 0.3)
    bb = jax.random.normal(jax.random.fold_in(key, 3), (b, s, g, n)) * 0.5
    cc = jax.random.normal(jax.random.fold_in(key, 4), (b, s, g, n)) * 0.5
    d = jax.random.normal(jax.random.fold_in(key, 5), (hh,))
    got = ref.ssd_scan_bshp_chunked_ref(x, dt, a, bb, cc, d, chunk=16)
    want = ref.ssd_scan_bshp_ref(x, dt, a, bb, cc, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_ssd_chunked_model_path_matches_ref():
    """The model's jnp chunked-SSD path equals the sequential recurrence."""
    from repro.models.mamba2 import ssd_chunked
    key = jax.random.PRNGKey(7)
    b, s, hh, p, n, g = 2, 64, 4, 16, 8, 2
    x = jax.random.normal(key, (b, s, hh, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, s, hh)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (hh,)) * 0.3)
    bb = jax.random.normal(jax.random.fold_in(key, 3), (b, s, g, n)) * 0.5
    cc = jax.random.normal(jax.random.fold_in(key, 4), (b, s, g, n)) * 0.5
    d = jax.random.normal(jax.random.fold_in(key, 5), (hh,))
    got = ssd_chunked(x, dt, a, bb, cc, d, chunk=16)
    bt = jnp.repeat(jnp.swapaxes(bb, 1, 2), hh // g, 1)
    ct = jnp.repeat(jnp.swapaxes(cc, 1, 2), hh // g, 1)
    want = jnp.swapaxes(
        ref.ssd_scan_ref(jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2),
                         a, bt, ct, d), 1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# fused LoRA matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,r,blk", [
    (64, 64, 64, 8, 32),
    (100, 96, 72, 4, 32),    # ragged everything -> padding path
    (128, 256, 128, 32, 64),
    # blk None: tiles derived from the shape
    (96, 448, 1152, 8, None),    # 2 column blocks of 640 reuse one x@A
    (8, 256, 384, 16, None),     # decode-like: m under one row block
    (1100, 200, 1152, 8, None),  # 2 row blocks of 552 x 2 column blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul(m, k, n, r, blk, dtype):
    key = jax.random.PRNGKey(m + n)
    x = jax.random.normal(key, (m, k), dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 0.1
         ).astype(dtype)
    a = (jax.random.normal(jax.random.fold_in(key, 2), (k, r)) * 0.1
         ).astype(dtype)
    b = (jax.random.normal(jax.random.fold_in(key, 3), (r, n)) * 0.1
         ).astype(dtype)
    blocks = {} if blk is None else dict(block_m=blk, block_n=blk,
                                         block_k=blk)
    got = ops.lora_matmul(x, w, a, b, **blocks, interpret=True)
    want = ref.lora_matmul_ref(x, w, a, b)
    _assert_close(got, want, dtype)


@pytest.mark.parametrize("m,n,max_steps", [
    (1024, 3584, 28),     # qwen2-7b q projection, one client's 2 x 512
    (1024, 512, 7),       # its v projection
    (8192, 3584, 224),    # the eval's q projection, 16 x 512 rows
    (64, 3584, 28),       # shared-adapter decode over 64 slots
])
def test_lora_layout_derives_tiles_from_the_shape(m, n, max_steps):
    """With no blocks named, the layout at qwen2-7b's widths (rank-32
    bf16 LoRA on d_model 3584) takes few grid steps, each above v5e's
    ridge of 240 FLOP/byte where the rows allow, and fits the declared
    VMEM budget; named blocks still win. No kernel runs."""
    lay = lora_layout(m, 3584, n, 32, jnp.bfloat16)
    assert math.prod(lay.grid) <= max_steps
    assert lay.vmem_bytes() <= VMEM_BUDGET_BYTES
    assert lint_layout(lay) == []
    bm, bk = lay.operands["x"].block
    bn = lay.operands["w"].block[1]
    if m == 64:
        assert bm == 64
    else:
        flops, bytes_ = 2 * bm * bk * bn, 2 * (bm * bk + bk * bn)
        assert flops / bytes_ >= 240
    named = lora_layout(m, 3584, n, 32, jnp.bfloat16, block_m=128,
                        block_n=128, block_k=128)
    assert named.grid == (-(-m // 128), n // 128, 28)


def test_lora_matmul_batched_leading_dims():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 8, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 32)) * 0.1
    a = jax.random.normal(jax.random.fold_in(key, 2), (64, 4)) * 0.1
    b = jax.random.normal(jax.random.fold_in(key, 3), (4, 32)) * 0.1
    got = ops.lora_matmul(x, w, a, b, block_m=16, block_n=16, block_k=32,
                          interpret=True)
    want = ref.lora_matmul_ref(x.reshape(-1, 64), w, a, b).reshape(2, 8, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# flash decode (single-token ragged-cache attention)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,hkv,hd,vd", [
    (4, 4, 32, 32),          # MHA
    (4, 2, 32, 32),          # GQA rep 2
    (4, 1, 32, 32),          # GQA rep 4 (h/hkv = 4)
    (1, 1, 32, 32),          # single head (h/hkv = 1)
    (4, 1, 48, 32),          # absorbed-MLA: qk rank+rope, v latent rank
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(h, hkv, hd, vd, dtype):
    key = jax.random.PRNGKey(h * 31 + hkv)
    b, cap = 4, 64
    q = jax.random.normal(key, (b, 1, h, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, cap, hkv, hd),
                          dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, cap, hkv, vd),
                          dtype)
    # ragged cursors: empty slot, single entry, mid-prefix, full cache
    valid = jnp.array([0, 1, 37, cap], jnp.int32)
    got = ops.flash_decode(q, k, v, kv_valid_len=valid, interpret=True)
    want = ref.flash_decode_ref(q, k, v, kv_valid_len=valid)
    assert got.shape == (b, 1, h, vd)
    _assert_close(got, want, dtype)
    # the empty slot (attend's fully-masked-row rule): exact zeros
    np.testing.assert_array_equal(np.asarray(got[0], np.float32), 0.0)


@pytest.mark.parametrize("block_k", [8, 16, 64, 128])
def test_flash_decode_block_sweep_and_ragged_cap(block_k):
    """Any block_k (incl. larger than the granule-rounded capacity,
    which caps) visits exactly the live prefix of a ragged capacity."""
    key = jax.random.PRNGKey(3)
    b, cap, h, hkv, hd = 2, 40, 4, 2, 32
    q = jax.random.normal(key, (b, 1, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, cap, hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, cap, hkv, hd))
    valid = jnp.array([17, 40], jnp.int32)
    got = ops.flash_decode(q, k, v, kv_valid_len=valid, block_k=block_k,
                           interpret=True)
    want = ref.flash_decode_ref(q, k, v, kv_valid_len=valid)
    _assert_close(got, want, jnp.float32)


def test_flash_decode_ring_wraparound_semantics():
    """After the ring-buffer cursor wraps, every cache slot is live
    (valid == cap) and attention covers the whole buffer, exactly as
    gqa_decode's `valid = min(pos + 1, cap)` produces."""
    key = jax.random.PRNGKey(9)
    b, cap, h, hkv, hd = 2, 16, 4, 2, 32
    q = jax.random.normal(key, (b, 1, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, cap, hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, cap, hkv, hd))
    pos = jnp.array([23, 16])                      # both wrapped past cap
    valid = jnp.minimum(pos + 1, cap)
    got = ops.flash_decode(q, k, v, kv_valid_len=valid, interpret=True)
    full = ref.flash_decode_ref(q, k, v,
                                kv_valid_len=jnp.full((b,), cap, jnp.int32))
    _assert_close(got, full, jnp.float32)


def test_flash_decode_scale_override():
    key = jax.random.PRNGKey(5)
    b, cap, h, hd = 2, 32, 2, 16
    q = jax.random.normal(key, (b, 1, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, cap, h, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, cap, h, hd))
    valid = jnp.array([5, 32], jnp.int32)
    got = ops.flash_decode(q, k, v, kv_valid_len=valid, scale=0.25,
                           interpret=True)
    want = ref.flash_decode_ref(q, k, v, kv_valid_len=valid, scale=0.25)
    _assert_close(got, want, jnp.float32)


# ---------------------------------------------------------------------------
# MoE grouped GEMM (batched expert SwiGLU)
# ---------------------------------------------------------------------------

def _moe_operands(e, c, d, ff, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    buf = jax.random.normal(key, (e, c, d), dtype)
    wg = (jax.random.normal(jax.random.fold_in(key, 1), (e, d, ff)) * 0.1
          ).astype(dtype)
    wu = (jax.random.normal(jax.random.fold_in(key, 2), (e, d, ff)) * 0.1
          ).astype(dtype)
    wd = (jax.random.normal(jax.random.fold_in(key, 3), (e, ff, d)) * 0.1
          ).astype(dtype)
    return buf, wg, wu, wd


@pytest.mark.parametrize("e,c,d,ff,bc,bf", [
    (4, 16, 128, 64, 128, 256),    # contract-family shape, default blocks
    (4, 16, 128, 64, 8, 128),      # small blocks -> multi-step ff loop
    (2, 20, 96, 72, 16, 128),      # ragged c/d/ff -> padding path
    (8, 64, 128, 256, 32, 128),    # wider ffn, several ff blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_expert_ffn(e, c, d, ff, bc, bf, dtype):
    from repro.models.moe import expert_ffn_reference
    buf, wg, wu, wd = _moe_operands(e, c, d, ff, dtype, seed=e + c)
    got = ops.moe_expert_ffn(buf, wg, wu, wd, block_c=bc, block_f=bf,
                             interpret=True)
    want = expert_ffn_reference(buf, wg, wu, wd)
    assert got.shape == (e, c, d)
    # kernel accumulates fp32 across ff blocks; the bf16 reference
    # accumulates in bf16 — wider ffn widens the rounding gap
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_moe_expert_ffn_empty_expert_exact_zeros():
    """A zero-filled capacity buffer (an expert no token routed to)
    must come out exactly zero — silu(0)*0 @ wd — not approximately."""
    buf, wg, wu, wd = _moe_operands(4, 16, 128, 64)
    buf = buf.at[1].set(0.0).at[3].set(0.0)
    got = ops.moe_expert_ffn(buf, wg, wu, wd, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[1]), 0.0)
    np.testing.assert_array_equal(np.asarray(got[3]), 0.0)
    assert float(jnp.abs(got[0]).max()) > 0          # live experts live


def test_moe_expert_ffn_grads_match_reference():
    """moe_block trains through this op: the custom_vjp backward (jnp
    reference) must match differentiating the reference directly, for
    every operand."""
    from repro.models.moe import expert_ffn_reference
    buf, wg, wu, wd = _moe_operands(2, 8, 32, 16, seed=7)

    def loss(fn, *operands):
        return jnp.sum(fn(*operands) ** 2)

    g_pal = jax.grad(
        lambda *o: loss(lambda *a: ops.moe_expert_ffn(*a, interpret=True),
                        *o), argnums=(0, 1, 2, 3))(buf, wg, wu, wd)
    g_ref = jax.grad(lambda *o: loss(expert_ffn_reference, *o),
                     argnums=(0, 1, 2, 3))(buf, wg, wu, wd)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
