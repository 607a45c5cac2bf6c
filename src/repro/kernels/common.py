"""Constants shared by the Pallas kernels, the jnp references, and the
model layers.

``NEG_INF`` is the additive masking value used by every attention /
scan implementation in the repo. It is deliberately a large *finite*
float32 (not ``-inf``): ``exp(NEG_INF - NEG_INF) == 1`` keeps
fully-masked softmax rows NaN-free, and finite values survive bf16
round-trips without collapsing to ``-inf`` (whose gradients poison
``jnp.where`` branches). Keep model code, ``ref.py`` and the kernels on
this single constant so the masked logits — and therefore the round-log
pins — can never drift between backends.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

NEG_INF = -1e30

# --------------------------------------------------------------------------
# TPU tiling geometry (shared by the kernels and the L003 layout lint)
# --------------------------------------------------------------------------

#: TPU vector lane count — the last dim of every VMEM tile
LANE = 128

#: minimum sublane (second-to-last dim) granule per dtype itemsize:
#: fp32 tiles are (8, 128), bf16 (16, 128), int8/fp8 (32, 128)
_SUBLANE_BY_ITEMSIZE = {1: 32, 2: 16, 4: 8, 8: 8}


def sublane(dtype) -> int:
    """Minimum sublane granule for ``dtype`` on TPU."""
    return _SUBLANE_BY_ITEMSIZE[np.dtype(dtype).itemsize]


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def tile_block_cap(default: int, dim: int, granule: int) -> int:
    """Cap a default block size to a dimension WITHOUT losing tile
    alignment: ``min(default, round_up(dim, granule))``.

    The naive ``min(default, dim)`` cap produces a tile-misaligned
    block whenever ``dim`` is not a granule multiple (e.g. seq 40 →
    block 40, not a multiple of the fp32 sublane 8), which forces the
    Mosaic compiler into padded/strided layouts. Rounding the cap up to
    the granule keeps the block aligned and lets the caller's padding
    logic absorb the remainder."""
    return min(default, round_up(dim, granule))


def fewest_blocks(dim: int, cap: int, granule: int) -> int:
    """The block that covers ``dim`` in the fewest blocks of at most
    ``cap`` (a ``granule`` multiple), each the smallest ``granule``
    multiple that does: 3584 at cap 512 is 7 blocks of 512, 640 is 2 of
    384 (padding 128, where 512 would pad 384)."""
    d = round_up(dim, granule)
    n_blocks = -(-d // cap)
    return round_up(-(-d // n_blocks), granule)


#: VMEM bytes one kernel instance may declare (double-buffered blocks
#: plus scratch): v5e's 16 MiB default scoped VMEM less headroom for
#: what Mosaic keeps itself. The L003 lint holds every layout to it.
VMEM_BUDGET_BYTES = 14 * 1024 * 1024


def tile_bytes(shape, dtype) -> int:
    """Bytes a block actually occupies in VMEM: last two dims rounded
    up to the dtype tile, leading dims multiplied through."""
    dt = np.dtype(dtype)
    dims = list(shape)
    if len(dims) >= 1:
        dims[-1] = round_up(dims[-1], LANE)
    if len(dims) >= 2:
        dims[-2] = round_up(dims[-2], sublane(dt))
    return int(np.prod(dims, dtype=np.int64)) * dt.itemsize


@dataclasses.dataclass(frozen=True)
class OperandLayout:
    """One pallas_call operand as the layout lint sees it: the PADDED
    array shape the kernel is actually called with, its block shape,
    dtype name, and memory space (``"vmem"`` blocks are tile-checked;
    ``"smem"`` scalars are exempt)."""
    shape: Tuple[int, ...]
    block: Tuple[int, ...]
    dtype: str
    memory: str = "vmem"


@dataclasses.dataclass(frozen=True)
class BlockLayout:
    """Declared block-level layout of one Pallas kernel at one concrete
    shape. The kernel wrappers DERIVE their grid / BlockSpecs / padding
    from this (single source of truth), and the L003 lint checks it:
    tile alignment, grid×block coverage, VMEM footprint, accumulator
    dtype."""
    kernel: str
    grid: Tuple[int, ...]
    operands: Dict[str, OperandLayout]
    outputs: Dict[str, OperandLayout]
    scratch: Tuple[OperandLayout, ...] = ()
    accum_dtype: str = "float32"

    def vmem_bytes(self) -> int:
        """Estimated VMEM footprint: every VMEM operand and output block
        double-buffered, plus the scratch."""
        blocks = [*self.operands.values(), *self.outputs.values()]
        return (sum(2 * tile_bytes(op.block, op.dtype)
                    for op in blocks if op.memory == "vmem")
                + sum(tile_bytes(sc.shape, sc.dtype)
                      for sc in self.scratch))
