"""L003 — Pallas block-layout lint (pure functions over
``repro.kernels.common.BlockLayout``; no jax import, so the rules are
unit-testable on synthetic layouts).

A kernel whose blocks violate TPU tiling can still be *correct* — the
Mosaic compiler pads and strides around it — but it can never be
*fast*, and the repo's ROADMAP explicitly calls out that every
committed kernel row is an interpret-mode non-win. The lint enforces
the preconditions of a winnable kernel before anyone burns time
autotuning one that can't win:

* **tile alignment** — every VMEM block's sublane (second-to-last) dim
  is a multiple of the dtype granule (fp32 8, bf16 16, int8 32), and
  its lane (last) dim is a multiple of 128 *or* spans the full padded
  array dim (narrow operands like a rank-8 LoRA factor or the SSD
  decay column are one tile wide — that is their whole array). The
  sublane rule has deliberately NO full-dim exemption: a (1, 1) VMEM
  block still burns a full (8, 128) tile, which is exactly the bug the
  SSD per-head scalars had before moving to SMEM.
* **whole-array SMEM** — an SMEM scalar operand is one block spanning
  its whole array, indexed by ``program_id`` inside the kernel. The TPU
  compiler applies the tiling rule to SMEM blocks too, so a (1, 1)
  block of an (N, 1) array is refused at compile time (the old
  ``flash_decode`` and ``ssd_scan`` scalar layouts, which interpret
  mode ran without complaint).
* **coverage** — grid × block tiles the padded array exactly (a
  remainder row means the index map re-reads or drops elements).
* **VMEM footprint** — double-buffered operand+output blocks plus
  scratch fit the per-platform budget.
* **accumulator dtype** — declared accumulation is fp32 or wider
  (bf16 accumulation loses the MXU's fp32 accumulate for free).
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.kernels.common import (
    LANE,
    VMEM_BUDGET_BYTES,
    BlockLayout,
    OperandLayout,
    sublane,
)

#: VMEM bytes available to one kernel instance, per platform; unknown
#: platforms get the TPU budget (the kernels are TPU-targeted).
VMEM_BUDGET = {"tpu": VMEM_BUDGET_BYTES}


def _check_operand(name: str, op: OperandLayout) -> List[str]:
    msgs: List[str] = []
    if op.memory != "vmem":
        # SMEM scalars are tile-exempt as whole arrays only
        if tuple(op.block) != tuple(op.shape):
            msgs.append(
                f"{name}: SMEM block {op.block} does not span its array "
                f"{op.shape} — block SMEM scalars whole and index them "
                f"by program_id")
        return msgs
    if len(op.block) != len(op.shape):
        return [f"{name}: block rank {len(op.block)} != array rank "
                f"{len(op.shape)}"]
    if len(op.block) >= 2:
        g = sublane(op.dtype)
        if op.block[-2] % g:
            msgs.append(
                f"{name}: sublane dim {op.block[-2]} of block "
                f"{op.block} is not a multiple of the {op.dtype} "
                f"granule {g} (tile ({g}, {LANE}))")
    if op.block and op.block[-1] % LANE and op.block[-1] != op.shape[-1]:
        msgs.append(
            f"{name}: lane dim {op.block[-1]} of block {op.block} is "
            f"neither a multiple of {LANE} nor the full array dim "
            f"{op.shape[-1]}")
    for ax, (s, b) in enumerate(zip(op.shape, op.block)):
        if s % b:
            msgs.append(
                f"{name}: padded dim {ax} ({s}) is not covered by "
                f"block dim {b} — grid x block leaves a remainder of "
                f"{s % b}")
    return msgs


def lint_layout(layout: BlockLayout, platform: str = "tpu") -> List[str]:
    """All L003 violations of one declared layout; [] == clean."""
    msgs: List[str] = []
    named = {**layout.operands,
             **{f"out:{k}": v for k, v in layout.outputs.items()}}
    for name, op in named.items():
        msgs.extend(_check_operand(name, op))

    acc = np.dtype(layout.accum_dtype)
    if acc.kind != "f" or acc.itemsize < 4:
        msgs.append(f"accumulator dtype {layout.accum_dtype} is below "
                    f"fp32 — MXU accumulation must be float32 or wider")

    vmem = layout.vmem_bytes()
    budget = VMEM_BUDGET.get(platform, VMEM_BUDGET_BYTES)
    if vmem > budget:
        msgs.append(f"estimated VMEM footprint {vmem} bytes "
                    f"(double-buffered blocks + scratch) exceeds the "
                    f"{platform} budget {budget}")
    return msgs
