"""Operations and bytes of the Mamba-2 / attention hybrid, computed from
the shapes, as ``chipbench/flops.py`` counts them for the GQA decoders.

A multiply-add counts two operations. A stage of ``capacity`` layers has
the Mamba and attention layers of ``reference_hybrid.stack_capacities``.
Attention counts the keys a query may see, ``(S + 1) / 2`` on average
for causal training at length ``S``. The SSD counts its chunked form at
the configuration's chunk ``Q`` (capped at the sequence): within a chunk
each token meets ``(Q + 1) / 2`` tokens on average (the ``C . B`` score
and the weighted sum of ``x``), across chunks it reads the carried state
(``C . state``) and adds itself to it (``x B^T``); per head and token
``(N + P) (Q + 1) + 4 N P``. Only the used vocabulary counts.
"""
from __future__ import annotations

from chipbench.flops import BF16, F32
from chipbench.reference_hybrid import stack_capacities
from chipbench.weights_hybrid import lora_targets, mamba_sizes


def _mamba_params(m) -> int:
    """Weights one token multiplies in a Mamba layer's projections."""
    d = m["hidden_size"]
    din, _, width = mamba_sizes(m)
    return d * width + din * d


def _attn_params(m) -> int:
    d, h, kv, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd


def _mlp_params(m) -> int:
    return 3 * m["hidden_size"] * m["shared_intermediate_size"]


def _lora(m, stack: str, rank: int) -> int:
    """Forward operations of one token through a layer's adapters."""
    return sum(2 * (din + dout) * rank
               for din, dout in lora_targets(m)[stack].values())


def ssd_flops(m, seq: int) -> float:
    """One token through one layer's SSD."""
    q = min(m["mamba_chunk_size"], seq)
    n, p = m["mamba_d_state"], m["mamba_d_head"]
    return m["mamba_n_heads"] * ((n + p) * (q + 1) + 4.0 * n * p)


def _conv_flops(m) -> float:
    _, ch, _ = mamba_sizes(m)
    return 2.0 * ch * m["mamba_d_conv"]


def _layers(m, capacity: int):
    caps = stack_capacities(m, capacity)
    return caps["mamba_mlp"], caps["attn_mlp"]


def forward_flops(m, capacity: int, kv_len: float, rank: int = 0,
                  head: bool = True, seq: int = None) -> float:
    """One token through a stage of ``capacity`` layers and the output
    head (the SSD's chunk is capped at ``seq``, by default the causal
    ``kv_len``'s sequence)."""
    seq = seq or int(round(2 * kv_len - 1))
    nm, na = _layers(m, capacity)
    mlp = 2 * _mlp_params(m)
    mamba = 2 * _mamba_params(m) + mlp + ssd_flops(m, seq) \
        + _conv_flops(m) + (_lora(m, "mamba_mlp", rank) if rank else 0)
    attn = 2 * _attn_params(m) + mlp \
        + 4.0 * m["num_attention_heads"] * m["head_dim"] * kv_len \
        + (_lora(m, "attn_mlp", rank) if rank else 0)
    return nm * mamba + na * attn + (
        2.0 * m["hidden_size"] * m["vocab_size"] if head else 0.0)


def train_flops(m, capacity: int, seq: int, rank: int) -> float:
    """One training token with a frozen base and LoRA adapters: the
    forward, the backward's activation gradients (the base matmuls
    again, twice the attention and the SSD, the convolution once) and
    the adapters' own gradients. No gradient of a base weight; nothing
    recomputed counts."""
    kv = (seq + 1) / 2.0
    nm, na = _layers(m, capacity)
    mlp = 2 * _mlp_params(m)
    bwd = nm * (2 * _mamba_params(m) + mlp + 2 * ssd_flops(m, seq)
                + _conv_flops(m) + 2 * _lora(m, "mamba_mlp", rank)) \
        + na * (2 * _attn_params(m) + mlp
                + 8.0 * m["num_attention_heads"] * m["head_dim"] * kv
                + 2 * _lora(m, "attn_mlp", rank)) \
        + 2.0 * m["hidden_size"] * m["vocab_size"]
    return forward_flops(m, capacity, kv, rank, seq=seq) + bwd


# ---------------------------------------------------------------------------
# kernels: (flops, bytes) of one call
# ---------------------------------------------------------------------------


def ssd_scan(m, rows: int, seq: int, itemsize: int = BF16):
    """The ``ssd_scan`` forward over ``rows`` sequences of ``seq``
    tokens, one layer: ``ssd_flops`` per token; x, B and C (per group)
    read once, the float32 step read once, y written once."""
    h, p, n, g = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                  m["mamba_n_groups"])
    tokens = rows * seq
    nbytes = tokens * (itemsize * (2 * h * p + 2 * g * n) + F32 * h)
    return tokens * ssd_flops(m, seq), nbytes


def forward_passes(w) -> int:
    """How often a training step runs each layer's forward: twice where
    the workload rematerialises each layer in the backward."""
    return 2 if w.get("remat") else 1


def lora_calls(m, capacity: int, rows: int):
    """``[(rows, din, dout, calls)]`` of one forward of a stage of
    ``capacity`` layers over ``rows`` tokens: the Mamba layers'
    ``in_proj`` and ``out_proj``, the attention layers' q and v."""
    nm, na = _layers(m, capacity)
    n = {"mamba_mlp": nm, "attn_mlp": na}
    return [(rows, din, dout, n[stack])
            for stack, targets in lora_targets(m).items()
            for din, dout in targets.values() if n[stack]]
