"""Operations and bytes the algorithm needs, computed from the shapes.

``m`` is a configuration file's model block (``hidden_size``, heads,
widths, ``vocab_size``, experts). A multiply-add counts two operations.
Attention counts the keys a query may see (``kv_len``): on average
``(S + 1) / 2`` for causal training at length ``S``, the valid cache
length for decode; never the allocated capacity. A mixture of experts
counts the routed ``top_k`` experts of each token, not the capacity
padding. Only the used vocabulary counts, not its padding.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def _dims(m):
    return (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"])


def proj_params(m) -> int:
    """Weights of one layer's q, k, v and o projections."""
    d, h, kv, hd = _dims(m)
    return 2 * d * h * hd + 2 * d * kv * hd


def ffn_params_active(m) -> int:
    """Weights one token multiplies in one layer's feed-forward block."""
    d = m["hidden_size"]
    if m.get("num_local_experts"):
        return d * m["num_local_experts"] + \
            3 * d * m["intermediate_size"] * m["num_experts_per_tok"]
    return 3 * d * m["intermediate_size"]


def lora_flops(m, rank: int) -> int:
    """Forward operations of one token through one layer's q and v
    adapters (x @ a, then @ b)."""
    d, h, kv, hd = _dims(m)
    return 2 * (d * rank + rank * h * hd) + 2 * (d * rank + rank * kv * hd)


def attention_flops(m, kv_len: float) -> float:
    """Scores and the weighted sum of values for one query token."""
    _, h, _, hd = _dims(m)
    return 4.0 * h * hd * kv_len


def forward_flops(m, n_layers: int, kv_len: float, rank: int = 0,
                  head: bool = True) -> float:
    """One token through ``n_layers`` layers and the output head."""
    layer = 2 * (proj_params(m) + ffn_params_active(m)) \
        + attention_flops(m, kv_len) + (lora_flops(m, rank) if rank else 0)
    return n_layers * layer + (2.0 * m["hidden_size"] * m["vocab_size"]
                               if head else 0.0)


def train_flops(m, n_layers: int, seq: int, rank: int) -> float:
    """One training token with a frozen base and LoRA adapters: the
    forward pass, the backward pass's activation gradients (the same
    matmuls transposed, twice the attention matmuls) and the adapters'
    own gradients. No gradient of a base weight; nothing recomputed."""
    kv = (seq + 1) / 2.0
    fwd = forward_flops(m, n_layers, kv, rank)
    bwd = n_layers * (2 * (proj_params(m) + ffn_params_active(m))
                      + 2 * attention_flops(m, kv) + 2 * lora_flops(m, rank)) \
        + 2.0 * m["hidden_size"] * m["vocab_size"]
    return fwd + bwd


# ---------------------------------------------------------------------------
# kernels: (flops, bytes) of one call
# ---------------------------------------------------------------------------


def lora_matmul(rows: int, din: int, dout: int, rank: int,
                itemsize: int = BF16):
    """y = x @ w + (x @ a) @ b over ``rows`` rows: x and w read once, y
    written once, the factors read once."""
    flops = 2.0 * rows * (din * dout + din * rank + rank * dout)
    nbytes = itemsize * (rows * din + din * dout + rows * dout
                         + din * rank + rank * dout)
    return flops, nbytes


def flash_decode(kv_tokens: float, n_seqs: int, n_heads: int,
                 n_kv_heads: int, head_dim: int, itemsize: int = BF16):
    """One query token for each of ``n_seqs`` sequences against their
    valid cached keys and values, ``kv_tokens`` of them in all: scores
    and the weighted sum, each valid cache entry read once, each query
    read and output written once."""
    flops = 4.0 * n_heads * head_dim * kv_tokens
    nbytes = itemsize * (2 * n_kv_heads * head_dim * kv_tokens
                         + 2 * n_seqs * n_heads * head_dim)
    return flops, nbytes


def moe_expert_ffn(routed_rows: int, d: int, f: int, n_experts: int,
                   itemsize: int = BF16):
    """SwiGLU experts over ``routed_rows`` (token, expert) pairs: three
    matmuls per pair; every expert's weights read once, each routed row
    read and written once."""
    flops = 6.0 * routed_rows * d * f
    nbytes = itemsize * (3 * n_experts * d * f + 2 * routed_rows * d)
    return flops, nbytes
