"""Model operations of the decode steps in the window (one token through
the model for each active slot, its attention over that slot's valid
cache; ``chipbench/flops.py``) over the traced window, as a share of the
chips' peak."""
from chipbench import flops


def read(ctx):
    m, c, t, pk = ctx["model"], ctx["counts"], ctx["trace"], ctx["peaks"]
    if not c["slot_tokens"]:
        return None
    n = m["num_hidden_layers"]
    per_token = flops.forward_flops(m, n, 0.0, m["lora_rank"])
    ops = c["slot_tokens"] * per_token \
        + n * flops.attention_flops(m, c["kv_tokens"])
    return 100.0 * ops / (t["window_s"] * pk["bf16_flops_per_s"]
                          * ctx["n_chips"])
