"""The least time the chip needs for the window's decode attention (each
active slot's query against its valid cache, read once; the larger of
operations over peak and bytes over bandwidth) over the ``flash_decode``
kernel's device time."""
from chipbench import flops
from chipbench.trace import kernel_seconds


def read(ctx):
    t = kernel_seconds(ctx["trace"], "decode")
    m, c, pk = ctx["model"], ctx["counts"], ctx["peaks"]
    if t is None or not c["slot_tokens"]:
        return None
    f, b = flops.flash_decode(c["kv_tokens"], c["slot_tokens"],
                              m["num_attention_heads"],
                              m["num_key_value_heads"], m["head_dim"])
    n = m["num_hidden_layers"]
    need = n * max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * need / t
