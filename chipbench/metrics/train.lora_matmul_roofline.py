"""The least time the chip needs for the window's ``lora_matmul`` calls
(each call the larger of its operations over peak and its bytes over
bandwidth) over the kernel's device time. The kernel runs in the forward
of every local step (the q and v projections of each layer) and of
every eval; its backward is not a kernel."""
from chipbench import flops
from chipbench.trace import kernel_seconds


def read(ctx):
    t = kernel_seconds(ctx["trace"], "lora_matmul")
    if t is None:
        return None
    m, w, c, pk = ctx["model"], ctx["workload"], ctx["counts"], ctx["peaks"]
    d, h, kv, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    r, s = w["lora_rank"], w["seq"]
    need = 0.0
    for cap in c["capacities"]:
        for rows, calls in ((w["local_batch"] * s,
                             c["n_sample"] * w["k_local"]),
                            (w["eval_rows"] * s, 1)):
            for dout in (h * hd, kv * hd):
                f, b = flops.lora_matmul(rows, d, dout, r)
                need += calls * cap * max(f / pk["bf16_flops_per_s"],
                                          b / pk["hbm_bytes_per_s"])
    return 100.0 * c["schedules"] * need / t
