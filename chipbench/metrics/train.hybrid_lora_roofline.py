"""The least time the chip needs for the window's ``lora_matmul`` calls
in the Mamba-2 / attention hybrid (each call the larger of its
operations over peak and its bytes over bandwidth) over the kernel's
device time. The calls are ``flops_hybrid.lora_calls``: each Mamba
layer's ``in_proj`` and ``out_proj`` and each attention layer's q and v,
in the forward of every local step of every client (a second forward
where the workload rematerialises each layer) and of every eval;
the backward is not a kernel."""
from chipbench import flops, flops_hybrid
from chipbench.trace import kernel_seconds


def read(ctx):
    t = kernel_seconds(ctx["trace"], "lora_matmul")
    if t is None:
        return None
    m, w, c, pk = ctx["model"], ctx["workload"], ctx["counts"], ctx["peaks"]
    r, s = w["lora_rank"], w["seq"]
    need = 0.0
    for cap in c["capacities"]:
        for rows, calls in ((w["local_batch"] * s,
                             c["n_sample"] * w["k_local"]
                             * flops_hybrid.forward_passes(w)),
                            (w["eval_rows"] * s, 1)):
            for _, din, dout, n in flops_hybrid.lora_calls(m, cap, rows):
                f, b = flops.lora_matmul(rows, din, dout, r)
                need += calls * n * max(f / pk["bf16_flops_per_s"],
                                        b / pk["hbm_bytes_per_s"])
    return 100.0 * c["schedules"] * need / t
