"""The least time the chip needs for the window's ``ssd_scan`` calls
(each call the larger of its operations over peak and its bytes over
bandwidth, ``chipbench/flops_hybrid.py``) over the kernel's device time.
The kernel runs once per Mamba layer in the forward of every local step
(one launch covers the round's clients, which the round program vmaps;
a second forward where the workload rematerialises each layer) and
of every eval; its backward is not a kernel. Nothing is read where the
trace holds no ``ssd_scan``."""
from chipbench import flops_hybrid
from chipbench.reference_hybrid import stack_capacities
from chipbench.trace import kernel_seconds


def read(ctx):
    t = kernel_seconds(ctx["trace"], "ssd_scan")
    if t is None:
        return None
    m, w, c, pk = ctx["model"], ctx["workload"], ctx["counts"], ctx["peaks"]
    need = 0.0
    for cap in c["capacities"]:
        layers = stack_capacities(m, cap)["mamba_mlp"]
        for rows, calls in ((c["n_sample"] * w["local_batch"],
                             w["k_local"] * flops_hybrid.forward_passes(w)),
                            (w["eval_rows"], 1)):
            f, b = flops_hybrid.ssd_scan(m, rows, w["seq"])
            need += calls * layers * max(f / pk["bf16_flops_per_s"],
                                         b / pk["hbm_bytes_per_s"])
    return 100.0 * c["schedules"] * need / t
