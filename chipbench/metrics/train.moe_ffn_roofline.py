"""The least time the chip needs for the expert FFN's routed work in the
window (top-k (token, expert) pairs, not the capacity padding; each call
the larger of operations over peak and bytes over bandwidth) over the
``moe_expert_ffn`` kernel's device time. The kernel runs once per MoE
layer in the forward of every local step (one launch covers all of the
round's clients, which the round program vmaps) and of every eval.

The kernel is found by its own name, ``moe_expert_ffn``, or, while its
``pallas_call`` carries no name, as the one custom-call family named
``closed_call`` (the closed call around it). Either way its launches in
the window have to number exactly what the schedule needs; where they
do not (another unnamed kernel shares the family, or the kernel runs
elsewhere), nothing is read."""
from chipbench import flops
from chipbench.trace import kernel_runs, kernel_seconds


def _family(reduced):
    named = [k for k in reduced["custom_calls"] if "moe_expert_ffn" in k]
    unnamed = [k for k in reduced["custom_calls"] if "closed_call" in k]
    if named:
        return "moe_expert_ffn"
    if len(unnamed) == 1:
        return unnamed[0]
    return None


def read(ctx):
    r = ctx["trace"]
    fam = _family(r)
    if fam is None:
        return None
    m, w, c, pk = ctx["model"], ctx["workload"], ctx["counts"], ctx["peaks"]
    launches = c["schedules"] * sum(c["capacities"]) * (w["k_local"] + 1)
    if kernel_runs(r, fam) != launches:
        return None
    t = kernel_seconds(r, fam)
    if t is None:
        return None
    s, k = w["seq"], m["num_experts_per_tok"]
    need = 0.0
    for cap in c["capacities"]:
        for rows, calls in ((c["n_sample"] * w["local_batch"] * s,
                             w["k_local"]),
                            (w["eval_rows"] * s, 1)):
            f, b = flops.moe_expert_ffn(rows * k, m["hidden_size"],
                                        m["intermediate_size"],
                                        m["num_local_experts"])
            need += calls * cap * max(f / pk["bf16_flops_per_s"],
                                      b / pk["hbm_bytes_per_s"])
    return 100.0 * c["schedules"] * need / t
