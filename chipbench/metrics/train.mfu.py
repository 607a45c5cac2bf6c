"""Model operations of the window's schedules (local training with a
frozen base and the eval forward; ``chipbench/flops.py``) over the traced
window, as a share of the chips' peak."""


def read(ctx):
    c, t, pk = ctx["counts"], ctx["trace"], ctx["peaks"]
    ops = c["schedules"] * (c["train_flops"] + c["eval_flops"])
    return 100.0 * ops / (t["window_s"] * pk["bf16_flops_per_s"]
                          * ctx["n_chips"])
