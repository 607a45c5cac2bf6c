"""Device self time of the collective operations (all-gather,
reduce-scatter, all-reduce, collective-permute, all-to-all, their
``-start``/``-done`` halves and the fusions the compiler names after
them) as a share of the time the device was busy, both averaged over
the chips. Nothing where the window ran no collective (one chip)."""
import re

COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce"
                        r"|collective-permute|all-to-all")


def read(ctx):
    t = ctx["trace"]
    s = sum(v for k, v in t["op_s"].items() if COLLECTIVE.search(k))
    return 100.0 * s / t["busy_s"] if s > 0 else None
