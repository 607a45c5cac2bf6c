"""Device time of the eval program (``jit_ev``) as a share of the time
the device was busy."""


def read(ctx):
    t = ctx["trace"]
    ev = t["module_s"].get("jit_ev")
    return 100.0 * ev / t["busy_s"] if ev else None
