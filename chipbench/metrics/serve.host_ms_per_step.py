"""Mean host time of an engine step (admission, input assembly, the
blocking fetch and the harvest): the window's step wall time less the
decode program's device time (``jit_fn``, the engine's jitted step),
per step."""


def read(ctx):
    c, t = ctx["counts"], ctx["trace"]
    dev = t["module_s"].get("jit_fn")
    if not dev or not c["steps"]:
        return None
    return 1e3 * (c["step_wall_s"] - dev) / c["steps"]
