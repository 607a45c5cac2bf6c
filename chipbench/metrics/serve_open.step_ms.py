"""Mean wall time of one engine step (``admit`` and ``step()``) in the
window, on the host clock."""


def read(ctx):
    c = ctx["counts"]
    return 1e3 * c["step_wall_s"] / c["steps"] if c["steps"] else None
