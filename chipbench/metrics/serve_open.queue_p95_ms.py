"""95th percentile, over the requests due in the window, of the wait from
a request's due time to its admission into a slot (the engine's
``Request.t_admit``)."""


def read(ctx):
    return ctx["counts"].get("queue_p95_ms")
