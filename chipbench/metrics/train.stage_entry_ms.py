"""Host wall time of stage construction per schedule, in ms: the
window's outermost ``repro.stage.enter`` spans and the
``repro.devft.transfer`` spans outside them (the last stage's transfer
back, in ``finalize``), over the schedules in the window."""
from chipbench import program_spans

NAMES = ("repro.stage.enter", "repro.devft.transfer")


def read(ctx):
    r = program_spans.reduce(ctx["cell"].trace_path)
    if not r["spans"]:
        return None
    spans = program_spans.outermost(r["spans"], NAMES)
    return 1e3 * sum(e - s for s, e, _, _ in spans) \
        / ctx["counts"]["schedules"]
