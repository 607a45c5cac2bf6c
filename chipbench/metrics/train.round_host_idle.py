"""Share of the traced window in which the device was idle while the
host was in the round loop's other spans (``repro.run.prepare``, a
round's planning, placement, dispatch, batch prefetch, eval fetch and
books, ``repro.run.finalize``). With ``train.stage_entry_idle`` it
leaves of ``train.device_idle`` the idle that no program span
explains."""
from chipbench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, stage=False)
