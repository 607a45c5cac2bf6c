"""Share of the traced window in which the device was idle while the
host built a stage: the idle gaps that fall to the program's
stage-construction spans (``repro.stage.*``, ``repro.devft.*``: DevFT's
grouping, fusion and transfer)."""
from chipbench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, stage=True)
