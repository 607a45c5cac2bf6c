"""``correct`` for the hybrid's kind of cell (``hybrid_round``) at the
rehearsal size on the CPU, through ``chipbench/run.py``: a sound run
passes, and each fault a round cell can have fails. The faults are those
of ``test_checks.py`` and a DevFT stage whose groups ignore the layers'
similarity; the limits are the rehearsal block's, set from readings at
this size."""
import pytest
from test_checks import (_half_batch_round, _programs_reached_another_way,
                         _unchanged_round, _wrong_transfer)

from chipbench.run import run_cell

CELL = "granite-4.0-h-micro.round.devft"


def _random_groups(monkeypatch):
    """DevFT stages grouped at random: each stage is a valid partition,
    fused and handed its adapter as the program does, of layers that are
    not alike."""
    import jax

    import repro.core.devft as devft
    from repro.core.grouping import random_grouping

    def groups(method, stack, lora_stack, n_groups, seed=0):
        return random_grouping(jax.tree.leaves(stack)[0].shape[0], n_groups,
                               seed)
    monkeypatch.setattr(devft, "make_groups", groups)


@pytest.mark.parametrize("fault", [
    None, _unchanged_round, _half_batch_round, _programs_reached_another_way,
    _wrong_transfer, _random_groups])
def test_sound_runs_pass_and_faults_fail(fault, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    r = run_cell(CELL, 21, 1.0, False, rehearse=True, t_start=0.0)
    assert r["correct"] is (fault is None), r["checks"]
    assert r["window_compiles"] == 0
