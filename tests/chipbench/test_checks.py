"""``correct`` at the rehearsal size on the CPU: a sound run passes, and
the control and each fault the cell can have fail. The limits here are
the ``rehearsal`` block's, set from readings at this size."""
import pytest

from chipbench import harness, round_cell, serve_cell
from chipbench.run import ROOT, run_cell

ROUND = "qwen2-7b-l4.round.devft"
SERVE = "qwen2-7b-l4.serve.saturated"


def _unchanged_round(monkeypatch):
    """A round program that returns the adapter it was given."""
    import repro.federated.simulator as sim

    make = sim.make_round_program

    def broken(*a, **k):
        fn, aux = make(*a, **k)

        def round_fn(params, lora, batches, lr, *rest):
            _, metrics = fn(params, lora, batches, lr, *rest)
            return lora, metrics
        return round_fn, aux
    monkeypatch.setattr(sim, "make_round_program", broken)


def _half_batch_round(monkeypatch):
    """A round program that trains on half of each batch."""
    import repro.federated.simulator as sim

    make = sim.make_round_program

    def broken(*a, **k):
        fn, aux = make(*a, **k)

        def round_fn(params, lora, batches, lr, *rest):
            half = batches["tokens"].shape[2] // 2
            return fn(params, lora, {k_: v[:, :, :half]
                                     for k_, v in batches.items()}, lr, *rest)
        return round_fn, aux
    monkeypatch.setattr(sim, "make_round_program", broken)


def _programs_reached_another_way(monkeypatch):
    """A runner whose ``run`` reaches its round and eval programs without
    going through its ``_round_fn`` and ``_eval_fn`` methods, as one that
    fused them would: the rounds are right, but nothing is recorded."""
    from repro.federated import FederatedRunner

    run = FederatedRunner.run

    def bypass(self, *a, **k):
        own = {n: self.__dict__.pop(n) for n in ("_round_fn", "_eval_fn")
               if n in self.__dict__}
        try:
            return run(self, *a, **k)
        finally:
            self.__dict__.update(own)
    monkeypatch.setattr(FederatedRunner, "run", bypass)


def _wrong_transfer(monkeypatch):
    """A DevFT stage entry that starts the new stage's adapter from
    zero instead of the transferred one."""
    from repro.federated.methods.devft import DevFT

    on_stage = DevFT.on_stage

    def broken(self, state, stage):
        on_stage(self, state, stage)
        if stage > 0:
            import dataclasses

            import jax
            import jax.numpy as jnp
            sub = state["sub"]
            state["sub"] = dataclasses.replace(
                sub, lora=jax.tree.map(jnp.zeros_like, sub.lora))
    monkeypatch.setattr(DevFT, "on_stage", broken)


def _altered_token(monkeypatch):
    """The engine serves, as each request's second token, another token
    than the one it computed."""
    from repro.serving import ServingEngine

    step = ServingEngine.step

    def broken(self):
        active = list(self.scheduler.active)
        before = [len(r.generated) for _, r in active]
        out = step(self)
        for (_, r), n in zip(active, before):
            if n == 1 and len(r.generated) == 2:
                r.generated[-1] = (r.generated[-1] + 1) % 256
        return out
    monkeypatch.setattr(ServingEngine, "step", broken)


def _stale_cache(monkeypatch):
    """A decode step that leaves the KV cache as it was."""
    from repro.serving import ServingEngine

    build = ServingEngine._build_step

    def broken(self):
        fn = build(self)

        def step(params, lora_op, idx, tokens, cache, active):
            nxt, new = fn(params, lora_op, idx, tokens, cache, active)
            new["stacks"] = cache["stacks"]
            return nxt, new
        return step
    monkeypatch.setattr(ServingEngine, "_build_step", broken)


@pytest.mark.parametrize("cell,fault", [
    (ROUND, None), (ROUND, _unchanged_round), (ROUND, _half_batch_round),
    (ROUND, _programs_reached_another_way), (ROUND, _wrong_transfer),
    (SERVE, None), (SERVE, _altered_token), (SERVE, _stale_cache)])
def test_sound_runs_pass_and_faults_fail(cell, fault, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    r = run_cell(cell, 21, 1.0, False, rehearse=True, t_start=0.0)
    assert r["correct"] is (fault is None), r["checks"]


def test_the_control_fails():
    """The reference with its base rounded to float8 in the program's
    place fails a number the cell compares."""
    cell = harness.Cell(ROOT, ROUND, 22, 1.0, False, True, 0.0)
    got = dict(round_cell.readings(cell, harness.cell_devices(cell, True),
                                   True, harness.CompileMeter()))
    limits = cell.params["limits"]
    assert any(v > limits[k] for k, v in got["control"].items())
    assert all(v <= limits[k] for k, v in got["program"].items())

    cell = harness.Cell(ROOT, SERVE, 22, 1.0, False, True, 0.0)
    got = dict(serve_cell.readings(cell, harness.cell_devices(cell, True),
                                   True, harness.CompileMeter()))
    limit = cell.params["limits"]["logit_gap"]
    assert got["control"]["logit_gap"] > limit >= got["program"]["logit_gap"]
