"""The program's host spans, as the profiler records them.

A tiny DevFT ``FederatedRunner.run()`` and one ``ServingEngine.step()``
run under ``jax.profiler``; the ``.xplane.pb`` is read back with
``ProfileData`` and the ``repro.*`` spans are checked for presence,
nesting, order and their counter arguments. The compiled round, eval
and decode programs carry the ``jax.named_scope`` names in their HLO
metadata.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.data import make_federated_data
from repro.federated import FedConfig, FederatedRunner
from repro.models import transformer as T
from repro.serving import ServingEngine


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    thread: str
    args: dict

    def inside(self, other: "Span") -> bool:
        return (self is not other and self.thread == other.thread
                and other.start <= self.start and self.end <= other.end)


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns its result and the
    ``repro.*`` host spans of the trace, in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      f"{plane.name}/{line.name}",
                                      dict(ev.stats)))
    return out, sorted(spans, key=lambda s: (s.start, -s.end))


def _parent(span, spans):
    """The innermost span that encloses ``span``, or None."""
    outer = [s for s in spans if span.inside(s)]
    return min(outer, key=lambda s: s.end - s.start) if outer else None


def _named(spans, name):
    return [s for s in spans if s.name == name]


ROUND_CHILDREN = ["repro.stage.enter", "repro.round.plan",
                  "repro.round.place", "repro.round.dispatch",
                  "repro.eval.dispatch", "repro.round.host_batches",
                  "repro.round.fetch", "repro.round.books"]


@pytest.fixture(scope="module")
def devft_run(tmp_path_factory, test_spec):
    cfg = dataclasses.replace(
        reduce_config(get_config("llama2-7b-proxy"), test_spec), n_layers=4)
    data = make_federated_data(cfg.vocab, n_clients=4, alpha=0.5, seed=0)
    fed = FedConfig(n_clients=4, sample_frac=0.5, k_local=2, local_batch=2,
                    seq=16, rounds=4, lora_rank=2, lr=1e-3, method="devft",
                    n_stages=2)
    runner = FederatedRunner(cfg, fed, data)
    runner.run()                       # compile outside the trace
    logs, spans = _traced(tmp_path_factory.mktemp("devft"), runner.run)
    return fed, logs, spans


def test_run_spans_nest_as_documented(devft_run):
    fed, logs, spans = devft_run
    run, = _named(spans, "repro.run")
    assert run.args["rounds"] == fed.rounds
    assert run.args["method"] == "devft"
    for name in ("repro.run.prepare", "repro.run.finalize"):
        s, = _named(spans, name)
        assert _parent(s, spans) is run
    rounds = _named(spans, "repro.round")
    assert [r.args["step_num"] for r in rounds] == list(range(len(logs)))
    assert [(r.args["stage"], r.args["capacity"]) for r in rounds] == \
        [(l.stage, l.capacity) for l in logs]
    for r in rounds:
        assert _parent(r, spans) is run
        assert r.args["clients"] == 2
        kids = [s for s in spans if _parent(s, spans) is r]
        order = [s.name for s in kids]
        assert order == [n for n in ROUND_CHILDREN if n in order]
        for n in ("repro.round.plan", "repro.round.place",
                  "repro.round.dispatch", "repro.eval.dispatch",
                  "repro.round.books"):
            assert order.count(n) == 1, (n, order)
        place, = [s for s in kids if s.name == "repro.round.place"]
        assert place.args["bytes"] == 2 * (2 * 2 * 2 * 16 * 4)
    # the prefetch of every round but the last, the fetch of every round
    # but the first; the last round's fetch follows the loop
    assert [_parent(s, spans) for s in
            _named(spans, "repro.round.host_batches")] == rounds[:-1]
    fetches = _named(spans, "repro.round.fetch")
    assert [_parent(s, spans) for s in fetches] == rounds[1:] + [run]


def test_one_stage_entry_per_stage_and_grouping_only_where_it_shrinks(
        devft_run):
    fed, logs, spans = devft_run
    enters = _named(spans, "repro.stage.enter")
    stages = sorted({l.stage for l in logs})
    assert [s.args["stage"] for s in enters] == stages
    for e in enters:
        assert _parent(e, spans).name == "repro.round"
        inner = {s.name for s in spans if s.inside(e)}
        shrinks = e.args["capacity"] < 4
        assert ("repro.devft.group" in inner) == shrinks
        assert ("repro.devft.fuse" in inner) == shrinks
        # every entry after the first hands the stage before back
        assert ("repro.devft.transfer" in inner) == (e.args["stage"] > 0)
    for name in ("repro.devft.group", "repro.devft.fuse"):
        for s in _named(spans, name):
            assert _parent(s, spans).name == "repro.stage.enter"
    group, = _named(spans, "repro.devft.group")
    assert (group.args["layers"], group.args["groups"]) == (4, 2)
    fuse, = _named(spans, "repro.devft.fuse")
    assert (fuse.args["layers_in"], fuse.args["layers_out"]) == (4, 2)
    finalize, = _named(spans, "repro.run.finalize")
    last, = [s for s in _named(spans, "repro.devft.transfer")
             if s.inside(finalize)]
    assert _parent(last, spans) is finalize


def test_round_tokens_sum_to_the_trained_tokens(devft_run):
    fed, logs, spans = devft_run
    n_sample = int(fed.n_clients * fed.sample_frac)
    trained = len(logs) * n_sample * fed.k_local * fed.local_batch * fed.seq
    assert sum(r.args["tokens"] for r in _named(spans, "repro.round")) \
        == trained


def test_engine_step_spans_in_order(tmp_path, test_spec):
    cfg = reduce_config(get_config("qwen2-7b"), test_spec)
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key, jnp.float32)
    lora = T.init_lora(cfg, key, rank=4)
    eng = ServingEngine(cfg, params, lora=lora, n_slots=2, kv_capacity=32)
    eng.warmup()
    eng.submit(np.arange(1, 3), max_new_tokens=1)
    eng.step()                         # admits; consumes prompt token 1
    done, spans = _traced(tmp_path, eng.step)
    step, = _named(spans, "repro.engine.step")
    assert step.args["step_num"] == 1
    kids = [s for s in spans if _parent(s, spans) is step]
    assert [s.name for s in kids] == [
        "repro.engine.admit", "repro.engine.assemble",
        "repro.engine.decode", "repro.engine.harvest"]
    admit, _, decode, harvest = kids
    assert admit.args["admitted"] == 0
    assert decode.args["active"] == 1
    assert harvest.args["finished"] == len(done) == 1


def _scopes(text):
    """Every name on the ``op_name`` paths of compiled HLO text, with
    the transformations around it taken off (``vmap(local_train)`` ->
    ``local_train``)."""
    return {re.sub(r"^(?:\w+\()+|\)+$", "", part)
            for path in re.findall(r'op_name="([^"]*)"', text)
            for part in path.split("/")}


def test_named_scopes_reach_the_hlo_metadata(test_spec):
    from repro.analysis.contracts.serving import _step_fn
    from repro.analysis.contracts.strategies import round_operands
    from repro.federated.methods.registry import make_strategy
    from repro.federated.simulator import make_round_program

    cfg = reduce_config(get_config("qwen2-7b"), test_spec)
    fed = FedConfig(n_clients=4, sample_frac=0.5, k_local=2, local_batch=2,
                    seq=16, rounds=2, lora_rank=2, method="fedit")
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key, jnp.float32)
    lora = T.init_lora(cfg, key, rank=fed.lora_rank)
    strategy = make_strategy("fedit", cfg, fed)
    state = strategy.init_state(params, lora)
    strategy.on_stage(state, 0)
    spec = strategy.local_spec(state)
    round_fn, _ = make_round_program(strategy, state, spec.cfg, 2,
                                     hetero=False)
    text = jax.jit(round_fn).lower(
        *round_operands(spec, fed, 2, False)).compile().as_text()
    assert {"local_train", "loss_and_grad", "adamw", "aggregate"} \
        <= _scopes(text)

    runner = FederatedRunner(cfg, fed, make_federated_data(
        cfg.vocab, n_clients=4, alpha=0.5, seed=0))
    batch = {k: jnp.asarray(v) for k, v in
             runner.data.eval_batch(2, fed.seq).items()}
    text = runner._eval_fn(cfg).lower(params, lora, batch).compile().as_text()
    assert "eval" in _scopes(text)

    n = 2
    cache = T.init_cache(cfg, n, 16, jnp.float32)
    text = jax.jit(_step_fn(cfg, multi=False)).lower(
        params, lora, jnp.zeros((n,), jnp.int32),
        jnp.zeros((n, 1), jnp.int32), cache,
        jnp.zeros((n,), bool)).compile().as_text()
    assert "decode" in _scopes(text)


def test_hybrid_stage_entries_carry_each_stacks_depth(tmp_path, test_spec):
    """On a hybrid, a stage entry counts its Mamba and attention layers,
    and each stack is grouped and fused under spans that name it."""
    cfg = dataclasses.replace(reduce_config(
        get_config("granite-4.0-h-micro"), test_spec), n_layers=20)
    data = make_federated_data(cfg.vocab, n_clients=4, alpha=0.5, seed=0)
    fed = FedConfig(n_clients=4, sample_frac=0.5, k_local=1, local_batch=1,
                    seq=16, rounds=2, lora_rank=2, lr=1e-3, method="devft",
                    n_stages=2)
    runner = FederatedRunner(cfg, fed, data)
    runner.run()                       # compile outside the trace
    _, spans = _traced(tmp_path, runner.run)
    enters = _named(spans, "repro.stage.enter")
    assert [(e.args["capacity"], e.args["mamba_layers"],
             e.args["attn_layers"]) for e in enters] == [(10, 9, 1),
                                                         (20, 18, 2)]
    group = sorted((s.args["stack"], s.args["layers"], s.args["groups"])
                   for s in _named(spans, "repro.devft.group"))
    assert group == [("attn_mlp", 2, 1), ("mamba_mlp", 18, 9)]
    fuse = sorted((s.args["stack"], s.args["layers_in"], s.args["layers_out"])
                  for s in _named(spans, "repro.devft.fuse"))
    assert fuse == group
