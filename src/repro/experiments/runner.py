"""run_experiment — the single entry point every driver routes through.

``run_experiment(spec)`` materializes the model config, the synthetic
federated data, and (when ``spec.pretrain_steps > 0``) the shared
pre-trained base, then runs the method-agnostic round engine and returns
a structured :class:`RunResult`.

The pre-trained-base cache is keyed on ``spec.base_key()`` — a hash of
the full-spec projection that actually determines the base (model shape
incl. vocab, ``seq``, pretrain protocol, seed) — so specs that differ
only in method/rounds/aggregation share one base, while any change to
the model or pretrain setup is a guaranteed cache miss.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.data import make_federated_data
from repro.experiments.results import RunResult, summarize
from repro.experiments.spec import ExperimentSpec
from repro.federated.simulator import FederatedRunner

_BASE_CACHE: Dict[str, Tuple] = {}


def clear_base_cache() -> None:
    _BASE_CACHE.clear()


def pretrained_base(spec: ExperimentSpec):
    """(params, pretrain_loss) for this spec's base model, cached on
    ``spec.base_key()`` (DESIGN.md §7: the paper fine-tunes *pretrained*
    models, so benchmarks briefly pre-train on a disjoint corpus)."""
    key = spec.base_key()
    if key not in _BASE_CACHE:
        from repro.federated.pretrain import centralized_pretrain
        from repro.models import transformer as T

        cfg = spec.build_cfg()
        params = T.init_params(cfg, jax.random.PRNGKey(spec.seed),
                               spec.base_dtype())
        if spec.homogeneous_init:
            # identical-layer init: the functional-homogeneity regime of
            # large pretrained LLMs that DGLG/DBLF assume
            params["blocks"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[:1], a.shape),
                params["blocks"])
        # pre-train on a DIFFERENT task (generic "pre-training corpus"),
        # fine-tune federatedly on the real one — else there is nothing
        # left to adapt
        pre_data = make_federated_data(cfg.vocab,
                                       n_clients=spec.n_clients,
                                       alpha=0.5, noise=0.0,
                                       seed=(spec.seed, "pretrain-corpus"))
        params, loss = centralized_pretrain(
            cfg, params, pre_data, steps=spec.pretrain_steps,
            batch=16, seq=spec.seq, lr=3e-3, seed=spec.seed)
        _BASE_CACHE[key] = (params, loss)
    return _BASE_CACHE[key]


def run_experiment(spec: ExperimentSpec, *,
                   round_progress: Optional[Callable] = None,
                   data=None, params=None,
                   export_adapters: bool = False) -> RunResult:
    """Run one spec end-to-end. ``round_progress(RoundLog)`` fires
    after every round (same name and shape as in ``sweep``).
    ``data``/``params`` are escape hatches for callers that already
    hold them (tests); by default both derive from the spec.

    ``export_adapters=True`` closes the train->serve loop: the result's
    ``adapter_registry`` holds the aggregated global adapter plus one
    personalized adapter per client (a few local steps on each client's
    own data), ready to pass to ``repro.serving.ServingEngine``."""
    cfg = spec.build_cfg()
    pretrain_loss = None
    if params is None and spec.pretrain_steps:
        params, pretrain_loss = pretrained_base(spec)
    if data is None:
        data = make_federated_data(cfg.vocab, n_clients=spec.n_clients,
                                   alpha=spec.alpha, noise=spec.noise,
                                   seed=spec.seed)
    from repro.launch.mesh import resolve_mesh
    runner = FederatedRunner(cfg, spec.fed_config(), data, params=params,
                             dtype=spec.base_dtype(),
                             mesh=resolve_mesh(spec.mesh))
    t0 = time.time()
    logs = runner.run(round_progress)
    wall = time.time() - t0
    result = RunResult(spec=spec, logs=logs, wall_s=wall,
                       metrics=summarize(logs, wall),
                       pretrain_loss=pretrain_loss,
                       final_lora=runner.lora)
    if export_adapters:
        from repro.serving import registry_from_run
        result.adapter_registry = registry_from_run(result, runner.params,
                                                    data)
    return result
