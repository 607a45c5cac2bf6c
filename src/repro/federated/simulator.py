"""Federated fine-tuning simulator — the method-agnostic round engine.

Reproduces the paper's experimental protocol (App. B): N=20 devices,
10% sampled per round, K=10 local steps, LoRA rank 32 on W_q/W_v,
AdamW + staged cosine LR. Clients are simulated with ``vmap`` over the
sampled-client axis; a round is one jitted call that runs local
training AND the server aggregation, so the per-client adapter stacks
never leave the device.

Everything method-specific — submodel construction, schedules, LR
ramps, aggregation, server-side adapter transforms — lives behind the
``Strategy`` interface (``repro.federated.methods``); this engine only
samples clients, runs local training (jit-cached per sub-config), and
keeps the ``RoundLog`` books. ``FedConfig.method`` selects a strategy
from the registry, so new methods plug in without touching this file.

Mesh execution (DESIGN.md §3): pass ``mesh=`` (``make_host_mesh()`` in
CPU tests, ``make_production_mesh()`` at scale) and the engine places
params/LoRA via the FSDP×TP ``params_shardings`` rules, shards the
stacked client-batch arrays' leading sampled-client axis over the
``pod``+``data`` axes via ``batch_shardings``, and donates the
per-round LoRA buffers to the round program. ``mesh=None``
(default) runs the same trace on the default device; trajectories are
identical either way — that parity is pinned by
``tests/test_mesh_round.py``. On a mesh of more than one device the
model runs the reference path (``mesh_kernel_backend``): GSPMD cannot
partition the Pallas kernels.

Heterogeneous clients (DESIGN.md §3): ``FedConfig.population`` names a
device fleet (``repro.federated.heterogeneity``); each round the engine
realizes a host-side :class:`~repro.federated.heterogeneity.RoundPlan`
— per-client local step counts (ragged work as a step mask inside the
vmapped scan), straggler drops under ``FedConfig.straggler_policy``,
the aggregation-weight vector for ``FedConfig.weighting``, and the
round's VIRTUAL duration (max over sampled clients of profile-scaled
compute plus LoRA transfer time), accumulated into
``RoundLog.sim_time_s`` so every method comparison gains a
time-to-accuracy axis. The ``uniform`` fleet with ``uniform`` weighting
keeps the original (unmasked, unweighted) round program bit-exactly.

The round loop is device-resident: ``RoundLog`` eval scalars are
fetched one round late (after the next round's work has been
dispatched), the host prefetches round ``r+1``'s client batches while
round ``r`` computes, and eval itself runs every
``FedConfig.eval_every`` rounds (default 1; skipped rounds carry the
last evaluated values forward, and the final round always evaluates).

Host spans (``jax.profiler.TraceAnnotation``, on the device trace's
clock; free when no profiler runs): ``repro.run`` (args ``rounds``,
``method``) holds ``repro.run.prepare`` (eval batch, strategy state,
the first round's batches), one ``repro.round`` step per round (a
``StepTraceAnnotation``; args ``stage``, ``capacity``, ``clients``,
``tokens``) and ``repro.run.finalize``. A round holds, in order,
``repro.stage.enter`` (at a stage's first round; args ``stage``,
``capacity``, and on a hybrid model the stage's ``mamba_layers`` and
``attn_layers``; DevFT's ``repro.devft.transfer``, and each stack's
``repro.devft.group`` and ``repro.devft.fuse`` (arg ``stack``) nest in
it), ``repro.round.plan``, ``repro.round.place`` (arg ``bytes``),
``repro.round.dispatch``, ``repro.eval.dispatch``,
``repro.round.host_batches`` (the prefetch; arg ``bytes``),
``repro.round.fetch`` (the blocking read of the round before's eval
scalars; the last one follows the loop) and ``repro.round.books``.
Device-side, ``jax.named_scope`` marks ``local_train`` (with
``loss_and_grad`` and ``adamw``), ``aggregate`` and ``eval`` in the HLO
metadata.

Cost accounting (per paper §4.4):
* communication — exact bytes of transmitted LoRA tensors, up + down,
  per sampled client (strategies can override the byte hooks; dropped
  stragglers upload nothing);
* computation — FLOPs proxy 6·N_sub·D per round (N_sub = active submodel
  params, D = tokens actually processed under ragged local work), so
  relative speedups mirror Figure 5 without needing wall clocks;
* time — the virtual wall-clock above (``sim_time_s``, cumulative);
* memory — bytes of (submodel params + LoRA + Adam state + activation
  estimate) per device, with the activation term scaled by the *stage
  submodel's* depth and width.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.data.synthetic import (
    FederatedData,
    client_round_batches,
    keyed_rng,
)
from repro.federated.aggregation import _tree_bytes
from repro.federated.client import make_local_train
from repro.federated.heterogeneity import (
    POLICIES,
    WEIGHTINGS,
    make_population,
    plan_round,
)
from repro.federated.methods import LocalSpec, make_strategy
from repro.models import transformer as T


@dataclasses.dataclass
class FedConfig:
    n_clients: int = 20
    sample_frac: float = 0.1
    k_local: int = 10
    local_batch: int = 16
    seq: int = 64
    rounds: int = 30
    lora_rank: int = 32
    lr: float = 1e-4
    method: str = "fedit"   # any name in methods.available_methods()
    eval_every: int = 1     # eval cadence (last round always evals)
    # system-heterogeneity knobs (repro.federated.heterogeneity)
    population: str = "uniform"          # device fleet name
    straggler_policy: str = "accept-partial"
    weighting: str = "uniform"           # uniform | examples | fednova
    deadline_factor: float = 2.0         # x reference full-work time
    # DEVFT knobs
    n_stages: int = 4
    growth: float = 2.0
    initial_capacity: Optional[int] = None
    beta: float = 0.1
    grouping: str = "dglg"
    fusion: str = "dblf"
    # baseline knobs
    lr_stage_factor: float = 10.0    # paper App. B: x10 per stage
    flora_ranks: Optional[List[int]] = None
    aggregation: Optional[str] = None  # override (compatibility runs)
    seed: int = 0


@dataclasses.dataclass
class RoundLog:
    round: int
    stage: int
    capacity: int
    eval_loss: float
    eval_acc: float
    comm_bytes_up: int
    comm_bytes_down: int
    flops: float
    memory_bytes: int
    sim_time_s: float = 0.0   # cumulative virtual wall-clock (§3)
    n_dropped: int = 0        # stragglers zero-weighted this round


#: positional args of the round program donated to the jitted round on
#: mesh runs (the incoming LoRA tree — ``new_lora`` aliases it). Named
#: so the L004 lowered check verifies the SAME declaration the engine
#: jits with actually materializes as input-output aliasing.
ROUND_DONATE_ARGNUMS = (1,)


def make_round_program(strategy, run_state, sub_cfg, n_sample, *,
                       hetero: bool, remat: bool = False):
    """Build the (untraced) round program: vmapped K-step local training
    plus the strategy's (registry-dispatched) server aggregation, as ONE
    function to be jitted. Returns ``(round_fn, aux)`` where
    ``aux["up"]`` is filled with the strategy's static uplink-byte count
    at trace time.

    Single source of truth for the round program shape: the runner's
    jit cache, the semantic contract layer (``--contracts``) and the
    lowered analyzer (``--lowered``) all trace exactly this function.

    Heterogeneous programs add two traced operands: per-client step
    masks ``(C, K)`` realizing ragged local work inside the scan, and
    the per-client aggregation-weight vector ``(C,)``. ``remat``
    rematerialises each layer in the local backward.
    """
    local = make_local_train(sub_cfg, remat=remat)
    aux: Dict = {}

    if hetero:
        def round_fn(params, lora, batches, lr, masks, weights):
            def per_client(bt, m):
                return local(params, lora, bt, lr, m)

            loras, metrics = jax.vmap(per_client)(batches, masks)
            spec = LocalSpec(sub_cfg, params, lora)
            with jax.named_scope("aggregate"):
                new_lora, aux["up"] = strategy.aggregate(
                    run_state, spec, loras, n_sample, weights=weights)
            return new_lora, metrics
    else:
        def round_fn(params, lora, batches, lr):
            def per_client(bt):
                return local(params, lora, bt, lr)

            loras, metrics = jax.vmap(per_client)(batches)
            spec = LocalSpec(sub_cfg, params, lora)
            with jax.named_scope("aggregate"):
                new_lora, aux["up"] = strategy.aggregate(
                    run_state, spec, loras, n_sample)
            return new_lora, metrics

    return round_fn, aux


def mesh_kernel_backend(cfg, mesh):
    """The config a round on ``mesh`` runs with. GSPMD cannot partition
    a Pallas (Mosaic) kernel — the TPU compiler refuses one inside a
    program sharded over more than one device — so there ``auto``
    takes the reference path, and an explicit ``pallas`` is an error
    until the kernels are wrapped in ``shard_map``. One device (no mesh,
    or a 1x1 mesh) keeps the config as it is."""
    if mesh is None or mesh.size == 1:
        return cfg
    from repro.kernels.dispatch import KernelBackend, canonical
    backend = canonical(cfg.kernel_backend)
    if backend == KernelBackend.PALLAS.value:
        raise ValueError(
            f"kernel_backend='pallas' on a {mesh.size}-device mesh: Pallas "
            f"kernels cannot be partitioned by GSPMD; use 'auto' or "
            f"'reference'")
    return dataclasses.replace(cfg, kernel_backend="reference")


def count_params(tree) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(tree)))


def _step_flops(params, batch, seq) -> float:
    """FLOPs of ONE local step on this (sub)model: 6·N_sub·(B·S)."""
    n = count_params(params["blocks"]) + count_params(params.get("embed"))
    return 6.0 * n * batch * seq


def _round_flops(params, total_steps, batch, seq) -> float:
    """Round FLOPs over the steps clients actually executed."""
    return _step_flops(params, batch, seq) * total_steps


def _hybrid_depths(params) -> Dict[str, int]:
    """A hybrid (sub)model's Mamba and attention layer counts."""
    sizes = T.stack_sizes(params["blocks"])
    return {"mamba_layers": sizes.get("mamba_mlp", 0)
            + sizes.get("mamba_moe", 0),
            "attn_layers": sizes.get("attn_mlp", 0)}


def _memory_bytes(params, lora, batch, seq, cfg) -> int:
    """Per-device bytes: submodel params + LoRA + Adam moments + a rough
    activation estimate scaled by the *submodel's* depth and width (a
    4-layer stage-1 submodel must not report 32-layer activations)."""
    p = _tree_bytes(params)
    lo = _tree_bytes(lora)
    n_layers = sum(n for _, n in cfg.layer_stacks())
    act = batch * seq * cfg.d_model * 4 * n_layers
    return p + 3 * lo + act


class FederatedRunner:
    """Runs one method end-to-end on synthetic federated data.

    ``mesh=None`` (default) executes on the default device; passing a
    mesh shards the same round program over it (see module docstring).
    ``remat=True`` rematerialises each layer in the local backward, for
    a model whose local step does not fit the device otherwise.
    """

    def __init__(self, cfg, fed: FedConfig, data: FederatedData, *,
                 dtype=jnp.float32, params=None, mesh=None,
                 remat: bool = False):
        cfg = mesh_kernel_backend(cfg, mesh)
        self.cfg = cfg
        self.fed = fed
        self.data = data
        self.mesh = mesh
        self.remat = remat
        self.strategy = make_strategy(fed.method, cfg, fed)
        if fed.straggler_policy not in POLICIES:
            raise ValueError(f"unknown straggler_policy "
                             f"{fed.straggler_policy!r}; available: "
                             f"{', '.join(POLICIES)}")
        if fed.weighting not in WEIGHTINGS:
            raise ValueError(f"unknown weighting {fed.weighting!r}; "
                             f"available: {', '.join(WEIGHTINGS)}")
        if fed.deadline_factor <= 0:
            # a non-positive deadline would run the whole fleet into a
            # negative virtual clock with every client dropped
            raise ValueError(f"deadline_factor must be > 0, got "
                             f"{fed.deadline_factor}")
        self.population = make_population(fed.population, fed.n_clients,
                                          fed.seed)
        # reference fleet + uniform weighting can never produce ragged
        # work or non-uniform weights -> keep the legacy round program
        # (no mask/weight operands), which is bit-exact with pre-
        # heterogeneity trajectories. Exception: a deadline policy with
        # deadline_factor <= 1 can bind even on the reference fleet
        # (every client's full-work time IS the reference time), so the
        # plan-consuming program must be compiled there too; run()
        # additionally guards that a legacy-program round never deviates
        # from the full-work plan.
        deadline_can_bind = (fed.straggler_policy != "wait"
                             and fed.deadline_factor <= 1.0)
        self._hetero = (not self.population.is_reference) \
            or fed.weighting != "uniform" or deadline_can_bind
        key = jax.random.PRNGKey(fed.seed)
        self.params = params if params is not None \
            else T.init_params(cfg, key, dtype)
        self.lora = T.init_lora(cfg, jax.random.fold_in(key, 1),
                                rank=fed.lora_rank)
        self.lora = self.strategy.init_lora(self.params, self.lora)
        # cohort-sampling stream: keyed tuple entropy, NOT RandomState(seed)
        # — the plain-int stream collided with every other consumer of
        # fed.seed (R001); the "cohort" label isolates it by construction.
        self.rng = keyed_rng(fed.seed, "cohort")
        self._round_fn_cache: Dict = {}
        self._round_aux: Dict = {}
        self._eval_fn_cache: Dict = {}
        self._sharding_cache: Dict = {}
        self._run_state: Optional[dict] = None
        self._n_sample = max(1, int(fed.n_clients * fed.sample_frac))

    # ---- jitted round ---------------------------------------------------
    @staticmethod
    def _jit_key(sub_cfg):
        # the FULL hashable sub-config (+ resolved backend): sub-configs
        # differing in any trace-relevant field can never share a stale
        # closure (the old (n_layers, arch_id, backend) key collided)
        return sub_cfg.cache_key()

    def _round_fn(self, spec):
        """Jitted round program (``make_round_program``; traced into ONE
        device program, so ``Strategy.aggregate`` runs under trace — it
        must be functionally pure; all built-ins are)."""
        sub_cfg = spec.cfg
        key = self._jit_key(sub_cfg)
        if key not in self._round_fn_cache:
            round_fn, aux = make_round_program(
                self.strategy, self._run_state, sub_cfg, self._n_sample,
                hetero=self._hetero, remat=self.remat)
            if self.mesh is not None:
                # donate the per-round adapter buffers: new_lora aliases
                # the incoming LoRA tree (the per-client stacks and opt
                # state are jit-internal, so this closes the loop on
                # round-lifetime buffers). Batches are int32 with no
                # matching output — donating them only buys a warning.
                # out_shardings pins the aggregated tree to the SAME
                # sharding the input carries — leave it to GSPMD and an
                # effectively-replicated factor (e.g. the TP-sharded
                # "b" on a pure-FSDP mesh) can come back resharded,
                # which silently voids its donation (L004).
                _, l_sh = self._shardings(key, spec)
                fn = jax.jit(round_fn,
                             donate_argnums=ROUND_DONATE_ARGNUMS,
                             out_shardings=(l_sh, None))
            else:
                fn = jax.jit(round_fn)
            self._round_fn_cache[key] = fn
            self._round_aux[key] = aux
        return self._round_fn_cache[key], self._round_aux[key]

    def _eval_fn(self, sub_cfg):
        key = self._jit_key(sub_cfg)
        if key not in self._eval_fn_cache:
            @jax.jit
            def ev(params, lora, batch):
                with jax.named_scope("eval"):
                    _, m = T.loss_fn(sub_cfg, params, lora, batch)
                return m["loss"], m["acc"]

            self._eval_fn_cache[key] = ev
        return self._eval_fn_cache[key]

    # ---- mesh placement -------------------------------------------------
    def _shardings(self, key, spec):
        """(params, lora) NamedSharding trees for this sub-config,
        cached per jit key (FSDP×TP rules of launch/sharding.py)."""
        if key not in self._sharding_cache:
            from repro.launch.sharding import params_shardings
            self._sharding_cache[key] = (
                params_shardings(self.mesh, spec.params),
                params_shardings(self.mesh, spec.lora))
        return self._sharding_cache[key]

    def _place_model(self, spec, *, fresh: bool):
        """Place the round's model view on the mesh (no-op when the
        arrays already carry the right sharding — steady-state rounds
        re-place nothing).

        ``fresh`` marks stage-entry rounds, where the adapter tree came
        from the strategy rather than the previous round's output. The
        round program donates its LoRA input, and a strategy-built tree
        may alias long-lived strategy state (e.g. ProgFed's final-stage
        prefix IS the global tree — jax's identity-slice fast path
        returns the same buffers), so the engine copies it once per
        stage and only ever donates buffers it owns."""
        if self.mesh is None:
            return spec.params, spec.lora
        lora = jax.tree.map(jnp.copy, spec.lora) if fresh else spec.lora
        p_sh, l_sh = self._shardings(self._jit_key(spec.cfg), spec)
        return (jax.device_put(spec.params, p_sh),
                jax.device_put(lora, l_sh))

    def _place_batches(self, batches):
        """Host batches -> device, sampled-client axis sharded over the
        pod+data mesh axes (replicated everywhere when mesh=None)."""
        if self.mesh is None:
            return {k: jnp.asarray(v) for k, v in batches.items()}
        from repro.launch.sharding import batch_shardings
        return jax.device_put(batches, batch_shardings(self.mesh, batches))

    # ---- host-side round prep -------------------------------------------
    def _host_batches(self, rnd: int):
        """Sample this round's clients and build their batches on the
        host (numpy); returns ``(clients, batches)``. Called one round
        ahead so batch generation overlaps the previous round's device
        compute; the sequential ``rng.choice`` order (one call per
        round) on the dedicated ``keyed_rng(seed, "cohort")`` stream is
        preserved. The batch seed is the ``(seed, round)`` SeedSequence
        key — the old ``seed * 10_000 + rnd`` arithmetic collided
        across base seeds."""
        fed = self.fed
        clients = self.rng.choice(fed.n_clients, self._n_sample,
                                  replace=False)
        return clients, client_round_batches(
            self.data, clients, fed.k_local, fed.local_batch, fed.seq,
            seed=(fed.seed, rnd))

    def _plan(self, spec, clients, rnd):
        """This round's heterogeneity realization (pure numpy; the
        ``uniform`` fleet yields full work, no drops, and the legacy
        uniform weights). Transfer terms use the strategy's payload
        hooks so the clock agrees with the comm-bytes accounting
        (FedSA's A-only uplink is charged as A-only time)."""
        fed, strat = self.fed, self.strategy
        return plan_round(
            self.population, clients, rnd,
            k_local=fed.k_local,
            step_flops=_step_flops(spec.params, fed.local_batch, fed.seq),
            up_bytes=strat.uplink_payload_bytes(spec),
            down_bytes=strat.downlink_payload_bytes(spec),
            policy=fed.straggler_policy, weighting=fed.weighting,
            deadline_factor=fed.deadline_factor,
            batch=fed.local_batch, seq=fed.seq)

    # ---- main loop ------------------------------------------------------
    def run(self, progress: Optional[Callable] = None) -> List[RoundLog]:
        fed = self.fed
        if fed.eval_every < 1:
            raise ValueError(f"eval_every must be >= 1, got "
                             f"{fed.eval_every}")
        with TraceAnnotation("repro.run", rounds=fed.rounds,
                             method=fed.method):
            return self._run(progress)

    def _run(self, progress: Optional[Callable]) -> List[RoundLog]:
        fed, strat = self.fed, self.strategy
        logs: List[RoundLog] = []
        n_sample = self._n_sample
        with TraceAnnotation("repro.run.prepare"):
            eval_batch = self._place_batches(
                self.data.eval_batch(16, fed.seq))
            state = strat.init_state(self.params, self.lora)
            self._run_state = state
            rounds = list(strat.build_rounds(state))
            n_rounds = len(rounds)
            clients, batches = self._host_batches(0) if n_rounds \
                else (None, None)
        stage_prev = -1
        pending: Optional[RoundLog] = None
        ev_loss = ev_acc = None          # device scalars, carried forward
        sim_time = 0.0                   # cumulative virtual wall-clock
        for rnd, (stage, capn) in enumerate(rounds):
            with StepTraceAnnotation("repro.round", step_num=rnd,
                                     stage=stage, capacity=capn,
                                     clients=n_sample) as round_span:
                stage_entry = stage != stage_prev
                if stage_entry:
                    with TraceAnnotation("repro.stage.enter", stage=stage,
                                         capacity=capn) as sp:
                        strat.on_stage(state, stage)
                        if self.cfg.family == "hybrid":
                            sp.set_metadata(**_hybrid_depths(
                                strat.local_spec(state).params))
                    stage_prev = stage
                with TraceAnnotation("repro.round.plan"):
                    spec = strat.local_spec(state)
                    plan = self._plan(spec, clients, rnd)
                    if not self._hetero and (
                            plan.n_dropped
                            or plan.total_steps != n_sample * fed.k_local):
                        # defense in depth: the legacy program ignores
                        # the plan, so a plan that deviates from full
                        # uniform work must never reach it (the _hetero
                        # gate should have engaged)
                        raise RuntimeError(
                            "internal: round plan deviates from full work "
                            "but the legacy round program is compiled "
                            f"(policy={fed.straggler_policy!r}, "
                            f"deadline_factor={fed.deadline_factor})")
                round_span.set_metadata(
                    tokens=plan.total_steps * fed.local_batch * fed.seq)
                sim_time += plan.duration_s

                # ---- local training + aggregation (one device program)
                lr = strat.client_lr(stage)
                with TraceAnnotation("repro.round.place",
                                     bytes=_tree_bytes(batches)):
                    dev_batches = self._place_batches(batches)
                    params_p, lora_p = self._place_model(
                        spec, fresh=stage_entry)
                with TraceAnnotation("repro.round.dispatch"):
                    round_fn, aux = self._round_fn(spec)
                    if self._hetero:
                        new_lora, _metrics = round_fn(
                            params_p, lora_p, dev_batches, jnp.float32(lr),
                            jnp.asarray(plan.step_mask),
                            jnp.asarray(plan.weights))
                    else:
                        new_lora, _metrics = round_fn(params_p, lora_p,
                                                      dev_batches,
                                                      jnp.float32(lr))
                    up_bytes = aux["up"]
                    new_lora = strat.post_round(state, new_lora)

                # ---- eval (every eval_every rounds; last round always)
                if rnd % fed.eval_every == 0 or rnd == n_rounds - 1:
                    with TraceAnnotation("repro.eval.dispatch"):
                        ev_loss, ev_acc = self._eval_fn(spec.cfg)(
                            params_p, new_lora, eval_batch)

                # ---- overlap: prefetch round r+1 while round r computes
                if rnd + 1 < n_rounds:
                    with TraceAnnotation("repro.round.host_batches") as sp:
                        clients, batches = self._host_batches(rnd + 1)
                        sp.set_metadata(bytes=_tree_bytes(batches))

                # ---- accounting (previous round's scalars fetched only
                #      after this round's work has been dispatched) ------
                if pending is not None:
                    logs.append(self._fetch(pending))
                    if progress:
                        progress(logs[-1])
                with TraceAnnotation("repro.round.books"):
                    n_kept = int(plan.kept.sum())
                    pending = RoundLog(
                        round=rnd, stage=stage, capacity=capn,
                        eval_loss=ev_loss, eval_acc=ev_acc,
                        # dropped stragglers never upload; every sampled
                        # client still downloaded the round's adapters
                        comm_bytes_up=strat.uplink_bytes(up_bytes, n_kept),
                        comm_bytes_down=strat.downlink_bytes(new_lora,
                                                             n_sample),
                        flops=_round_flops(spec.params, plan.total_steps,
                                           fed.local_batch, fed.seq),
                        memory_bytes=_memory_bytes(spec.params, new_lora,
                                                   fed.local_batch, fed.seq,
                                                   spec.cfg),
                        sim_time_s=sim_time,
                        n_dropped=plan.n_dropped,
                    )
        if pending is not None:
            logs.append(self._fetch(pending))
            if progress:
                progress(logs[-1])

        with TraceAnnotation("repro.run.finalize"):
            self.lora = strat.finalize(state)
        self._run_state = None
        return logs

    @staticmethod
    def _fetch(log: RoundLog) -> RoundLog:
        """Materialise a pending log's device scalars (the only blocking
        reads in the loop)."""
        with TraceAnnotation("repro.round.fetch"):
            log.eval_loss = float(log.eval_loss)
            log.eval_acc = float(log.eval_acc)
        return log
