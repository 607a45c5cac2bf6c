"""Surface enumeration for the lowered tier: lower/compile every
contracted program surface and return per-surface records for the
L001/L002/L004 checks (L003 operates on declared BlockLayouts — see
``layout_cases``).

Three surface kinds, mirroring the contract layer's enumeration so the
coverage claims line up:

* ``kernel:<name>:<backend>:<tag>`` — every registered kernel × every
  backend (+ ``auto``) × its bench shape family, LOWER-ONLY (kernels
  never reach SPMD partitioning; budgets are read off StableHLO text).
* ``round:<method>:<mesh>`` — the simulator's real round program
  (``make_round_program``) per registered strategy × mesh, compiled
  with the runner's ``in_shardings``/``donate_argnums`` on a forced
  multi-device host platform.
* ``serving:<arch>`` — the engine's real ``_build_step`` per serving
  arch family, compiled with the engine's ``DONATE_ARGNUMS``.

``REPRO_LOWERED_INJECT`` (collective | cost | layout | donation)
deliberately regresses one aspect of the enumerated surfaces — the
mechanism ``tests/test_lowered.py`` uses to prove each check actually
fires through the public CLI path.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

#: env var naming a deliberate regression to inject (tests only)
INJECT_ENV = "REPRO_LOWERED_INJECT"

#: (tag, shape) meshes the round program is compiled on — FSDP-heavy
#: and pure-FSDP splits of the 8 forced host devices
MESHES = (("4x2", (4, 2)), ("8x1", (8, 1)))

#: ExperimentSpec preset the round surfaces compile under (the smallest
#: committed budget — compile time is the constraint here)
ROUND_PRESET = "bench-tiny"

#: minimum host devices the sharded surfaces need
MIN_DEVICES = 8


def _inject() -> str:
    return os.environ.get(INJECT_ENV, "")


def _keep(surface: str, flt: Sequence[str]) -> bool:
    return not flt or any(f in surface for f in flt)


# ---------------------------------------------------------------------------
# kernels (lower-only)
# ---------------------------------------------------------------------------


def kernel_surfaces(flt: Sequence[str]) -> List[Dict]:
    import jax

    from repro.analysis.contracts import shapes
    from repro.analysis.lowered import costs
    from repro.kernels import dispatch

    records: List[Dict] = []
    contracts = dispatch.kernel_contracts()
    for name, backends in dispatch.available_kernels().items():
        contract = contracts.get(name)
        if contract is None:
            continue                     # C001 owns the missing-contract case
        cases = list(shapes.kernel_cases(contract.family))
        for backend in (*backends, "auto"):
            fn = dispatch.get_kernel(name, backend)
            static_extra = {}
            if dispatch.resolve(backend) == "pallas":
                # off-TPU the Pallas bodies only lower via the interpreter
                static_extra["interpret"] = dispatch.interpret_default()
            for tag, args, kwargs in cases:
                surface = f"kernel:{name}:{backend}:{tag}"
                if not _keep(surface, flt):
                    continue
                static = {k: v for k, v in kwargs.items()
                          if not isinstance(v, jax.ShapeDtypeStruct)}
                static.update(static_extra)
                operands = {k: v for k, v in kwargs.items()
                            if isinstance(v, jax.ShapeDtypeStruct)}
                rec: Dict = {"surface": surface, "kind": "kernel"}
                try:
                    lowered = jax.jit(
                        lambda *a, **kw: fn(*a, **static, **kw)).lower(
                            *args.values(), **operands)
                    text = lowered.as_text()
                    rec["collectives"] = costs.stablehlo_collective_counts(
                        text)
                    rec["transfers"] = costs.stablehlo_transfer_count(text)
                except Exception as e:
                    rec["error"] = f"{type(e).__name__}: {e}"
                records.append(rec)
    return records


# ---------------------------------------------------------------------------
# L003 layout cases
# ---------------------------------------------------------------------------


def layout_cases(flt: Sequence[str]) -> List[Tuple[str, object,
                                                   Optional[str]]]:
    """(surface, BlockLayout | None, error | None) per declared kernel
    layout × its contract shape family."""
    import jax

    from repro.analysis.contracts import shapes
    from repro.kernels import dispatch

    out: List[Tuple[str, object, Optional[str]]] = []
    contracts = dispatch.kernel_contracts()
    for name, layout_fn in sorted(dispatch.kernel_layouts().items()):
        family = contracts[name].family
        for tag, args, kwargs in shapes.kernel_cases(family):
            surface = f"layout:{name}:{tag}"
            if not _keep(surface, flt):
                continue
            static = {k: v for k, v in kwargs.items()
                      if not isinstance(v, jax.ShapeDtypeStruct)}
            try:
                out.append((surface, layout_fn(*args.values(), **static),
                            None))
            except Exception as e:
                out.append((surface, None, f"{type(e).__name__}: {e}"))
    if _inject() == "layout":
        from repro.kernels.common import BlockLayout, OperandLayout
        surface = "layout:flash_attention:injected"
        if _keep(surface, flt):
            # a (7, 100) block: sublane 7 (not a granule multiple), lane
            # 100 (neither 128-multiple nor the array dim), non-covering
            bad = BlockLayout(
                kernel="flash_attention", grid=(4, 4, 5, 1),
                operands={"q": OperandLayout((4, 4, 32, 32),
                                             (1, 1, 7, 100), "float32")},
                outputs={})
            out.append((surface, bad, None))
    return out


# ---------------------------------------------------------------------------
# federated round programs (compiled, sharded, donated)
# ---------------------------------------------------------------------------


def _require_devices(n: int) -> None:
    import jax

    if len(jax.devices()) < n:
        raise RuntimeError(
            f"sharded surfaces need {n} devices, have "
            f"{len(jax.devices())} — run via `python -m repro.analysis "
            f"--lowered` (it forces a multi-device host platform before "
            f"jax initializes)")


def round_surfaces(flt: Sequence[str]) -> List[Dict]:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.analysis.contracts.strategies import round_operands
    from repro.analysis.lowered import costs
    from repro.experiments.presets import get_preset
    from repro.federated.methods.registry import (available_methods,
                                                  make_strategy)
    from repro.federated.simulator import (ROUND_DONATE_ARGNUMS,
                                           _round_flops,
                                           make_round_program)
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import batch_shardings, params_shardings
    from repro.models import transformer as T

    records: List[Dict] = []
    inject = _inject()
    for method in available_methods():
        if not any(_keep(f"round:{method}:{tag}", flt)
                   for tag, _ in MESHES):
            continue
        spec = get_preset(ROUND_PRESET).replace(method=method)
        cfg = spec.build_cfg()
        fed = spec.fed_config()
        n_sample = max(1, int(fed.n_clients * fed.sample_frac))
        key = jax.random.PRNGKey(fed.seed)
        params = T.init_params(cfg, key, jax.numpy.float32)
        lora = T.init_lora(cfg, jax.random.fold_in(key, 1),
                           rank=fed.lora_rank)
        strategy = make_strategy(method, cfg, fed)
        lora = strategy.init_lora(params, lora)
        state = strategy.init_state(params, lora)
        stage0 = strategy.build_rounds(state)[0][0]
        strategy.on_stage(state, stage0)
        spec_l = strategy.local_spec(state)
        round_fn, aux = make_round_program(strategy, state, spec_l.cfg,
                                           n_sample, hetero=False)
        args = round_operands(spec_l, fed, n_sample, False)
        n_p = len(jax.tree.leaves(args[0]))
        donated = frozenset(range(n_p, n_p + len(jax.tree.leaves(args[1]))))
        up_expected = strategy.uplink_payload_bytes(spec_l)
        if inject == "cost":
            up_expected *= 3             # skewed analytical payload model
        analytic = {
            # the 6·N·D proxy counts ideal training math; the lowered
            # module adds aggregation/optimizer work and XLA counts scan
            # bodies once — hence a band, not an equality (DESIGN.md §13)
            "flops": _round_flops(args[0], n_sample * fed.k_local,
                                  fed.local_batch, fed.seq),
            "flops_band": (0.05, 20.0),
            "up_bytes": up_expected,
        }
        for mesh_tag, mesh_shape in MESHES:
            surface = f"round:{method}:{mesh_tag}"
            if not _keep(surface, flt):
                continue
            chips = int(np.prod(mesh_shape))
            rec: Dict = {"surface": surface, "kind": "round",
                         "chips": chips}
            try:
                _require_devices(MIN_DEVICES)
                mesh = make_mesh(mesh_shape, ("data", "model"))
                in_sh = (params_shardings(mesh, args[0]),
                         params_shardings(mesh, args[1]),
                         batch_shardings(mesh, args[2]),
                         NamedSharding(mesh, P()))
                fn = round_fn
                if inject == "collective":
                    repl = jax.tree.map(
                        lambda _: NamedSharding(mesh, P()), args[1])

                    def fn(params, lora, batches, lr, _fn=round_fn,
                           _repl=repl):
                        # force the sharded adapter tree replicated:
                        # SPMD must insert all-gathers the fingerprint
                        # does not budget for
                        lora = jax.lax.with_sharding_constraint(lora,
                                                                _repl)
                        return _fn(params, lora, batches, lr)

                donate = () if inject == "donation" \
                    else ROUND_DONATE_ARGNUMS
                # keep_unused pins HLO entry-parameter numbering to the
                # jax flat-arg order — otherwise argument pruning shifts
                # the alias table's indices under L004's feet.
                # out_shardings mirrors the runner's jit: the aggregated
                # tree is pinned to the adapter input sharding (a
                # resharded output voids its donation).
                with mesh:
                    compiled = jax.jit(
                        fn, in_shardings=in_sh,
                        out_shardings=(in_sh[1], None),
                        donate_argnums=donate,
                        keep_unused=True).lower(*args).compile()
                text = compiled.as_text()
                rec["collectives"] = costs.collective_counts(text)
                rec["transfers"] = costs.transfer_count(text)
                rec["flops_total"] = (costs.device_costs(compiled)["flops"]
                                      * chips)
                rec["aliased"] = costs.alias_sources(text)
                rec["donated"] = donated
                rec["up_traced"] = aux.get("up")
                rec["analytic"] = analytic
            except Exception as e:
                rec["error"] = f"{type(e).__name__}: {e}"
            records.append(rec)
    return records


# ---------------------------------------------------------------------------
# serving step programs (compiled, donated)
# ---------------------------------------------------------------------------


def serving_surfaces(flt: Sequence[str]) -> List[Dict]:
    import jax
    import jax.numpy as jnp

    from repro.analysis.contracts.base import avals_of
    from repro.analysis.contracts.serving import (_CAPACITY, _N_SLOTS,
                                                  _RANK, ARCH_FAMILIES,
                                                  _family_cfg, _step_fn)
    from repro.analysis.lowered import costs
    from repro.federated.simulator import count_params
    from repro.models import transformer as T
    from repro.serving.engine import ServingEngine

    SDS = jax.ShapeDtypeStruct
    records: List[Dict] = []
    inject = _inject()
    n = _N_SLOTS
    for arch in ARCH_FAMILIES:
        surface = f"serving:{arch}"
        if not _keep(surface, flt):
            continue
        rec: Dict = {"surface": surface, "kind": "serving", "chips": 1}
        try:
            cfg = _family_cfg(arch)
            key = jax.random.PRNGKey(0)
            params = avals_of(T.init_params(cfg, key, jnp.float32))
            lora = avals_of(T.init_lora(cfg, jax.random.fold_in(key, 1),
                                        rank=_RANK))
            cache = avals_of(T.init_cache(cfg, n, _CAPACITY,
                                          jnp.dtype(cfg.dtype)))
            sargs = (params, lora, SDS((n,), jnp.int32),
                     SDS((n, 1), jnp.int32), cache, SDS((n,), jnp.bool_))
            n_before = sum(len(jax.tree.leaves(a)) for a in sargs[:4])
            donated = frozenset(range(
                n_before, n_before + len(jax.tree.leaves(cache))))
            donate = () if inject == "donation" \
                else ServingEngine.DONATE_ARGNUMS
            fn = _step_fn(cfg, multi=False)
            # keep_unused=True: the shared-mode step ignores the adapter
            # index vector; pruning it would shift the alias table's
            # parameter numbering off the jax flat-arg indices
            compiled = jax.jit(
                fn, donate_argnums=donate,
                keep_unused=True).lower(*sargs).compile()
            text = compiled.as_text()
            rec["collectives"] = costs.collective_counts(text)
            rec["transfers"] = costs.transfer_count(text)
            rec["flops_total"] = costs.device_costs(compiled)["flops"]
            rec["aliased"] = costs.alias_sources(text)
            rec["donated"] = donated
            # one decode token per slot: 2·N_params·n_slots ideal flops
            rec["analytic"] = {
                "flops": 2.0 * count_params(params) * n,
                "flops_band": (0.05, 20.0),
            }
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        records.append(rec)
    return records
