"""Round cells of the Mamba-2 / attention hybrid (Granite 4.0-H): whole
DevFT or FedIT schedules of ``FederatedRunner.run`` on one chip, as
``round_cell`` runs them, with the hybrid's parts.

The program's config is built from the configuration file and every key
the file states is checked against what the program runs. The weights
(``weights_hybrid``), the reference (``reference_hybrid``) and the
operation counts (``flops_hybrid``) are the hybrid's own. The tap on
the round and eval programs, the program's readings and the gaps are
``round_cell``'s; the set-up is ``round_cell.setup``'s on one chip, with
the workload's ``remat`` given to the runner and the program's groups of
every stage entry kept. The reference recomputes the compared
rounds with DevFT's fusion and transfer applied to each stack (the Mamba
layers, the attention layers) on its own, at the stack capacities the
stage splits its layers into, from the program's groups once it has
judged them (``group_gap``): two sound spectral groupings of these
layers can differ (``reference_hybrid``).

The numbers compared are ``round_cell.gaps``' ``loss_gap`` and
``change_gap``, and ``group_gap``.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops_hybrid, harness, round_cell, weights_hybrid
from chipbench import reference_hybrid as RH

STACKS = ("mamba_mlp", "attn_mlp")

#: the numbers ``correct`` is judged on, each against its limit
CHECKED = ("loss_gap", "change_gap", "group_gap")


def program_cfg(m: dict):
    """The program's config for the file's sizes; every key the file
    states must be what the program runs."""
    from repro.configs import MambaConfig, get_config
    from repro.models import mamba2, transformer as T

    period, offset = RH.period_offset(m)
    cfg = dataclasses.replace(
        get_config(m["arch"]), n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["shared_intermediate_size"], vocab=m["vocab_size"],
        attn_period=period, attn_offset=offset,
        mamba=MambaConfig(d_state=m["mamba_d_state"],
                          expand=m["mamba_expand"],
                          head_dim=m["mamba_d_head"],
                          n_groups=m["mamba_n_groups"],
                          conv_width=m["mamba_d_conv"],
                          chunk=m["mamba_chunk_size"]),
        dtype=m["dtype"], kernel_backend="auto")
    kinds = ["attention" if name == "attn_mlp" else "mamba"
             for name, _ in T.execution_order(cfg)]
    runs = {
        "layer_types": kinds, "attention_bias": cfg.qkv_bias,
        "attention_multiplier": cfg.attention_multiplier,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "intermediate_size": cfg.d_ff, "hidden_act": "silu",
        "normalization_function": "rmsnorm",
        "position_embedding_type": "nope" if cfg.nope else "rope",
        "num_local_experts": 0 if cfg.moe is None else cfg.moe.n_experts,
        "num_experts_per_tok": 0 if cfg.moe is None else cfg.moe.top_k,
        "mamba_n_heads": mamba2.n_heads(cfg),
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings, "dtype": cfg.dtype}
    for k, v in runs.items():
        if m[k] != v:
            raise harness.BenchError(f"{m['name']}: the file states {k}="
                                     f"{m[k]!r}, the program runs {v!r}")
    return cfg


PARTS = round_cell.Parts(program_cfg=program_cfg, weights=weights_hybrid,
                         reference=RH, flops=flops_hybrid)


def _model(cell) -> dict:
    """The cell's model block with the file's ``layer_types`` (a list,
    which the harness leaves out of it at the full size)."""
    cell.model.setdefault("layer_types", cell.config["layer_types"])
    return cell.model


def setup(cell, devices):
    """``round_cell.setup`` on one chip, with the workload's ``remat``
    given to the runner; the program's groups of each stage entry
    of the set-up schedule are kept as ``tap.groups`` (per stage, stack
    -> groups)."""
    from repro.federated import FederatedRunner
    from repro.models import transformer as T

    m, w = _model(cell), cell.params
    cfg = program_cfg(m)
    key = jax.random.PRNGKey(0)
    base = weights_hybrid.base_params(m, cell.seed)
    round_cell.check_tree(base, cfg, lambda: T.init_params(cfg, key))
    lora0 = weights_hybrid.init_lora(m, cell.seed, w["lora_rank"])
    round_cell.check_tree(lora0, cfg,
                          lambda: T.init_lora(cfg, key, w["lora_rank"]))
    data = round_cell.make_data(m["vocab_size"], w["n_clients"], cell.seed)
    runner = FederatedRunner(cfg, round_cell.fed_config(w, cell.seed), data,
                             dtype=jnp.dtype(m["dtype"]), params=base,
                             remat=w.get("remat", False))
    runner.lora = runner.strategy.init_lora(base, lora0)
    tap = round_cell.Tap(runner, w["rounds"])
    tap.groups = []
    on_stage = runner.strategy.on_stage

    def kept(state, stage):
        on_stage(state, stage)
        plan = state["sub"].plan if "sub" in state else {}
        tap.groups.append({k: v["groups"] for k, v in plan.items()})

    runner.strategy.on_stage = kept
    with harness.span("schedule"):
        runner.run()
        jax.block_until_ready(runner.lora)
    tap.close()
    del runner.strategy.on_stage
    return runner, base, lora0, tap


def run(cell, devices, meter) -> dict:
    m, w = _model(cell), cell.params
    runner, base, lora0, tap = setup(cell, devices)
    counts = round_cell.schedule_counts(m, w, PARTS)
    n_sched = 0
    with cell.window(meter):
        while True:
            with harness.span("schedule"):
                runner.run()
                jax.block_until_ready(runner.lora)
            n_sched += 1
            if time.perf_counter() - cell.t_window >= cell.seconds:
                break
    peak = harness.memory_peak_bytes(devices)
    del runner
    gc.collect()
    checks = check_rounds(m, w, base, lora0, tap)
    tokens = n_sched * counts["train_tokens"]
    return {
        "e2e": {"setup_s": cell.setup_s,
                "train_tokens_per_s": tokens / cell.window_s},
        "counts": dict(counts, schedules=n_sched),
        "checks": checks,
        "attempted": n_sched * counts["rounds"], "failed": 0,
        "memory_peak_bytes": peak,
    }


def compared(got, want) -> dict:
    """``round_cell.gaps``' numbers and ``group_gap``: the largest over
    the schedule's stage entries (None where one had no partition)."""
    g = round_cell.gaps(got, want)
    entries = [r["group_gap"] for r in want]
    g["group_gap"] = None if not want or None in entries else max(entries)
    return g


def readings(cell, devices, control: bool, meter) -> list:
    """The numbers the cell compares, from the set-up schedule, and with
    ``control`` then ``unchanged``, ``random_groups``, those of the
    half-batch fault and those of the control (the reference with its
    base rounded to float8), in that order: the control's base takes the
    program's place leaf by leaf, so that the whole model is held about
    once. ``unchanged`` is ``loss_gap`` of a
    program that returned each compared round's adapter unchanged (its
    eval then reads the round's input adapter); ``random_groups`` is
    the least of ten ``group_gap`` a program that grouped at random
    would read (``reference_rounds``' ``random_gap``)."""
    m, w = _model(cell), cell.params
    runner, base, lora0, tap = setup(cell, devices)
    del runner
    gc.collect()
    if not round_cell.recorded(w, tap):
        raise harness.BenchError("the set-up schedule was not recorded")
    want = reference_rounds(m, w, base, lora0, tap, unchanged=control)
    got = round_cell.program_rounds(tap, want)
    out = [("program", compared(got, want))]
    if control:
        out.append(("unchanged", _unchanged_loss_gap(want, got)))
        out.append(("random_groups", float(want[-1]["random_gap"].min())))
        out.append(("half_batch", compared(got, reference_rounds(
            m, w, base, lora0, tap, half_batch=True))))
        _quantize_in_place(base)
        out.append(("control", compared(got, reference_rounds(
            m, w, base, lora0, tap))))
    out.append(("groups", [r["groups"] for r in want]))
    out.append(("peak", harness.memory_peak_bytes(devices)))
    del base, lora0, tap
    jax.clear_caches()
    return out


def _unchanged_loss_gap(want, got) -> float:
    """``loss_gap`` had each compared round returned its input adapter:
    its eval then reads what the reference's eval of that input reads."""
    gap = 0.0
    for r, g in zip(want, got):
        pairs = zip(g["loss_first"] + g["loss_last"] + [r["eval_in"]],
                    r["loss_first"] + r["loss_last"] + [r["eval"]])
        gap = max([gap] + [abs(a - b) / abs(b) for a, b in pairs])
    return gap


def _quantize_in_place(base):
    """``reference.quantize`` a leaf at a time, each rounded leaf put in
    its original's place, so that the whole base is held about once."""
    base["embed"] = RH.quantize({"embed": base["embed"],
                                 "blocks": {}})["embed"]
    for stack in base["blocks"].values():
        for group in [stack] + [v for v in stack.values()
                                if isinstance(v, dict)]:
            for name, leaf in list(group.items()):
                if not isinstance(leaf, dict):
                    group[name] = RH.quantize(
                        {"blocks": {name: leaf}})["blocks"][name]


def reference_rounds(m, w, base, lora0, tap, *, half_batch=False,
                     unchanged=False):
    """``round_cell.reference_rounds`` for the hybrid: at a stage's first
    round each stack is fused and handed the last stage's adapter on its
    own, at the stage's capacity for it, with the groups the program
    formed there once ``group_gap`` has judged them (with the
    reference's own where they are no partition). Fused layers are
    computed in float32 a leaf at a time and kept in the base's dtype,
    as the program keeps them. Each compared round also carries
    ``group_gap``, the largest over the stage entries since the last
    compared round, and ``random_gap``, ten draws of what ``group_gap``
    would read so far had every stage entry's groups been a random
    partition into the program's group sizes; with ``unchanged`` also
    ``eval_in``, the eval loss of the round's input adapter."""
    caps, per, lr_of = round_cell._schedule(m, w, RH)
    depth = weights_hybrid.stack_depths(m)
    check = set(w["check_rounds"])
    rng = np.random.default_rng(0)
    def steps(p, lo, b, lr):
        return RH.adamw_steps(m, p, lo, b, lr)
    out, stage, glob, sub_lora, groups = [], -1, lora0, None, None
    entry_gap, random_gap = 0.0, np.zeros(10)
    with jax.default_matmul_precision("highest"):
        for r, rec in enumerate(tap.rounds):
            st = min(r // per, len(caps) - 1)
            if st != stage:
                if stage >= 0:
                    glob = {k: RH.broadcast(sub_lora[k], groups[k], depth[k])
                            for k in STACKS}
                sizes = RH.stack_capacities(m, caps[st])
                mine = tap.groups[st] if st < len(tap.groups) else {}
                blocks, sub_lora, groups = {}, {}, {}
                for k in STACKS:
                    stack = base["blocks"][k]
                    if sizes[k] < depth[k]:
                        gram = RH.layer_gram(stack, glob[k])
                        gap = RH.group_gap(gram, mine.get(k, []), sizes[k])
                        groups[k] = mine[k] if gap is not None else \
                            RH.spectral_groups(gram, sizes[k])
                        entry_gap = None if None in (entry_gap, gap) \
                            else max(entry_gap, gap)
                        random_gap = np.maximum(random_gap, _random_gaps(
                            gram, groups[k], rng, len(random_gap)))
                        blocks[k] = jax.tree.map(
                            lambda a, g=groups[k]: RH.fuse(
                                a, g, w["beta"]).astype(a.dtype), stack)
                        sub_lora[k] = RH.fuse(glob[k], groups[k], w["beta"])
                    else:
                        groups[k] = [[i] for i in range(depth[k])]
                        blocks[k], sub_lora[k] = stack, glob[k]
                sub = dict(base, blocks=blocks)
                stage = st
            if r in check:
                out.append(round_cell._reference_round(
                    m, sub, sub_lora, rec, tap.evals[r], lr_of(st), steps,
                    half_batch, RH))
                out[-1].update(round=r, groups=groups, group_gap=entry_gap,
                               random_gap=random_gap.copy())
                if unchanged:
                    out[-1]["eval_in"] = RH.eval_loss(m, sub, sub_lora, {
                        k: jnp.asarray(v)
                        for k, v in tap.evals[r]["batch"].items()})
                entry_gap = 0.0
            # the next round starts from what the program returned
            sub_lora = jax.tree.map(jnp.asarray, rec["lora_out"])
    return out


def _random_gaps(gram, groups, rng, draws: int) -> np.ndarray:
    """``group_gap`` of ``draws`` random partitions of the layers into
    groups of the sizes of ``groups``."""
    cuts = np.cumsum([len(g) for g in groups])[:-1]
    return np.array([RH.group_gap(gram, sorted(
        sorted(p) for p in np.split(rng.permutation(len(gram)), cuts)),
        len(groups)) for _ in range(draws)])


def check_rounds(m, w, base, lora0, tap):
    """Each number compared, with its limit from the workload file."""
    limits = w.get("limits", {})
    if round_cell.recorded(w, tap):
        want = reference_rounds(m, w, base, lora0, tap)
        g = compared(round_cell.program_rounds(tap, want), want)
    else:
        print(f"the set-up schedule ran {len(tap.rounds)} round and "
              f"{len(tap.evals)} eval programs through the runner's own "
              f"methods, not {w['rounds']}: nothing is compared",
              file=sys.stderr, flush=True)
        g = {}
    return {k: (g.get(k), limits.get(k)) for k in CHECKED}
