"""Round cells: whole DevFT or FedIT schedules of ``FederatedRunner.run``
on one runner.

Set-up makes the base, the starting adapter and the clients' data from
the seed, builds the runner (on the workload's ``mesh`` where it names
one, with the base and the adapter made in the shardings the runner
places them with), and drives it through one whole schedule:
that compiles every stage's round and eval programs and records, for
every round, what the round and eval programs were given and what they
returned (the same runner then serves the window). The window runs
schedules back to back and ends with the first one that finishes after
``--seconds``. Once it has closed, the reference recomputes the rounds
that the workload's ``check_rounds`` lists (in the cells, the first
round of every stage), each from the program's state before it, with
the stage transfer into it, and the two are compared.

What is particular to a model's block comes in as ``Parts``: the
program's config for the file's sizes, the benchmark's weight maker and
layout, the plain reference, and the operation counts. A kind of cell
for another block is a file ``chipbench/<kind>_cell.py`` whose ``run``
and ``readings`` call this module's with its own ``Parts``.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from types import ModuleType
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, harness, reference as R, weights

# ---------------------------------------------------------------------------
# the program side
# ---------------------------------------------------------------------------


def program_cfg(m: dict):
    """The program's model config for the sizes in ``m``; every width it
    states must equal the file's."""
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(m["arch"])
    kw = {"n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
          "n_heads": m["num_attention_heads"],
          "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
          "vocab": m["vocab_size"], "kernel_backend": "auto"}
    kw["d_ff"] = m["intermediate_size"]
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=m["num_local_experts"],
            top_k=m["num_experts_per_tok"], d_ff_expert=m["intermediate_size"],
            capacity_factor=m["capacity_factor"],
            router_aux_coef=m["router_aux_loss_coef"])
    cfg = dataclasses.replace(cfg, **kw)
    stated = {"qkv_bias": cfg.qkv_bias, "rope_theta": cfg.rope_theta,
              "rms_norm_eps": cfg.norm_eps,
              "tie_word_embeddings": cfg.tie_embeddings, "dtype": cfg.dtype}
    for k, v in stated.items():
        if m[k] != v:
            raise harness.BenchError(f"{m['name']}: the file states {k}="
                                     f"{m[k]!r}, the program runs {v!r}")
    return cfg


def check_tree(mine, cfg, init):
    """The benchmark's tree has the program's structure, shapes and
    dtypes (``init`` is the program's own initializer)."""
    want = jax.eval_shape(init)
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), mine)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise harness.BenchError("the benchmark's weights do not match the "
                                 "program's layout")


def make_data(vocab: int, n_clients: int, seed: int):
    """The clients' corpora (a shared bigram permutation mixed with one of
    each client's own), drawn from the seed by the benchmark."""
    from repro.data.synthetic import FederatedData

    rng = np.random.default_rng([seed, 0xDA7A])
    return FederatedData(
        vocab=vocab, n_clients=n_clients,
        global_perm=rng.permutation(vocab),
        client_perms=np.stack([rng.permutation(vocab)
                               for _ in range(n_clients)]),
        mix=rng.dirichlet([0.5, 0.5], size=n_clients)[:, 0], noise=0.05)


class Tap:
    """Records the first ``n_rounds`` calls of the round and eval
    programs, their inputs and outputs, while ``on``; the calls
    themselves are the runner's own. On a mesh the round program donates
    its adapter, which the next round then takes in: the record keeps a
    copy of each round's output."""

    def __init__(self, runner, n_rounds: int):
        self.rounds, self.evals, self.n = [], [], n_rounds
        self.on = True
        self._runner = runner
        round_fn, eval_fn = runner._round_fn, runner._eval_fn
        keep = (lambda t: jax.tree.map(jnp.copy, t)) \
            if runner.mesh is not None else (lambda t: t)

        def tapped_round(spec):
            fn, aux = round_fn(spec)

            def call(params, lora, batches, lr, *rest):
                new, metrics = fn(params, lora, batches, lr, *rest)
                if self.on and len(self.rounds) < self.n:
                    self.rounds.append({"lora_out": keep(new),
                                        "batches": batches, "lr": lr,
                                        "metrics": metrics})
                return new, metrics
            return call, aux

        def tapped_eval(sub_cfg):
            fn = eval_fn(sub_cfg)

            def call(params, lora, batch):
                out = fn(params, lora, batch)
                if self.on and len(self.evals) < self.n:
                    self.evals.append({"batch": batch, "loss": out[0]})
                return out
            return call

        runner._round_fn, runner._eval_fn = tapped_round, tapped_eval

    def close(self):
        """Stop recording, hand the records to the host, and give the
        runner back its own methods."""
        self.on = False
        del self._runner._round_fn, self._runner._eval_fn
        self.rounds, self.evals = jax.device_get((self.rounds, self.evals))


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Parts:
    """What a round cell takes from its model's block: the program's
    config for the file's sizes (``program_cfg(m)``), the benchmark's
    weights (``base_shapes``, ``base_params``, ``init_lora``), the plain
    reference (``adamw_steps``, ``eval_loss``, ``quantize`` and DevFT's
    ``capacities``, ``stage_lr``, ``layer_gram``, ``spectral_groups``,
    ``fuse``, ``broadcast``) and the operation counts (``train_flops``,
    ``forward_flops``)."""
    program_cfg: Callable = program_cfg
    weights: ModuleType = weights
    reference: ModuleType = R
    flops: ModuleType = flops


#: this module's own block: dense or MoE GQA decoders
PARTS = Parts()

#: ``FedConfig`` fields a workload file may set
FED_KEYS = ("n_clients", "sample_frac", "k_local", "local_batch", "seq",
            "rounds", "lora_rank", "lr", "method", "n_stages", "growth",
            "beta", "lr_stage_factor")


def fed_config(w: dict, seed: int):
    from repro.federated.simulator import FedConfig

    return FedConfig(eval_every=1, seed=seed % (1 << 31),
                     **{k: w[k] for k in FED_KEYS if k in w})


def cell_mesh(cell, devices):
    """The workload's ``mesh`` (axis name -> size, in order) over its
    devices, or None for one device."""
    spec = cell.workload.get("mesh")
    if spec is None:
        return None
    from repro.launch.mesh import make_mesh

    shape = tuple(spec.values())
    if int(np.prod(shape)) != len(devices):
        raise harness.BenchError(f"{cell.name}: mesh {spec} does not cover "
                                 f"its {len(devices)} devices")
    return make_mesh(shape, tuple(spec), devices=list(devices))


def setup(cell, devices, parts: Parts = PARTS):
    """Everything before the window: returns ``(runner, base, lora0,
    tap)`` with one whole schedule driven through ``runner``."""
    from repro.federated import FederatedRunner
    from repro.models import transformer as T

    m, w = cell.model, cell.params
    cfg = parts.program_cfg(m)
    key = jax.random.PRNGKey(0)
    mesh = cell_mesh(cell, devices)
    base_sh = lora_sh = None
    if mesh is not None:
        from repro.launch.sharding import params_shardings

        base_sh = params_shardings(mesh, parts.weights.base_shapes(m))
        lora_sh = params_shardings(mesh, jax.eval_shape(
            lambda: parts.weights.init_lora(m, 0, w["lora_rank"])))
    base = parts.weights.base_params(m, cell.seed, base_sh)
    check_tree(base, cfg, lambda: T.init_params(cfg, key))
    lora0 = parts.weights.init_lora(m, cell.seed, w["lora_rank"], lora_sh)
    check_tree(lora0, cfg, lambda: T.init_lora(cfg, key, w["lora_rank"]))
    fed = fed_config(w, cell.seed)
    data = make_data(m["vocab_size"], w["n_clients"], cell.seed)
    runner = FederatedRunner(cfg, fed, data, dtype=jnp.dtype(m["dtype"]),
                             params=base, mesh=mesh)
    runner.lora = runner.strategy.init_lora(base, lora0)
    tap = Tap(runner, w["rounds"])
    with harness.span("schedule"):
        runner.run()
        jax.block_until_ready(runner.lora)
    tap.close()
    return runner, base, lora0, tap


def _schedule(m: dict, w: dict, R: ModuleType = R):
    """``(capacities, rounds per stage, stage -> client lr)``: DevFT's
    growing stages, or FedIT's one stage of the whole model."""
    n = m["num_hidden_layers"]
    if w["method"] == "fedit":
        return [n], w["rounds"], lambda st: w["lr"]
    if w["method"] != "devft":
        raise harness.BenchError(f"no reference for method {w['method']!r}")
    caps = R.capacities(n, w["n_stages"], w["growth"])
    return caps, w["rounds"] // w["n_stages"], lambda st: R.stage_lr(
        w["lr"], w["lr_stage_factor"], st, w["n_stages"])


def schedule_counts(m: dict, w: dict, parts: Parts = PARTS) -> dict:
    """Tokens and model operations of one whole schedule."""
    fl = parts.flops
    n_sample = max(1, int(w["n_clients"] * w["sample_frac"]))
    caps, per, _ = _schedule(m, w, parts.reference)
    rounds = [caps[min(r // per, len(caps) - 1)] for r in range(w["rounds"])]
    tok = n_sample * w["k_local"] * w["local_batch"] * w["seq"]
    ev = w["eval_rows"] * w["seq"]
    train = sum(tok * fl.train_flops(m, c, w["seq"], w["lora_rank"])
                for c in rounds)
    evalf = sum(ev * fl.forward_flops(m, c, (w["seq"] + 1) / 2,
                                      w["lora_rank"]) for c in rounds)
    return {"rounds": len(rounds), "capacities": rounds,
            "train_tokens": tok * len(rounds), "train_flops": train,
            "eval_flops": evalf, "n_sample": n_sample}


def run(cell, devices, meter, parts: Parts = PARTS) -> dict:
    m, w = cell.model, cell.params
    runner, base, lora0, tap = setup(cell, devices, parts)
    counts = schedule_counts(m, w, parts)
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
    checks = check_rounds(m, w, base, lora0, tap, parts.reference)
    tokens = n_sched * counts["train_tokens"]
    return {
        "e2e": {"setup_s": cell.setup_s,
                "train_tokens_per_s": tokens / cell.window_s},
        "counts": dict(counts, schedules=n_sched),
        "checks": checks,
        "attempted": n_sched * counts["rounds"], "failed": 0,
        "memory_peak_bytes": peak,
    }


def readings(cell, devices, control: bool, meter,
             parts: Parts = PARTS) -> list:
    """The numbers the cell compares, from the set-up schedule of the
    timed path, and with ``control`` those of the control (the reference
    with its base rounded to float8 in the program's place) and of the
    fault of a step that trains on half of each batch (the reference so
    planted): ``[(what, {number: value}), ...]`` for ``calibrate.py``."""
    m, w, ref = cell.model, cell.params, parts.reference
    runner, base, lora0, tap = setup(cell, devices, parts)
    del runner
    gc.collect()
    if not recorded(w, tap):
        raise harness.BenchError("the set-up schedule was not recorded")
    want = reference_rounds(m, w, base, lora0, tap, ref)
    out = [("program", gaps(program_rounds(tap, want), want))]
    if control:
        out.append(("control", gaps(reference_rounds(
            m, w, base, lora0, tap, ref, control=True), want)))
        out.append(("half_batch", gaps(reference_rounds(
            m, w, base, lora0, tap, ref, half_batch=True), want)))
    out.append(("groups", [r["groups"] for r in want]))
    out.append(("peak", harness.memory_peak_bytes(devices)))
    del base, lora0, tap
    jax.clear_caches()
    return out


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------


def reference_rounds(m, w, base, lora0, tap, R: ModuleType = R, *,
                     control=False, half_batch=False):
    """The reference over the rounds that ``check_rounds`` lists, each
    started from the program's adapter after the round before it (the
    first from ``lora0``); at a stage's first round the reference makes
    the stage itself (its grouping, the fusion of base and adapter, and
    the transfer of the last stage's adapter), from those same inputs.
    Per compared round: the clients' first and last local losses, the
    eval loss, the round's input adapter, each adapter layer's change,
    and each layer's first-step gradient norm."""
    n_layers = m["num_hidden_layers"]
    caps, per, lr_of = _schedule(m, w, R)
    check = set(w["check_rounds"])
    params = R.quantize(base) if control else base
    steps = jax.jit(lambda p, lo, b, lr: R.adamw_steps(m, p, lo, b, lr))
    out, stage, glob, sub_lora, groups = [], -1, lora0, None, None
    with jax.default_matmul_precision("highest"):
        for r, rec in enumerate(tap.rounds):
            st = min(r // per, len(caps) - 1)
            if st != stage:
                if stage >= 0:
                    glob = {"layers": R.broadcast(sub_lora["layers"], groups,
                                                  n_layers)}
                if caps[st] < n_layers:
                    gram = R.layer_gram(params["blocks"]["layers"],
                                        glob["layers"])
                    groups = R.spectral_groups(gram, caps[st])
                    sub = dict(params)
                    sub["blocks"] = {"layers": R.fuse(
                        params["blocks"]["layers"], groups, w["beta"])}
                    sub_lora = {"layers": R.fuse(glob["layers"], groups,
                                                 w["beta"])}
                else:
                    groups = [[i] for i in range(n_layers)]
                    sub, sub_lora = params, glob
                stage = st
            if r in check:
                out.append(_reference_round(
                    m, sub, sub_lora, rec, tap.evals[r], lr_of(st), steps,
                    half_batch, R))
                out[-1].update(round=r, groups=groups)
            # the next round starts from what the program returned
            sub_lora = jax.tree.map(jnp.asarray, rec["lora_out"])
    return out


def _reference_round(m, sub, sub_lora, rec, ev, lr, steps, half_batch, R):
    batches = {k: jnp.asarray(v) for k, v in rec["batches"].items()}
    if half_batch:
        half = batches["tokens"].shape[2] // 2
        batches = {k: v[:, :, :half] for k, v in batches.items()}
    firsts, lasts, clients, grads = [], [], [], []
    for c in range(batches["tokens"].shape[0]):
        lo, losses, gn = steps(sub, sub_lora,
                               {k: v[c] for k, v in batches.items()},
                               jnp.float32(lr))
        firsts.append(float(losses[0]))
        lasts.append(float(losses[-1]))
        clients.append(lo)
        grads.append(gn)
    new = jax.tree.map(lambda *xs: sum(xs) / len(xs), *clients)
    ev = {k: jnp.asarray(v) for k, v in ev["batch"].items()}
    return {
        "loss_first": firsts, "loss_last": lasts,
        "eval": R.eval_loss(m, sub, new, ev),
        "lora_in": jax.tree.map(np.asarray, sub_lora),
        "change": _layer_norms(jax.tree.map(jnp.subtract, new, sub_lora)),
        "grad": jax.tree.map(lambda *g: np.mean(np.stack(
            [np.asarray(x) for x in g]), 0), *grads)}


def _layer_norms(tree):
    """Per-layer norms of each leaf of a stacked tree, as numpy."""
    return jax.tree.map(lambda a: np.sqrt(np.sum(
        np.square(np.asarray(a, np.float64)),
        axis=tuple(range(1, a.ndim)))), tree)


def program_rounds(tap, want):
    """The same readings, from what the runner's own programs returned,
    for the rounds in ``want``. A round's change is taken from the
    reference's input to it, so that a fault in the program's stage
    transfer shows in it as well."""
    out = []
    for r in want:
        rec, ev = tap.rounds[r["round"]], tap.evals[r["round"]]
        out.append({
            "loss_first": [float(x) for x in rec["metrics"]["loss_first"]],
            "loss_last": [float(x) for x in rec["metrics"]["loss_last"]],
            "eval": float(ev["loss"]),
            "change": _layer_norms(jax.tree.map(
                lambda a, b: np.asarray(a, np.float64)
                - np.asarray(b, np.float64),
                rec["lora_out"], r["lora_in"]))})
    return out


def gaps(got, want) -> dict:
    """``loss_gap``: the largest relative gap of a client's first or last
    local loss or of the eval loss, over the compared rounds.
    ``change_gap``: over rounds and adapter layers, the gap between the
    norms of the two sides' change in the round, against the larger of
    that layer's reference norm and the median layer's. Layers whose
    reference first-step gradient is under a thousandth of the median
    layer's (moved by Adam's rounding alone) are left out. A number with
    nothing to compare (no round, or a round with no layer kept) is
    None, which no limit admits."""
    if not want or len(got) != len(want):
        return {"loss_gap": None, "change_gap": None}
    loss_gap, change_gap = 0.0, 0.0
    for g, r in zip(got, want):
        pairs = list(zip(g["loss_first"] + g["loss_last"] + [g["eval"]],
                         r["loss_first"] + r["loss_last"] + [r["eval"]]))
        loss_gap = max([loss_gap] + [abs(a - b) / abs(b) for a, b in pairs])
        gn = np.concatenate([np.ravel(x) for x in jax.tree.leaves(r["grad"])])
        keep = [np.asarray(x) >= 1e-3 * np.median(gn)
                for x in jax.tree.leaves(r["grad"])]
        ref = [np.asarray(x) for x in jax.tree.leaves(r["change"])]
        prog = [np.asarray(x) for x in jax.tree.leaves(g["change"])]
        kept = np.concatenate([x[k] for x, k in zip(ref, keep)])
        if not kept.size:
            change_gap = None
        if change_gap is None:
            continue
        med = np.median(kept)
        for a, b, k in zip(prog, ref, keep):
            if k.any():
                change_gap = max(change_gap, float(np.max(
                    np.abs(a[k] - b[k]) / np.maximum(b[k], med))))
    return {"loss_gap": loss_gap, "change_gap": change_gap}


def recorded(w, tap) -> bool:
    """The tap saw every round and eval of the set-up schedule: a runner
    that reaches its programs another way leaves it short, and then
    nothing is compared."""
    return len(tap.rounds) == len(tap.evals) == w["rounds"] and all(
        0 <= r < w["rounds"] for r in w["check_rounds"])


def check_rounds(m, w, base, lora0, tap, R: ModuleType = R):
    """Each number compared, with its limit from the workload file."""
    limits = w.get("limits", {})
    if recorded(w, tap):
        want = reference_rounds(m, w, base, lora0, tap, R)
        g = gaps(program_rounds(tap, want), want)
    else:
        print(f"the set-up schedule ran {len(tap.rounds)} round and "
              f"{len(tap.evals)} eval programs through the runner's own "
              f"methods, not {w['rounds']}: nothing is compared",
              file=sys.stderr, flush=True)
        g = {"loss_gap": None, "change_gap": None}
    return {k: (v, limits.get(k)) for k, v in g.items()}
