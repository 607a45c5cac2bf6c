#!/usr/bin/env python3
"""Smoke run of the two device programs on a TPU, at qwen2-7b widths.

    python chip_smoke.py              # one chip: federated rounds, serving
    python chip_smoke.py --chips 4    # four chips: the mesh round, and the
                                      # same round unsharded on one chip
    python chip_smoke.py --rehearse   # every phase at a tiny size on any
                                      # device; never prints the ok line

Training drives ``run_experiment`` (DevFT, two stages of capacity 2 and
4), serving drives ``ServingEngine`` over an ``AdapterRegistry``, each
through the entry points a user calls. The model is qwen2-7b at its
published widths (d_model 3584, 28 query and 4 KV heads of 128, d_ff
18944, QKV bias, the full 152064-token vocabulary), with random weights
made from the seed. The one cut is listed in ``REDUCED``.

Each phase prints JSON lines to stdout: round losses, compile seconds,
sizes, peak device memory, kernel counts and every comparison with its
tolerance. The last line is ``{"ok": true, "device": {...}}``, printed
only when every phase passed on a TPU. Without a TPU, or when any phase
raises, the script exits non-zero and prints no ok line. It runs in one
process and starts none.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

ARCH = "qwen2-7b"
#: the one cut from the published config (widths are all kept)
REDUCED = {"n_layers": "28 -> 4"}
LAYERS = 4
#: 512, not 1024: the eval program scores 16 sequences in one call with
#: full-vocabulary float32 logits. At 16 x 1024 a v5e compile is refused
#: (17.7 GB of 15.75 GB HBM); at 16 x 512 it needs 4.05 GB of arguments
#: and 7.48 GB of temporaries.
SEQ = 512

#: Pallas vs the jnp reference, same chip. The base is bfloat16 (8-bit
#: mantissa, 3.9e-3 per rounding), and the kernels round and accumulate
#: in another order than the reference does; an eval loss is a mean over
#: 16 x 512 tokens, so its rounding noise is far below this.
TOL_EVAL_LOSS_REL = 1e-2
#: Decode logits of one request are compared as the max over its
#: generated positions of ||got - want||_2 / ||want||_2. A wrong mask or
#: a wrong cache slot moves the attention output by O(1) and this by
#: >> 0.1.
#:
#: In float32 (the same weights upcast, matmuls at full precision) the
#: Pallas and reference paths differ only in the order of float32 sums:
#: the sharp check of the kernel.
TOL_DECODE_LOGITS_F32 = 1e-3
#: In bf16, as served, each path lies a rounding distance e from the
#: float32 model, and once one op differs every later rounding differs
#: too, so the two paths may lie up to 2e apart. e is measured in the
#: run as the bf16 reference's distance from the float32 model.
BF16_SPREAD = 2.0
#: the engine's greedy token must be the argmax of the replayed logits
#: wherever the top two logits are further apart than this
ARGMAX_MARGIN = 0.05
#: sharded vs unsharded round (four chips): a reduction split over
#: devices rounds in another order
TOL_MESH_LOSS_REL = 1e-2


def _import_repo():
    """Put this checkout's ``src/`` first on the path; a copy of the
    script without the repo next to it has nothing to run."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke.py: no src/repro next to {HERE}; "
                         f"run it from a checkout of the repository")
    sys.path.insert(0, SRC)


def emit(**kv) -> None:
    print(json.dumps(kv, sort_keys=True), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def require_tpu(n_chips: int = 1):
    """The devices to run on: at least ``n_chips`` TPU devices, or an
    error. There is no fallback to another backend."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(f"chip_smoke needs a TPU; JAX found "
                           f"{devices[0].platform!r} devices")
    if len(devices) < n_chips:
        raise RuntimeError(f"chip_smoke needs {n_chips} TPU chips; JAX "
                           f"found {len(devices)}")
    return devices


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def device_bytes(devices, stat: str = "peak_bytes_in_use") -> dict:
    """One ``memory_stats()`` entry of each device, where the backend
    reports it: the process's peak by default, or ``bytes_in_use``."""
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        if stat in stats:
            out[str(d.id)] = int(stats[stat])
    return out


class CompileMeter:
    """Backend compile seconds (a persistent-cache hit counts only its
    read) and persistent-cache hits, since the last ``take()``."""

    def __init__(self):
        import jax

        self.seconds, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_s": self.seconds, "compiled_programs": self.programs,
               "cache_hits": self.hits}
        self.seconds, self.programs, self.hits = 0.0, 0, 0
        return out


def count_kernels(hlo_text: str) -> int:
    """Pallas kernels in a compiled TPU program."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')


@dataclasses.dataclass(frozen=True)
class Plan:
    train: object              # ExperimentSpec of the DevFT run
    mesh: object               # ExperimentSpec of the four-chip round
    slots: int = 4
    requests: int = 8
    prompt: int = 64
    gen: int = 32
    n_adapters: int = 3


def full_plan() -> Plan:
    from repro.experiments import ExperimentSpec

    train = ExperimentSpec(
        arch=ARCH, full=True, layers=LAYERS, method="devft", n_stages=2,
        n_clients=8, sample_frac=0.25, local_batch=2, k_local=2, seq=SEQ,
        lora_rank=32, rounds=4, seed=0)
    return Plan(train=train, mesh=_mesh_spec(train))


def _mesh_spec(train):
    """One FedIT round. GSPMD cannot partition the Pallas kernels, so
    the sharded runner takes the reference path
    (``simulator.mesh_kernel_backend``); the unsharded run it is
    compared with takes it too, so only the sharding differs."""
    return train.replace(method="fedit", rounds=1,
                         kernel_backend="reference")


def tiny_plan() -> Plan:
    """Every phase at CPU size, through the Pallas kernels (interpreted
    off the chip)."""
    from repro.experiments import ExperimentSpec

    train = ExperimentSpec(
        arch=ARCH, reduced={"n_layers": LAYERS, "d_model": 64, "n_heads": 4,
                            "n_kv_heads": 2, "d_ff": 128, "vocab": 256},
        layers=LAYERS, method="devft", n_stages=2, n_clients=8,
        sample_frac=0.25, local_batch=2, k_local=2, seq=16, lora_rank=4,
        rounds=4, seed=0, kernel_backend="pallas")
    return Plan(train=train, mesh=_mesh_spec(train), requests=4, prompt=8,
                gen=4)


# ---------------------------------------------------------------------------
# training: run_experiment, then the same spec on the reference kernels
# ---------------------------------------------------------------------------


def _round_logger(tag: str, devices):
    def log(rl):
        emit(phase=tag, round=rl.round, stage=rl.stage, capacity=rl.capacity,
             eval_loss=rl.eval_loss, eval_acc=rl.eval_acc,
             bytes_in_use=device_bytes(devices, "bytes_in_use"),
             peak_bytes=device_bytes(devices))
    return log


def train_phase(spec, meter, devices, on_tpu: bool):
    from repro.experiments import run_experiment
    from repro.kernels.dispatch import resolve

    cfg = spec.build_cfg()
    emit(phase="train", arch=ARCH, reduced=REDUCED, d_model=cfg.d_model,
         n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
         d_ff=cfg.d_ff, vocab=cfg.vocab, n_layers=cfg.n_layers,
         base_dtype=str(spec.base_dtype()), seq=spec.seq,
         n_clients=spec.n_clients, per_round=int(spec.n_clients
                                                 * spec.sample_frac),
         local_batch=spec.local_batch, k_local=spec.k_local,
         lora_rank=spec.lora_rank, rounds=spec.rounds,
         backend=resolve(spec.kernel_backend))
    if on_tpu:
        check(resolve(spec.kernel_backend) == "pallas",
              "the TPU run must take the Pallas kernels")
    meter.take()
    t0 = time.perf_counter()
    result = run_experiment(spec,
                            round_progress=_round_logger("train", devices))
    wall = time.perf_counter() - t0
    logs = result.logs
    gc.collect()                         # the run's base is garbage now
    emit(phase="train", wall_s=wall, peak_bytes=device_bytes(devices),
         bytes_in_use=device_bytes(devices, "bytes_in_use"), **meter.take())
    check(len(logs) == spec.rounds, f"{len(logs)} of {spec.rounds} rounds")
    check(sorted({rl.capacity for rl in logs}) == [LAYERS // 2, LAYERS],
          f"both DevFT stages must run: capacities "
          f"{[rl.capacity for rl in logs]}")
    check(all(math.isfinite(rl.eval_loss) for rl in logs),
          f"non-finite eval loss: {[rl.eval_loss for rl in logs]}")

    t0 = time.perf_counter()
    ref = run_experiment(spec.replace(kernel_backend="reference"),
                         round_progress=_round_logger("train_reference",
                                                      devices))
    emit(phase="train_reference", wall_s=time.perf_counter() - t0,
         peak_bytes=device_bytes(devices), **meter.take())
    for got, want in zip(logs, ref.logs):
        rel = abs(got.eval_loss - want.eval_loss) / abs(want.eval_loss)
        emit(phase="train_vs_reference", round=got.round,
             eval_loss=got.eval_loss, reference=want.eval_loss, rel=rel,
             tol=TOL_EVAL_LOSS_REL)
        check(rel <= TOL_EVAL_LOSS_REL,
              f"round {got.round}: Pallas eval loss {got.eval_loss} vs "
              f"reference {want.eval_loss}")
    emit(phase="train_vs_reference", **_lora_diff(result.final_lora,
                                                  ref.final_lora))
    return result.final_lora


def _lora_diff(got, want) -> dict:
    """Largest per-leaf ||got - want||_2 / ||want||_2 of two LoRA trees,
    for the ``a`` (down) and ``b`` (up, initialised to zero) factors."""
    import jax
    import numpy as np

    worst = {"a": 0.0, "b": 0.0}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        rel = float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))
        name = getattr(path[-1], "key", "")
        if name in worst:
            worst[name] = max(worst[name], rel)
    return {"lora_rel_a": worst["a"], "lora_rel_b": worst["b"]}


def round_program_text(spec) -> str:
    """Compiled text of the run's round program at full capacity (the
    last DevFT stage), built the way the runner builds it."""
    import jax

    from repro.analysis.contracts.strategies import round_operands
    from repro.federated.methods import LocalSpec, make_strategy
    from repro.federated.simulator import make_round_program
    from repro.models import transformer as T

    cfg, fed = spec.build_cfg(), spec.fed_config()
    n_sample = max(1, int(fed.n_clients * fed.sample_frac))
    key = jax.random.PRNGKey(fed.seed)
    params = jax.eval_shape(lambda: T.init_params(cfg, key,
                                                  spec.base_dtype()))
    lora = jax.eval_shape(lambda: T.init_lora(cfg, key,
                                              rank=fed.lora_rank))
    round_fn, _ = make_round_program(make_strategy(fed.method, cfg, fed),
                                     None, cfg, n_sample, hetero=False)
    args = round_operands(LocalSpec(cfg, params, lora), fed, n_sample,
                          False)
    return jax.jit(round_fn).lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# serving: the engine over a registry holding the trained adapter
# ---------------------------------------------------------------------------


def _tenant_adapter(template, key):
    """A non-trivial adapter shaped like ``template`` (both factors
    random, so it moves the logits)."""
    import jax

    leaves, treedef = jax.tree.flatten(template)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        0.05 * jax.random.normal(k, x.shape, x.dtype)
        for k, x in zip(keys, leaves)])


def replay_logits(cfg, params, stacked, row: int, tokens, capacity: int,
                  cache_dtype=None):
    """Teacher-force ``tokens`` through the decode step the engine runs,
    with registry row ``row`` gathered the way the engine gathers it.
    Returns the float32 logits at every position, ``(len(tokens), V)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T

    @jax.jit
    def replay(params, stacked, idx, toks, cache):
        lora = jax.tree.map(lambda x: jnp.moveaxis(x[idx], 0, 1), stacked)

        def body(c, t):
            logits, c = T.decode_step(cfg, params, lora, t.reshape(1, 1), c)
            return c, logits[0, -1].astype(jnp.float32)

        return jax.lax.scan(body, cache, toks)[1]

    cache = T.init_cache(cfg, 1, capacity, cache_dtype)
    return np.asarray(replay(params, stacked, jnp.array([row], jnp.int32),
                             jnp.asarray(tokens, jnp.int32), cache))


def _upcast(leaves, treedef):
    """The float32 tree of ``leaves``, emptying the list as it goes so
    each bf16 leaf is freed once its copy exists (the two whole trees
    would not fit the chip together with what else is resident)."""
    import jax
    import jax.numpy as jnp

    out = []
    while leaves:
        out.append(leaves.pop(0).astype(jnp.float32))
    return jax.tree.unflatten(treedef, out)


def _logits_rel(got, want) -> float:
    """max over positions of ||got - want||_2 / ||want||_2."""
    import numpy as np

    return float(np.max(np.linalg.norm(got - want, axis=-1)
                        / np.linalg.norm(want, axis=-1)))


def serve_phase(plan: Plan, lora, meter, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.serving import AdapterRegistry, ServingEngine

    spec = plan.train
    cfg = spec.build_cfg()
    capacity = plan.prompt + plan.gen
    params = T.init_params(cfg, jax.random.PRNGKey(spec.seed),
                           spec.base_dtype())
    registry = AdapterRegistry(lora, plan.n_adapters)
    ids = ["global"] + [f"tenant/{i}" for i in range(1, plan.n_adapters)]
    registry.add("global", lora)
    for i, name in enumerate(ids[1:], 1):
        registry.add(name, _tenant_adapter(
            lora, jax.random.PRNGKey(1000 + i)))
    engine = ServingEngine(cfg, params, adapters=registry,
                           n_slots=plan.slots, kv_capacity=capacity)
    emit(phase="serve", slots=plan.slots, requests=plan.requests,
         prompt=plan.prompt, gen=plan.gen, adapters=ids,
         kv_capacity=capacity)
    meter.take()
    t0 = time.perf_counter()
    engine.warmup()
    emit(phase="serve", warmup_s=time.perf_counter() - t0, **meter.take())

    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7919), (plan.requests, plan.prompt), 0,
        cfg.vocab), np.int32)
    t0 = time.perf_counter()
    reqs = engine.run(list(prompts), max_new_tokens=plan.gen,
                      adapter=[ids[i % len(ids)]
                               for i in range(plan.requests)])
    wall = time.perf_counter() - t0
    answered = [r for r in reqs if len(r.generated) == plan.gen]
    emit(phase="serve", answered=len(answered), wall_s=wall,
         first_tokens=[r.generated[:8] for r in reqs[:2]],
         peak_bytes=device_bytes(devices), **meter.take())
    check(len(engine.finished) == plan.requests
          and len(answered) == plan.requests,
          f"{len(answered)} of {plan.requests} requests answered")
    check(all(0 <= t < cfg.vocab for r in reqs for t in r.generated),
          "a generated token lies outside the vocabulary")

    # the decode program as the engine compiled it
    n = plan.slots
    stacked = registry.stacked
    text = engine._step_fn.lower(
        params, stacked, jnp.zeros((n,), jnp.int32),
        jnp.zeros((n, 1), jnp.int32), engine.kv.cache,
        jnp.zeros((n,), bool)).compile().as_text()

    # one request, replayed through the decode step on both backends:
    # in bf16 as served, then in float32 with the same weights
    req = reqs[1]
    row = registry.index(req.adapter)
    tokens = np.concatenate([req.prompt, np.asarray(req.generated[:-1],
                                                    np.int32)])
    gen_pos = slice(plan.prompt - 1, None)
    vocab = slice(0, cfg.vocab)            # the padding columns are masked
    ref_cfg = dataclasses.replace(cfg, kernel_backend="reference")

    def replay(c, p, dtype):
        return replay_logits(c, p, stacked, row, tokens, capacity,
                             dtype)[gen_pos, vocab]

    got16 = replay(cfg, params, cfg.dtype)
    want16 = replay(ref_cfg, params, cfg.dtype)
    top2 = np.sort(got16, axis=-1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > ARGMAX_MARGIN
    agree = np.argmax(got16, axis=-1) == np.asarray(req.generated)
    leaves, treedef = jax.tree.flatten(params)
    del params, engine
    gc.collect()                         # the engine holds the bf16 base
    params32 = _upcast(leaves, treedef)
    with jax.default_matmul_precision("highest"):
        got32 = replay(cfg, params32, jnp.float32)
        want32 = replay(ref_cfg, params32, jnp.float32)
    del params32
    rel32 = _logits_rel(got32, want32)
    rel16 = _logits_rel(got16, want16)
    floor = _logits_rel(want16, want32)
    emit(phase="serve_vs_reference", request=req.rid, adapter=req.adapter,
         positions=int(got16.shape[0]), f32_logits_rel=rel32,
         f32_tol=TOL_DECODE_LOGITS_F32, bf16_logits_rel=rel16,
         bf16_tol=BF16_SPREAD * floor, bf16_reference_vs_f32=floor,
         bf16_pallas_vs_f32=_logits_rel(got16, want32),
         engine_argmax_agree=int(agree.sum()),
         clear_positions=int(clear.sum()), margin=ARGMAX_MARGIN,
         peak_bytes=device_bytes(devices), **meter.take())
    check(rel32 <= TOL_DECODE_LOGITS_F32,
          f"float32 decode logits: Pallas vs reference rel {rel32}")
    check(rel16 <= BF16_SPREAD * floor,
          f"bf16 decode logits: Pallas vs reference rel {rel16}, over "
          f"{BF16_SPREAD} x the reference's own distance {floor} from "
          f"the float32 model")
    check(bool(np.all(agree[clear])),
          "the engine's greedy tokens disagree with the replayed logits")
    return count_kernels(text)


# ---------------------------------------------------------------------------
# four chips: the sharded round against the same round on one chip
# ---------------------------------------------------------------------------


def mesh_phase(spec, meter, devices):
    import jax
    import numpy as np

    from repro.data import make_federated_data
    from repro.federated import FederatedRunner
    from repro.launch.mesh import make_mesh

    cfg, fed = spec.build_cfg(), spec.fed_config()
    data = make_federated_data(cfg.vocab, n_clients=spec.n_clients,
                               alpha=spec.alpha, noise=spec.noise,
                               seed=spec.seed)
    emit(phase="mesh", arch=ARCH, reduced=REDUCED, method=fed.method,
         rounds=fed.rounds, mesh={"data": 2, "model": 2}, seq=fed.seq,
         base_dtype=str(spec.base_dtype()))
    results = {}
    for tag, mesh in (("unsharded", None),
                      ("sharded", make_mesh((2, 2), ("data", "model"),
                                            devices=devices[:4]))):
        meter.take()
        t0 = time.perf_counter()
        runner = FederatedRunner(cfg, fed, data, dtype=spec.base_dtype(),
                                 mesh=mesh)
        logs = runner.run()
        lora = jax.tree.map(np.asarray, runner.lora)
        emit(phase="mesh", run=tag, eval_loss=logs[0].eval_loss,
             backend=runner.cfg.kernel_backend,
             wall_s=time.perf_counter() - t0,
             peak_bytes=device_bytes(devices), **meter.take())
        check(math.isfinite(logs[0].eval_loss), f"{tag}: non-finite loss")
        results[tag] = (logs[0].eval_loss, lora)
        del runner
        gc.collect()
    (l1, lora1), (l4, lora4) = results["unsharded"], results["sharded"]
    rel = abs(l4 - l1) / abs(l1)
    emit(phase="mesh_vs_unsharded", eval_loss=l4, unsharded=l1, rel=rel,
         tol=TOL_MESH_LOSS_REL, **_lora_diff(lora4, lora1))
    check(rel <= TOL_MESH_LOSS_REL,
          f"sharded eval loss {l4} vs unsharded {l1}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the mesh round and its unsharded "
                         "comparison, and nothing else")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever device JAX finds; "
                         "exits 1 and never prints the ok line")
    args = ap.parse_args(argv)

    _import_repo()
    import jax

    from repro.launch.env import setup_environment

    if args.rehearse:
        devices = jax.devices()
        if len(devices) < args.chips:
            raise RuntimeError(f"--chips {args.chips} needs that many "
                               f"devices; JAX found {len(devices)}")
        plan = tiny_plan()
    else:
        devices = require_tpu(args.chips)
        plan = full_plan()
    on_tpu = devices[0].platform == "tpu"
    emit(phase="setup", device=device_info(devices),
         bytes_limit=device_bytes(devices, "bytes_limit"),
         rehearse=args.rehearse, **setup_environment())
    meter = CompileMeter()

    if args.chips == 4:
        mesh_phase(plan.mesh, meter, devices)
    else:
        lora = train_phase(plan.train, meter, devices, on_tpu)
        round_kernels = count_kernels(round_program_text(plan.train))
        emit(phase="train", round_program_tpu_custom_calls=round_kernels,
             **meter.take())
        gc.collect()
        decode_kernels = serve_phase(plan, lora, meter, devices)
        emit(phase="serve", decode_program_tpu_custom_calls=decode_kernels,
             **meter.take())
        if on_tpu:
            check(round_kernels > 0, "no Pallas kernel in the round program")
            check(decode_kernels > 0,
                  "no Pallas kernel in the decode program")
    if args.rehearse:
        emit(phase="rehearsal", done=True,
             note="tiny sizes prove nothing about the chip: no ok line")
        return 1
    print(json.dumps({"ok": True, "device": device_info(devices)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
