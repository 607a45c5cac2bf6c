"""Continuous-batching multi-tenant serving engine.

One :class:`ServingEngine` owns a fixed pool of ``n_slots`` decode
slots, a ragged KV cache (``repro.serving.kv_cache``) and — in
multi-tenant mode — an :class:`~repro.serving.adapters.AdapterRegistry`
of batch-stacked LoRA adapters. Every engine step runs ONE compiled
device program over all slots:

* slots in PREFILL feed their next prompt token (teacher-forced, the
  output is discarded) — a newly admitted request therefore joins the
  running batch immediately, while other slots keep decoding;
* slots in DECODE feed their last generated token;
* free slots ride along masked out (``active``): their position cursor
  is frozen and their outputs ignored, so the traced shapes — and the
  compiled program — never change as requests come and go.

Per-slot adapters are gathered inside the jitted step from the
registry's ``(N, ...)``-stacked tree by the slot->adapter index vector
and flow through the model's LoRA projection path with a leading batch
axis (``layers._proj`` broadcasts batched ``a``/``b`` factors), so any
resident adapter mix is served by the same program. Finished slots are
recycled by zeroing their cache lane (``KVCacheManager.reset_slot``) —
no reallocation, no recompile.

Engine modes (mutually exclusive):

* ``adapters=AdapterRegistry`` — multi-tenant: every request names a
  registered adapter;
* ``lora=<tree>`` — one shared global adapter (bit-identical to the
  sequential ``launch.serve.generate`` baseline, pinned by
  ``tests/test_serving.py``);
* neither — base / merged weights.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.models import transformer as T
from repro.serving.adapters import AdapterRegistry
from repro.serving.kv_cache import KVCacheManager, check_capacity
from repro.serving.scheduler import Request, RequestState, SlotScheduler

OVERFLOW = ("error", "ring")


@dataclasses.dataclass(frozen=True)
class StepContract:
    """Declared abstract-interpretation contract for the engine's jitted
    ``_step_fn``, verified by ``python -m repro.analysis --contracts``
    across arch families and N=1 vs N-stacked adapter modes (DESIGN.md
    §12): the next-token vector must be ``int32[n_slots]`` with no weak
    type, and the returned cache must carry exactly the avals of the
    cache operand — the condition that makes ``donate_argnums=(4,)``
    sound (a drifted cache aval would silently disable donation and
    double the KV memory footprint)."""
    next_tokens_dtype: str = "int32"
    donated: str = "cache"


class ServingEngine:
    #: abstract step contract (see :class:`StepContract`)
    contract = StepContract()

    #: positional args of ``_build_step``'s fn donated to the jitted
    #: step (the KV cache — ``new_cache`` aliases it in place). Named so
    #: the L004 lowered check verifies the SAME declaration the engine
    #: jits with actually materializes as input-output aliasing.
    DONATE_ARGNUMS = (4,)

    def __init__(self, cfg, params, *, lora=None,
                 adapters: Optional[AdapterRegistry] = None,
                 n_slots: int = 4, kv_capacity: int = 256,
                 policy: str = "fifo", overflow: str = "error",
                 stop_tokens: Sequence[int] = (),
                 clock: Callable[[], float] = time.perf_counter):
        if lora is not None and adapters is not None:
            raise ValueError("pass either a shared `lora` tree or an "
                             "`adapters` registry, not both")
        if overflow not in OVERFLOW:
            raise ValueError(f"unknown overflow policy {overflow!r}; "
                             f"known: {list(OVERFLOW)}")
        self.cfg = cfg
        self.params = params
        self.lora = lora
        self.adapters = adapters
        self.overflow = overflow
        self.kv = KVCacheManager(cfg, n_slots, kv_capacity)
        self.scheduler = SlotScheduler(n_slots, policy=policy)
        self.finished: List[Request] = []
        self._stop = tuple(stop_tokens)
        self._clock = clock
        self._rid = 0
        self._adapter_idx = np.zeros((n_slots,), np.int32)
        self._step_fn = jax.jit(self._build_step(),
                                donate_argnums=self.DONATE_ARGNUMS)
        self._warm = False
        self._n_steps = 0

    # ---- jitted step -------------------------------------------------
    def _build_step(self):
        cfg = self.cfg
        multi = self.adapters is not None

        def fn(params, lora_op, idx, tokens, cache, active):
            if multi:
                # (N, L, ...) -> per-slot rows (B, L, ...) -> layer-major
                # (L, B, ...) so the decode scan slices layers as usual
                lora = jax.tree.map(
                    lambda x: jnp.moveaxis(x[idx], 0, 1), lora_op)
            else:
                lora = lora_op
            with jax.named_scope("decode"):
                logits, new_cache = T.decode_step(cfg, params, lora,
                                                  tokens, cache)
            # per-slot active mask: free/finished slots stay frozen (their
            # lanes still compute, but the cursor does not advance)
            new_cache["pos"] = jnp.where(active, new_cache["pos"],
                                         cache["pos"])
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt, new_cache

        return fn

    # ---- request intake ----------------------------------------------
    def submit(self, prompt, *, max_new_tokens: int = 16,
               adapter: Optional[str] = None, priority: int = 0,
               stop_tokens: Optional[Sequence[int]] = None) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        check_capacity(self.kv.capacity, prompt.size, max_new_tokens,
                       self.overflow == "ring")
        if self.adapters is not None:
            if adapter is None:
                raise ValueError("multi-tenant engine: every request must "
                                 "name a registered adapter")
            self.adapters.index(adapter)          # existence check + touch
        elif adapter is not None:
            raise ValueError("engine has no adapter registry; submit "
                             "without `adapter` (shared/merged mode)")
        req = Request(rid=self._rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, adapter=adapter,
                      priority=priority,
                      stop_tokens=tuple(stop_tokens)
                      if stop_tokens is not None else self._stop)
        self._rid += 1
        req.t_submit = self._clock()
        self.scheduler.submit(req)
        return req

    # ---- engine loop -------------------------------------------------
    def warmup(self) -> None:
        """Compile the decode step before any request is timed (runs one
        masked step: every slot inactive, all writes land in free lanes
        that admission resets)."""
        if self._warm:
            return
        if self.scheduler.n_active:
            raise RuntimeError("warmup() must run before admission")
        n = self.scheduler.n_slots
        nxt, cache = self._step_fn(
            self.params, self._lora_operand(),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n, 1), jnp.int32),
            self.kv.cache, jnp.zeros((n,), bool))
        nxt.block_until_ready()
        self.kv.cache = cache
        self._warm = True

    def _lora_operand(self):
        return self.adapters.stacked if self.adapters is not None \
            else self.lora

    def _admit(self) -> None:
        with TraceAnnotation("repro.engine.admit") as span:
            now = self._clock()
            admitted = self.scheduler.admit()
            for slot, req in admitted:
                self.kv.reset_slot(slot)
                if self.adapters is not None:
                    self._adapter_idx[slot] = self.adapters.index(
                        req.adapter)
                    self.adapters.pin(req.adapter)
                req.t_admit = now
                req.state = RequestState.PREFILL
            span.set_metadata(admitted=len(admitted))

    def _finish(self, slot: int, req: Request, now: float) -> None:
        req.state = RequestState.FINISHED
        req.t_finish = now
        if self.adapters is not None:
            self.adapters.unpin(req.adapter)
        self.scheduler.release(slot)
        self.finished.append(req)

    def step(self) -> List[Request]:
        """Admit what fits, run one batched decode step, harvest slot
        outputs. Returns the requests that finished this step.

        Host spans: ``repro.engine.step`` (a ``StepTraceAnnotation``)
        holds ``repro.engine.admit`` (arg ``admitted``),
        ``repro.engine.assemble`` (the token and mask arrays),
        ``repro.engine.decode`` (the dispatch and the blocking read of
        the next tokens; arg ``active``) and ``repro.engine.harvest``
        (arg ``finished``)."""
        with StepTraceAnnotation("repro.engine.step",
                                 step_num=self._n_steps):
            self._n_steps += 1
            self._admit()
            active = self.scheduler.active
            if not active:
                return []
            with TraceAnnotation("repro.engine.assemble"):
                n = self.scheduler.n_slots
                tokens = np.zeros((n, 1), np.int32)
                mask = np.zeros((n,), bool)
                for slot, req in active:
                    tokens[slot, 0] = req.next_feed()
                    mask[slot] = True

            with TraceAnnotation("repro.engine.decode", active=len(active)):
                t0 = self._clock()
                nxt, cache = self._step_fn(
                    self.params, self._lora_operand(),
                    jnp.asarray(self._adapter_idx), jnp.asarray(tokens),
                    self.kv.cache, jnp.asarray(mask))
                nxt_host = np.asarray(nxt)         # blocks on the device
                dt = self._clock() - t0
            now = t0 + dt
            self.kv.cache = cache

            with TraceAnnotation("repro.engine.harvest") as span:
                done = []
                for slot, req in active:
                    if req.cursor < req.prompt_len:  # consumed a prompt token
                        req.cursor += 1
                        req.prefill_s += dt
                        if req.cursor < req.prompt_len:
                            continue                  # still prefilling
                        # last prompt token -> this step produced the
                        # first output
                        req.t_first_token = now
                        req.state = RequestState.DECODE
                    else:
                        req.decode_times.append(dt)
                    tok = int(nxt_host[slot])
                    req.generated.append(tok)
                    if (len(req.generated) >= req.max_new_tokens
                            or tok in req.stop_tokens):
                        self._finish(slot, req, now)
                        done.append(req)
                span.set_metadata(finished=len(done))
            return done

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def run(self, prompts=None, *, max_new_tokens: int = 16,
            adapter=None, max_steps: Optional[int] = None) -> List[Request]:
        """Closed-loop convenience: optionally submit ``prompts`` (each a
        1-D token array; ``adapter`` a shared id or one id per prompt),
        then step until the queue drains. Returns the submitted requests
        (or everything finished during the drain)."""
        submitted = []
        if prompts is not None:
            ads = adapter if isinstance(adapter, (list, tuple)) \
                else [adapter] * len(prompts)
            for p, a in zip(prompts, ads):
                submitted.append(self.submit(
                    p, max_new_tokens=max_new_tokens, adapter=a))
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return submitted or self.finished
