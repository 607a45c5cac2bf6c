"""Client-side local training: K local AdamW steps on LoRA params only.

``local_train`` is pure and jit/vmap-friendly: the federated simulator
vmaps it over the sampled-client axis, which on the production mesh maps
client parallelism onto the data axes (DESIGN.md §3).

Ragged local work (DESIGN.md §3, heterogeneous clients): an optional
``step_mask`` operand of shape ``(K,)`` realizes a per-client step
count ``k_c ≤ K`` with static shapes — every scan iteration still runs
the forward/backward, but masked steps leave the adapters and optimizer
state untouched (``jnp.where`` on a traced 0/1 mask, so an all-ones
mask is bit-identical to the unmasked program). The returned metrics
carry the client's processed example count for weighted aggregation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.transformer import loss_fn
from repro.optim.adamw import adamw_update, init_adamw


def make_local_train(cfg, *, lr_is_input: bool = True, remat: bool = False,
                     window=None, moe_path: str = "gather", mesh=None):
    """Returns local_train(params, lora, batches, lr, step_mask=None)
    -> (lora', metrics).

    batches: {'tokens': (K, B, S), 'labels': (K, B, S), ...} — K local
    steps (paper App. B: K=10, batch 16). Optimizer state is reset per
    round (stateless-client FedAvg, matching OpenFedLLM).

    ``step_mask`` (optional, shape (K,), 0/1 float): step t's update is
    applied only where the mask is 1; masked steps are no-ops on the
    carried (lora, opt) state. ``metrics['n_examples']`` reports the
    number of label tokens actually trained on — informational for
    callers; the engine's aggregation weights are derived HOST-side
    from the same plan that built the mask
    (``heterogeneity.RoundPlan``/``aggregation_weights``), not from
    this traced value.

    The HLO metadata names the work ``local_train``, and each step's
    ``loss_and_grad`` and ``adamw`` inside it (``jax.named_scope``).
    """

    def step(carry, batch, params, lr, m=None):
        lora, opt = carry

        def lfn(lo):
            return loss_fn(cfg, params, lo, batch, remat=remat,
                           window=window, moe_path=moe_path, mesh=mesh)

        with jax.named_scope("loss_and_grad"):
            (total, metrics), grads = jax.value_and_grad(
                lfn, has_aux=True)(lora)
        with jax.named_scope("adamw"):
            new_lora, new_opt = adamw_update(grads, opt, lora, lr,
                                             weight_decay=0.0)
        if m is not None:
            keep = m > 0
            new_lora = jax.tree.map(
                lambda n, o: jnp.where(keep, n, o), new_lora, lora)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(keep, n, o), new_opt, opt)
        return (new_lora, new_opt), metrics["loss"]

    @jax.named_scope("local_train")
    def local_train(params, lora, batches, lr, step_mask=None):
        opt = init_adamw(lora)
        k, b, s = batches["labels"].shape[:3]
        if step_mask is None:
            def body(carry, batch):
                return step(carry, batch, params, lr)

            (lora, _), losses = jax.lax.scan(body, (lora, opt), batches)
            n_examples = jnp.float32(k * b * s)
        else:
            def body(carry, xs):
                batch, m = xs
                return step(carry, batch, params, lr, m)

            (lora, _), losses = jax.lax.scan(body, (lora, opt),
                                             (batches, step_mask))
            n_examples = jnp.sum(step_mask) * (b * s)
        return lora, {"loss_first": losses[0], "loss_last": losses[-1],
                      "n_examples": n_examples}

    return local_train
