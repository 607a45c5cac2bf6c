"""Top-k mixture-of-experts with capacity-based gather/scatter dispatch.

Two execution paths share the same parameters and router:

* ``moe_block`` — plain-jit path. Tokens are gathered into per-expert
  capacity buffers via index arithmetic (NO one-hot dispatch einsum, so
  ``cost_analysis`` reflects true active FLOPs), batched-matmul'd against
  the expert weights and scattered back. GSPMD shards the expert dim of
  the weights; this is the paper-faithful baseline path.
* ``moe_block_ep`` — shard_map expert-parallel path (beyond-paper
  optimization, see EXPERIMENTS.md §Perf): experts live on the ``model``
  axis, tokens are replicated across it, each shard computes only its
  local experts and the outputs are psum'd.

Used by granite-moe, jamba (every-2nd-layer MoE) and deepseek-v3
(+1 shared expert, first-3-dense handled by the transformer driver).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.models.layers import init_mlp, mlp, model_backend


def expert_ffn_reference(buf, wg, wu, wd, *, constrain=None,
                         interpret: bool = False):
    """Batched SwiGLU over per-expert capacity buffers: (E,C,d) -> (E,C,d).

    Registered as the ``reference`` implementation of the
    ``moe_expert_ffn`` kernel (see ``repro.kernels.dispatch``): a Pallas
    grouped-GEMM can later register under the same name and every MoE
    arch picks it up with no changes here. ``constrain`` optionally
    applies a sharding constraint to the hidden activations (the
    gather_sharded path).
    """
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
        * jnp.einsum("ecd,edf->ecf", buf, wu)
    if constrain is not None:
        h = constrain(h)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def init_moe(key, cfg, dtype) -> dict:
    m = cfg.moe
    d, e, ff = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = jax.random.split(key, 5)
    si, so = 1.0 / math.sqrt(d), 1.0 / math.sqrt(ff)
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * si,
        "wg": jax.random.normal(ks[1], (e, d, ff), dtype) * si,
        "wu": jax.random.normal(ks[2], (e, d, ff), dtype) * si,
        "wd": jax.random.normal(ks[3], (e, ff, d), dtype) * so,
    }
    if m.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, ff * m.n_shared_experts, dtype)
    return p


def router_topk(params, cfg, x):
    """Returns (weights (T,k), experts (T,k) int32, aux_loss scalar)."""
    m = cfg.moe
    t = x.shape[0]
    logits = (x.astype(jnp.float32) @ params["router"])          # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.clip(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.zeros((m.n_experts,)).at[idx.reshape(-1)].add(
        jnp.ones((t * m.top_k,))) / (t * m.top_k)
    aux = m.n_experts * jnp.sum(me * ce) * m.router_aux_coef
    return w, idx, aux


def _capacity(cfg, n_tokens: int) -> int:
    m = cfg.moe
    c = int(math.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly tiling


def _dispatch_indices(idx: jax.Array, n_experts: int, capacity: int):
    """slot -> (expert, position-in-expert) with capacity dropping.

    idx: (T*k,) expert id per slot. Returns (pos (T*k,), keep (T*k,) bool).
    Position is computed with a cumsum over a one-hot *int8* matrix —
    integer bookkeeping only, never a FLOP-bearing dispatch einsum.
    """
    one_hot = jax.nn.one_hot(idx, n_experts, dtype=jnp.int32)     # (S,E)
    pos_in_e = jnp.cumsum(one_hot, axis=0) - 1                    # (S,E)
    pos = jnp.take_along_axis(pos_in_e, idx[:, None], axis=1)[:, 0]
    keep = pos < capacity
    return pos, keep


def moe_block(params: dict, cfg, x: jax.Array, *,
              capacity: Optional[int] = None, mesh=None,
              constrain: bool = False):
    """x: (T, d) flattened tokens -> (y (T, d), aux_loss).

    With ``constrain=True`` (and a mesh in context) the dispatch buffers
    carry explicit sharding constraints: expert dim on the tensor axis,
    capacity dim on the data axes. Without them GSPMD is free to
    replicate the (E, C, d) buffers — which it in fact does on the
    3-axis multi-pod mesh, inflating per-device FLOPs ~400×
    (EXPERIMENTS.md §Perf iteration 1).
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    t, d = x.shape
    cap = capacity or _capacity(cfg, t)

    def _c(arr, spec):
        if not (constrain and mesh is not None):
            return arr
        return jax.lax.with_sharding_constraint(
            arr, jax.sharding.NamedSharding(mesh, spec))

    data_axes = tuple(a for a in (mesh.axis_names if mesh is not None
                                  else ()) if a != "model")
    w, idx, aux = router_topk(params, cfg, x)                     # (T,k)
    flat_idx = idx.reshape(-1)                                    # (T*k,)
    pos, keep = _dispatch_indices(flat_idx, m.n_experts, cap)
    # gather tokens into (E, C, d) buffers
    tok_of_slot = jnp.repeat(jnp.arange(t), m.top_k)              # (T*k,)
    safe_e = jnp.where(keep, flat_idx, 0)
    safe_p = jnp.where(keep, pos, cap - 1)
    buf = jnp.zeros((m.n_experts, cap, d), x.dtype)
    buf = buf.at[safe_e, safe_p].add(
        jnp.where(keep[:, None], x[tok_of_slot], 0))
    buf = _c(buf, P("model", data_axes or None, None))
    # expert computation: batched SwiGLU over (E, C, d), dispatched so a
    # Pallas grouped-GEMM can take over on accelerators
    expert_ffn = dispatch.get_kernel("moe_expert_ffn", model_backend(cfg))
    out = expert_ffn(
        buf, params["wg"], params["wu"], params["wd"],
        constrain=lambda arr: _c(arr, P("model", data_axes or None, None)),
        interpret=dispatch.interpret_default())
    out = _c(out, P("model", data_axes or None, None))             # (E,C,d)
    # combine back
    gathered = out[safe_e, safe_p]                                # (T*k,d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    scale = w.reshape(-1)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok_of_slot].add(gathered * scale)
    y = _c(y, P(data_axes or None, None))
    if "shared" in params:
        y = y + mlp(params["shared"], x[None])[0]
    return y, aux


def moe_block_ep(params: dict, cfg, x: jax.Array, *, mesh,
                 tp_axis: str = "model",
                 capacity: Optional[int] = None):
    """Expert-parallel shard_map variant (optimized path).

    Expert weights are sharded on the expert dim over ``tp_axis``; tokens
    (already sharded over the data axes outside) are replicated across
    ``tp_axis``. Each shard runs only its E/tp experts; a psum over
    ``tp_axis`` combines expert outputs. Collective cost per MoE layer:
    one all-reduce of (T_local, d) — instead of GSPMD's gather/scatter
    resharding of (E, C, d) buffers on the baseline path.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    tp = mesh.shape[tp_axis]
    t = x.shape[0]
    cap = capacity or _capacity(cfg, t)
    e_local = m.n_experts // tp

    data_axes = tuple(a for a in mesh.axis_names if a != tp_axis)

    def local_fn(x_l, router, wg, wu, wd, shared):
        t_l = x_l.shape[0]                         # local token count
        axis_i = jax.lax.axis_index(tp_axis)
        lo = axis_i * e_local
        cap_l = max(8, -(-t_l * m.top_k // m.n_experts) * 2)
        logits = x_l.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, m.top_k)
        w = w / jnp.clip(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
        flat_idx = idx.reshape(-1)
        local = (flat_idx >= lo) & (flat_idx < lo + e_local)
        loc_idx = jnp.where(local, flat_idx - lo, e_local)  # e_local = drop bin
        one_hot = jax.nn.one_hot(loc_idx, e_local + 1, dtype=jnp.int32)
        pos = (jnp.take_along_axis(jnp.cumsum(one_hot, axis=0) - 1,
                                   loc_idx[:, None], axis=1)[:, 0])
        keep = local & (pos < cap_l)
        tok_of_slot = jnp.repeat(jnp.arange(t_l), m.top_k)
        safe_e = jnp.where(keep, loc_idx, 0)
        safe_p = jnp.where(keep, pos, cap_l - 1)
        buf = jnp.zeros((e_local, cap_l, x_l.shape[-1]), x_l.dtype)
        buf = buf.at[safe_e, safe_p].add(
            jnp.where(keep[:, None], x_l[tok_of_slot], 0))
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
            * jnp.einsum("ecd,edf->ecf", buf, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)
        gathered = jnp.where(keep[:, None], out[safe_e, safe_p], 0)
        scale = w.reshape(-1)[:, None].astype(x_l.dtype)
        y = jnp.zeros_like(x_l).at[tok_of_slot].add(gathered * scale)
        y = jax.lax.psum(y, tp_axis)
        if shared is not None:
            y = y + mlp(shared, x_l[None])[0]
        # load-balance aux from local router stats, averaged over data axes
        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((m.n_experts,)).at[flat_idx].add(
            jnp.ones((t_l * m.top_k,))) / (t_l * m.top_k)
        aux = m.n_experts * jnp.sum(me * ce) * m.router_aux_coef
        if data_axes:
            aux = jax.lax.pmean(aux, data_axes)
        return y, aux

    shared = params.get("shared")
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(data_axes), P(), P(tp_axis), P(tp_axis), P(tp_axis),
                  None if shared is None else P()),
        out_specs=(P(data_axes), P()),
        check_vma=False,
    )
    return fn(x, params["router"], params["wg"], params["wu"], params["wd"],
              shared)
