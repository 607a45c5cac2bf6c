"""Ragged KV-cache manager: per-slot write cursors over the model's
stacked cache tree, with reset-on-recycle.

The model's decode cache (``transformer.init_cache``) already carries a
per-slot position vector ``pos (B,)``; the decode path writes each
slot's new K/V at its OWN cursor (``pos % capacity`` per batch row) and
masks reads with ``kv_valid_len = min(pos + 1, capacity)`` — the ragged
contract of ``layers.attend``. This manager owns that tree for a slot
pool: allocation at a fixed ``(n_slots, capacity)``, per-slot validity
windows, and zero-reset of one slot when it is recycled to a new
request (conv/SSM state included, so recurrent families recycle too).

Kernel seam: single-token decode attention routes through the
``flash_decode`` name in ``repro.kernels.dispatch``; the Pallas kernel
registered under ``("flash_decode", "pallas")`` serves every
engine/serve path on the TPU with no model edits. Its contract
is the reference signature: ``flash_decode(q, k, v, *, kv_valid_len,
scale=None, interpret=False)`` with ``q (B, 1, H, hd)``, cache-resident
``k/v (B, C, Hkv, hd)`` and ``kv_valid_len (B,)`` masking ragged slots.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T


def _reset_slot(cache, slot):
    """Zero one slot's entries across the whole cache tree (stack leaves
    are ``(L, B, ...)`` — batch axis 1 — and ``pos`` is ``(B,)``)."""
    stacks = jax.tree.map(lambda a: a.at[:, slot].set(0), cache["stacks"])
    return {"stacks": stacks, "pos": cache["pos"].at[slot].set(0)}


class KVCacheManager:
    """Fixed-pool ragged cache for ``n_slots`` decode slots of capacity
    ``capacity`` tokens each. ``cache`` is the live device tree the
    engine threads through its jitted step (replace it after each
    step); ``reset_slot`` recycles one slot without touching the rest.
    """

    def __init__(self, cfg, n_slots: int, capacity: int, dtype=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.capacity = capacity
        self.cache = T.init_cache(cfg, n_slots, capacity,
                                  dtype or jnp.dtype(cfg.dtype))
        # slot traced -> one compile covers every recycle
        self._reset = jax.jit(_reset_slot, donate_argnums=(0,))

    def reset_slot(self, slot: int) -> None:
        self.cache = self._reset(self.cache, jnp.int32(slot))

    # ---- host-side views --------------------------------------------
    def positions(self) -> np.ndarray:
        """Per-slot write cursors (absolute token positions)."""
        return np.asarray(self.cache["pos"])

    def valid_len(self) -> np.ndarray:
        """Per-slot count of live cache entries (ragged lengths)."""
        return np.minimum(self.positions(), self.capacity)

    def fits(self, n_tokens: int) -> bool:
        """Whether a request of ``n_tokens`` total (prompt + generated)
        fits without ring-buffer wraparound."""
        return n_tokens <= self.capacity


def check_capacity(capacity: int, prompt_len: int, max_new: int,
                   ring: bool, *, what: str = "request") -> None:
    """Shared admission guard: a job needing ``prompt_len + max_new``
    cache entries either fits, runs as an explicit ring buffer
    (sliding-window attention over the last ``capacity`` tokens via
    ``kv_valid_len``), or is an error — never a silent truncation."""
    need = prompt_len + max_new
    if need > capacity and not ring:
        raise ValueError(
            f"{what} needs {need} cache entries (prompt {prompt_len} + "
            f"gen {max_new}) but capacity is {capacity}; raise the "
            f"capacity or opt into ring-buffer (sliding-window) decode "
            f"explicitly")


def flash_decode(q, k, v, *, kv_valid_len, scale: Optional[float] = None,
                 backend: str = "reference"):
    """Single-token ragged-cache attention through the dispatch seam
    (falls back to the reference implementation until a Pallas decode
    kernel registers)."""
    from repro.kernels import dispatch
    fd = dispatch.get_kernel("flash_decode", backend)
    return fd(q, k, v, kv_valid_len=kv_valid_len, scale=scale,
              interpret=dispatch.interpret_default())
