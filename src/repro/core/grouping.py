"""Deconfliction-guided layer grouping (DGLG) — paper §3.2.

Pipeline (Eq. 1–3): per-layer parameter vectors → cosine similarity matrix
W → graph Laplacian L = D − W → eigenvectors of the L_s smallest
eigenvalues → k-means on the spectral embedding → L_s groups.

Ablation variants (paper Table 2): RANDOM and EVEN grouping.

All functions operate on a *layer stack*: a pytree whose leaves have a
leading layer axis (the representation used by ``repro.models``).
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import keyed_rng, seed_entropy

# ---------------------------------------------------------------------------
# Layer vectors + similarity (Eq. 1)
# ---------------------------------------------------------------------------


def layer_vectors(stack: dict, lora_stack: Optional[dict] = None,
                  max_elems: int = 1 << 20) -> jax.Array:
    """Flatten each layer of a stack into a vector (L, D).

    Includes the layer's LoRA parameters when given (Eq. 1: "including
    their corresponding LoRA parameters"). For very wide layers a
    deterministic stride subsample caps D at ``max_elems`` — cosine
    similarity is preserved in expectation and this keeps the server-side
    grouping cheap even at 671B scale. The subsample is every
    ``stride``-th element of the layer's leaves laid end to end, read out
    of each leaf where it lies, so no full-width float32 copy of the
    stack is made.
    """
    leaves = list(jax.tree.leaves(stack))
    if lora_stack is not None:
        leaves += list(jax.tree.leaves(lora_stack))
    sizes = [math.prod(x.shape[1:]) for x in leaves]
    d = sum(sizes)
    stride = -(-d // max_elems) if d > max_elems else 1
    parts, offset = [], 0
    for x, n in zip(leaves, sizes):
        start = -offset % stride
        if start < n:
            parts.append(_strided(x, start, stride))
        offset += n
    return jnp.concatenate(parts, axis=1)


@functools.partial(jax.jit, static_argnames=("start", "stride"))
def _strided(x, start: int, stride: int) -> jax.Array:
    """Elements ``start, start + stride, ...`` of each layer of a leaf,
    as float32 (L, n)."""
    return jnp.reshape(x, (x.shape[0], -1))[:, start::stride].astype(
        jnp.float32)


def similarity_matrix(vecs: jax.Array) -> jax.Array:
    """Cosine similarity (Eq. 1). vecs: (L, D) -> (L, L) float32."""
    norms = jnp.linalg.norm(vecs, axis=1, keepdims=True)
    vn = vecs / jnp.clip(norms, 1e-12)
    w = vn @ vn.T
    return jnp.clip(w, -1.0, 1.0)


# ---------------------------------------------------------------------------
# Spectral clustering (Eq. 2–3)
# ---------------------------------------------------------------------------


def _kmeans(emb: np.ndarray, k: int, seed, iters: int = 100) -> np.ndarray:
    """Deterministic k-means++ on (L, k) spectral embedding. ``seed`` is
    an int or a tuple of keyed entropy (see ``keyed_rng``)."""
    rng = keyed_rng(*seed_entropy(seed), "grouping-kmeans")
    n = emb.shape[0]
    # k-means++ init
    centers = [emb[rng.randint(n)]]
    for _ in range(1, k):
        d2 = np.min(
            [np.sum((emb - c) ** 2, axis=1) for c in centers], axis=0)
        probs = d2 / max(d2.sum(), 1e-12)
        centers.append(emb[rng.choice(n, p=probs)])
    centers = np.stack(centers)
    labels = np.zeros(n, dtype=np.int64)
    for _ in range(iters):
        dists = np.sum((emb[:, None] - centers[None]) ** 2, axis=2)
        new_labels = np.argmin(dists, axis=1)
        # keep clusters non-empty: reassign the farthest point to any empty one
        for c in range(k):
            if not np.any(new_labels == c):
                far = np.argmax(np.min(dists, axis=1))
                new_labels[far] = c
        if np.array_equal(new_labels, labels):
            break
        labels = new_labels
        for c in range(k):
            centers[c] = emb[labels == c].mean(axis=0)
    return labels


def spectral_grouping(w: jax.Array, n_groups: int, seed=0
                      ) -> List[List[int]]:
    """Partition L layers into ``n_groups`` groups (Eq. 2–3).

    Returns groups as lists of layer indices, each sorted ascending,
    ordered by their anchor (minimum) index — the order in which the
    representative layers are concatenated into the submodel.
    """
    w = np.asarray(w, dtype=np.float64)
    L = w.shape[0]
    n_groups = min(n_groups, L)
    if n_groups == L:
        return [[i] for i in range(L)]
    np.fill_diagonal(w, 0.0)
    d = np.diag(w.sum(axis=1))
    lap = d - w
    eigvals, eigvecs = np.linalg.eigh(lap)          # ascending
    emb = eigvecs[:, :n_groups]                     # (L, L_s)
    # row-normalize (standard spectral clustering stabilization)
    nrm = np.linalg.norm(emb, axis=1, keepdims=True)
    emb = emb / np.clip(nrm, 1e-12, None)
    labels = _kmeans(emb, n_groups, seed)
    groups = [sorted(np.nonzero(labels == c)[0].tolist())
              for c in range(n_groups)]
    groups.sort(key=lambda g: g[0])
    return groups


# ---------------------------------------------------------------------------
# Ablation variants (Table 2)
# ---------------------------------------------------------------------------


def random_grouping(n_layers: int, n_groups: int, seed=0
                    ) -> List[List[int]]:
    rng = keyed_rng(*seed_entropy(seed), "grouping-random")
    n_groups = min(n_groups, n_layers)
    perm = rng.permutation(n_layers)
    groups = [sorted(perm[i::n_groups].tolist()) for i in range(n_groups)]
    groups.sort(key=lambda g: g[0])
    return groups


def even_grouping(n_layers: int, n_groups: int) -> List[List[int]]:
    """Contiguous equal-size blocks."""
    n_groups = min(n_groups, n_layers)
    bounds = np.linspace(0, n_layers, n_groups + 1).round().astype(int)
    return [list(range(bounds[i], bounds[i + 1])) for i in range(n_groups)]


def make_groups(method: str, stack: dict, lora_stack, n_groups: int,
                seed=0) -> List[List[int]]:
    L = jax.tree.leaves(stack)[0].shape[0]
    if method == "dglg":
        w = similarity_matrix(layer_vectors(stack, lora_stack))
        return spectral_grouping(w, n_groups, seed)
    if method == "random":
        return random_grouping(L, n_groups, seed)
    if method == "even":
        return even_grouping(L, n_groups)
    raise ValueError(f"unknown grouping method {method!r}")


def labels_from_groups(groups: Sequence[Sequence[int]], n_layers: int
                       ) -> np.ndarray:
    labels = np.zeros(n_layers, dtype=np.int64)
    for gi, g in enumerate(groups):
        for j in g:
            labels[j] = gi
    return labels
