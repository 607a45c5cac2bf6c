"""Unit tests for the paper's core: DGLG (§3.2), DBLF (§3.3), knowledge
transfer (§3.4), and the stage schedule (§4.1)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core import (
    broadcast_lora,
    build_submodel,
    capacity_schedule,
    even_grouping,
    fuse_stack,
    layer_vectors,
    make_schedule,
    random_grouping,
    similarity_matrix,
    spectral_grouping,
    transfer_stage,
)
from repro.models import transformer as T


def _stack(key, L=8, d=16):
    return {"w": jax.random.normal(key, (L, d, d)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (L, d))}


# ---------------------------------------------------------------------------
# DGLG
# ---------------------------------------------------------------------------

def test_similarity_matrix_properties(rng):
    v = layer_vectors(_stack(rng))
    w = similarity_matrix(v)
    assert w.shape == (8, 8)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w).T, atol=1e-6)
    np.testing.assert_allclose(np.diag(np.asarray(w)), 1.0, atol=1e-5)
    assert np.all(np.abs(np.asarray(w)) <= 1.0 + 1e-6)


@pytest.mark.parametrize("max_elems", [1 << 20, 300, 37, 7, 1])
def test_layer_vectors_subsample_every_stride_th_element(rng, max_elems):
    """The subsample is every ``stride``-th element of each layer's
    leaves (and LoRA) laid end to end, read out of each leaf in place:
    the same vector as concatenating all of them in float32 first."""
    stack = dict(_stack(rng, d=12), h=jax.random.normal(
        jax.random.fold_in(rng, 2), (8, 3, 5), jnp.bfloat16))
    lora = {"a": jax.random.normal(jax.random.fold_in(rng, 3), (8, 11))}
    full = jnp.concatenate([x.astype(jnp.float32).reshape(8, -1) for x in
                            jax.tree.leaves(stack) + jax.tree.leaves(lora)],
                           axis=1)
    want = full[:, ::-(-full.shape[1] // max_elems)] \
        if full.shape[1] > max_elems else full
    got = layer_vectors(stack, lora, max_elems=max_elems)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_spectral_grouping_partitions(rng):
    w = similarity_matrix(layer_vectors(_stack(rng)))
    for g in [1, 2, 3, 8]:
        groups = spectral_grouping(w, g, seed=0)
        assert len(groups) == g
        flat = sorted(i for grp in groups for i in grp)
        assert flat == list(range(8))                 # disjoint cover
        assert all(len(grp) > 0 for grp in groups)
        anchors = [grp[0] for grp in groups]
        assert anchors == sorted(anchors)             # concat order


def test_spectral_grouping_finds_obvious_clusters():
    """Two copies of the same layer must land in the same group."""
    base = np.random.RandomState(0).randn(4, 64)
    # layers: [A, A+eps, B, B+eps, C, C+eps, D, D+eps]
    vecs = np.repeat(base, 2, axis=0)
    vecs[1::2] += 0.01 * np.random.RandomState(1).randn(4, 64)
    w = similarity_matrix(jnp.asarray(vecs))
    groups = spectral_grouping(w, 4, seed=0)
    pair_of = {}
    for gi, g in enumerate(groups):
        for j in g:
            pair_of[j] = gi
    for twin in range(0, 8, 2):
        assert pair_of[twin] == pair_of[twin + 1], groups


def test_grouping_variants_partition():
    for g in random_grouping(10, 3, seed=1), even_grouping(10, 3):
        flat = sorted(i for grp in g for i in grp)
        assert flat == list(range(10))
    # EVEN is contiguous
    for grp in even_grouping(10, 3):
        assert grp == list(range(grp[0], grp[-1] + 1))


# ---------------------------------------------------------------------------
# DBLF (Eq. 5)
# ---------------------------------------------------------------------------

def test_dblf_formula_exact(rng):
    stack = _stack(rng, L=6)
    groups = [[0, 2, 5], [1, 3], [4]]
    beta = 0.3
    fused = fuse_stack(stack, groups, beta, "dblf")
    for leaf_name in ("w", "b"):
        x = np.asarray(stack[leaf_name])
        for gi, g in enumerate(groups):
            anchor = x[g[0]]
            want = anchor + beta * sum(x[j] - anchor for j in g)
            np.testing.assert_allclose(np.asarray(fused[leaf_name][gi]),
                                       want, rtol=1e-5, atol=1e-5)


def test_dblf_beta_zero_is_anchor(rng):
    stack = _stack(rng)
    groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
    fused = fuse_stack(stack, groups, 0.0, "dblf")
    anchor = fuse_stack(stack, groups, 0.0, "anchor")
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(anchor)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_dblf_singleton_groups_identity(rng):
    stack = _stack(rng, L=4)
    groups = [[0], [1], [2], [3]]
    for beta in (0.0, 0.1, 1.0):
        fused = fuse_stack(stack, groups, beta, "dblf")
        for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(stack)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


def test_sum_and_rone_variants(rng):
    stack = _stack(rng, L=4)
    groups = [[0, 1], [2, 3]]
    s = fuse_stack(stack, groups, 0.1, "sum")
    np.testing.assert_allclose(np.asarray(s["w"][0]),
                               np.asarray(stack["w"][0] + stack["w"][1]),
                               rtol=1e-6)
    r = fuse_stack(stack, groups, 0.1, "rone", seed=3)
    for gi, g in enumerate(groups):
        assert any(np.allclose(np.asarray(r["w"][gi]),
                               np.asarray(stack["w"][j])) for j in g)


# ---------------------------------------------------------------------------
# Knowledge transfer (§3.4)
# ---------------------------------------------------------------------------

def test_broadcast_lora_maps_groups(rng):
    sub = {"a": jnp.arange(3, dtype=jnp.float32)[:, None]}
    groups = [[0, 3], [1], [2, 4, 5]]
    out = broadcast_lora(sub, groups, 6)
    np.testing.assert_array_equal(
        np.asarray(out["a"][:, 0]), [0, 1, 2, 0, 2, 2])


def test_transfer_preserves_structure_and_shapes(rng, test_spec):
    cfg = dataclasses.replace(
        reduce_config(get_config("llama2-7b-proxy"), test_spec), n_layers=8)
    params = T.init_params(cfg, rng, jnp.float32)
    lora = T.init_lora(cfg, rng, rank=2)
    sub = build_submodel(cfg, params, lora, 3, beta=0.1)
    assert jax.tree.leaves(sub.params["blocks"]["layers"])[0].shape[0] == 3
    new = transfer_stage(lora, sub.lora, sub.plan)
    assert jax.tree.structure(new) == jax.tree.structure(lora)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(lora)):
        assert a.shape == b.shape
    # every layer's lora must equal its group representative's
    groups = sub.plan["layers"]["groups"]
    a_new = np.asarray(new["layers"]["wq"]["a"])
    a_sub = np.asarray(sub.lora["layers"]["wq"]["a"])
    for gi, g in enumerate(groups):
        for j in g:
            np.testing.assert_allclose(a_new[j], a_sub[gi], atol=1e-6)


# ---------------------------------------------------------------------------
# Stage schedule (§4.1, Tables 5/6)
# ---------------------------------------------------------------------------

def test_paper_capacity_sequences():
    assert capacity_schedule(32) == [4, 8, 16, 32]          # LLaMA2-7B
    assert capacity_schedule(40) == [5, 10, 20, 40]         # LLaMA2-13B
    assert capacity_schedule(32, initial=4) == [4, 8, 16, 32]
    assert capacity_schedule(32, initial=4, growth=4) == [4, 16, 32]
    assert capacity_schedule(32, initial=4, growth=8) == [4, 32]
    for init in (1, 2, 8, 16, 32):                          # Table 5
        caps = capacity_schedule(32, initial=init)
        assert caps[0] == init and caps[-1] == 32
        assert all(a < b for a, b in zip(caps, caps[1:]))


def test_capacity_schedule_rejects_non_growing():
    """Regression: growth <= 1 used to spin forever in the
    ``initial=`` loop (and divide by int(growth**k)==0 without it) —
    now a clear error, in both branches."""
    for growth in (1.0, 0.5, 0.0, -2.0):
        with pytest.raises(ValueError, match="growth must be > 1"):
            capacity_schedule(32, initial=4, growth=growth)
        with pytest.raises(ValueError, match="growth must be > 1"):
            capacity_schedule(32, n_stages=4, growth=growth)


def test_capacity_schedule_fractional_growth_terminates():
    # int() truncation used to stall at caps[-1]=1 for growth < 2
    caps = capacity_schedule(8, initial=1, growth=1.5)
    assert caps[0] == 1 and caps[-1] == 8
    assert all(a < b for a, b in zip(caps, caps[1:]))


def test_make_schedule_rounds():
    sched = make_schedule(32, total_rounds=300)
    assert sum(sched.rounds_per_stage) == 300
    assert sched.capacities == [4, 8, 16, 32]
