"""The Mamba-2 / attention hybrid (Granite 4.0-H) against its plain
reference, ``chipbench/reference_hybrid.py``, at the configuration's
rehearsal widths on seeded random weights (``weights_hybrid``), on the
CPU.

The program runs in float32 here (the weights are made in float32), so
that a gap between the two sides is the algorithm's and not bfloat16's:
the program's SSD is chunked and the reference's quadratic, and the
chunked kernel's backward differentiates the chunked jnp form; the sums
run in another order, which float32 rounding bounds to about 1e-6 of
each value. Each tolerance below says what it allows for.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, hybrid_round_cell as H
from chipbench import reference_hybrid as RH, weights_hybrid as W
from chipbench.run import ROOT

CELL = "granite-4.0-h-micro.round.devft"


def _model(**over):
    """The rehearsal's model block in float32, with ``over`` applied."""
    cell = harness.Cell(ROOT, CELL, 0, 1.0, False, True, 0.0)
    m = dict(cell.model, dtype="float32", **over)
    return m


def _lora(m, seed, rank=4):
    """An adapter with both factors random, so every projection's LoRA
    moves the output."""
    lo = W.init_lora(m, seed, rank)
    key = jax.random.PRNGKey(seed)
    return jax.tree.map(
        lambda a: a if a.any() else 0.05 * jax.random.normal(
            jax.random.fold_in(key, a.size), a.shape, a.dtype), lo)


def _batch(m, seed, b=2, s=16):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"tokens": jax.random.randint(k1, (b, s), 0, m["vocab_size"]),
            "labels": jax.random.randint(k2, (b, s), 0, m["vocab_size"])}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _ssd_inputs(seed, b=2, s=24, h=4, p=8, g=2, n=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7))
    bb = jax.random.normal(ks[3], (b, s, g, n))
    cc = jax.random.normal(ks[4], (b, s, g, n))
    d = jax.random.normal(ks[5], (h,))
    return x, dt, a, bb, cc, d


def test_quadratic_ssd_matches_the_recurrence():
    """The reference's quadratic form against the token-by-token
    recurrence in float64: float32's rounding over 24 tokens."""
    args = _ssd_inputs(1)
    with jax.default_matmul_precision("highest"):
        y = RH.ssd(*args)
    want = RH.ssd_recurrence(*args)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)


def test_ssd_scan_forward_and_vjp_match_the_reference():
    """The program's ``ssd_scan`` (the Pallas kernel in interpret mode,
    three chunks of 8) and its VJP (the chunked jnp form) against the
    quadratic reference and its VJP: float32's rounding, in another
    order of summation."""
    from repro.kernels import ops

    args = _ssd_inputs(2, g=1)
    y, vjp = jax.vjp(lambda *a: ops.ssd_scan(*a, chunk=8, interpret=True),
                     *args)
    with jax.default_matmul_precision("highest"):
        want, vjp_ref = jax.vjp(RH.ssd, *args)
    assert _rel(y, want) < 1e-5
    g = jax.random.normal(jax.random.PRNGKey(3), y.shape)
    for got, ref in zip(vjp(g), vjp_ref(g)):
        assert _rel(got, ref) < 1e-4


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_program_loss_and_lora_grads_match_the_reference(backend):
    """The program's training loss and LoRA gradients, with each layer
    rematerialised as the cell runs it, on the jnp path and on the Pallas
    kernels in interpret mode (``ssd_scan``, ``lora_matmul``,
    ``flash_attention``), against the reference: float32 rounding in
    another order (chunked against quadratic SSD, fused kernels)."""
    from repro.models import transformer as T

    m = _model()
    cfg = dataclasses.replace(H.program_cfg(m), kernel_backend=backend)
    assert cfg.nope and cfg.mamba.chunk == 8
    params = W.base_params(m, 3)
    lora = _lora(m, 3)
    batch = _batch(m, 3)

    loss, grads = jax.value_and_grad(
        lambda lo: T.loss_fn(cfg, params, lo, batch, remat=True)[0])(lora)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(
            lambda lo: RH.loss(m, params, lo, batch)[0])(lora)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        ref = want_g
        for k in path:
            ref = ref[k.key]
        assert _rel(g, ref) < 2e-4, path


def test_the_reference_steps_a_period_at_a_time():
    """The reference's training path (a period's program at a time, its
    backward from each period's kept input) gives ``loss``'s value and
    gradient over two periods: the same operations in the same order,
    so float32 agrees to its last bits but for XLA's fusion."""
    types = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 2
    m = _model(num_hidden_layers=20, layer_types=types)
    params, lora, batch = W.base_params(m, 4), _lora(m, 4), _batch(m, 4)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(
            lambda lo: RH.loss(m, params, lo, batch)[0])(lora)
        nll, parts = RH.loss_and_grads(m, params, lora, batch)
    assert len(parts) == 2
    assert abs(float(nll) - float(want)) < 1e-6 * abs(float(want))
    for i, k in enumerate(("mamba_mlp", "attn_mlp")):
        got = jax.tree.map(lambda *a: jnp.concatenate(a),
                           *[p[i] for p in parts])
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want_g[k])):
            assert _rel(a, b) < 1e-5, k


def test_the_multipliers_and_nope_are_not_identities_here():
    """Each of Granite's multipliers moves the reference's LoRA gradients
    at these weights by more than ten times the tolerance of the
    comparison above (2e-4 of a leaf), so that comparison would see a
    program that left one out."""
    m = _model()
    params, lora, batch = W.base_params(m, 4), _lora(m, 4), _batch(m, 4)

    def grads(mm):
        return jax.grad(lambda lo: RH.loss(mm, params, lo, batch)[0])(lora)

    with jax.default_matmul_precision("highest"):
        base = grads(m)
        for k in ("embedding_multiplier", "attention_multiplier",
                  "residual_multiplier", "logits_scaling"):
            other = grads(dict(m, **{k: 1.0}))
            assert max(_rel(a, b) for a, b in zip(
                jax.tree.leaves(other), jax.tree.leaves(base))) > 2e-3, k


def test_decode_through_the_caches_matches_the_reference_forward():
    """Token-by-token decode through the Mamba conv/SSM states and the
    attention KV cache, with NoPE and the multipliers on, against the
    reference's full forward logits: float32 rounding of the recurrent
    state against the quadratic form."""
    from repro.models import transformer as T

    m = _model()
    cfg = H.program_cfg(m)
    params, lora = W.base_params(m, 5), _lora(m, 5)
    tokens = _batch(m, 5, s=12)["tokens"]
    cache = T.init_cache(cfg, 2, 12, jnp.float32)
    outs = []
    for t in range(tokens.shape[1]):
        lg, cache = T.decode_step(cfg, params, lora, tokens[:, t:t + 1],
                                  cache)
        outs.append(lg[:, 0])
    got = jnp.stack(outs, 1)[..., :m["vocab_size"]]
    with jax.default_matmul_precision("highest"):
        h = RH.rms_norm(RH.hidden(m, params, lora, tokens),
                        params["final_norm"], m["rms_norm_eps"])
        want = h @ params["embed"].T / m["logits_scaling"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_a_stage_that_shrinks_both_stacks_matches_the_reference():
    """A DevFT stage of 10 of 20 layers (two periods): the program groups
    and fuses the 18 Mamba layers into 9 and the 2 attention layers into
    1, each stack on its own; the reference judges its groups
    (``group_gap``, under the cell's limit) and fuses from them as the
    program does; the transfer hands each layer its group's adapter.
    Fusion is exact up to float32's rounding of Eq. 5."""
    from repro.core.devft import build_submodel
    from repro.core.transfer import transfer_stage
    from repro.models import transformer as T

    types = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 2
    m = _model(num_hidden_layers=20, layer_types=types)
    cfg = H.program_cfg(m)
    params, lora = W.base_params(m, 6), _lora(m, 6)
    sub = build_submodel(cfg, params, lora, 10, beta=0.1, seed=6)
    caps = RH.stack_capacities(m, 10)
    assert caps == {"mamba_mlp": 9, "attn_mlp": 1}
    assert T.stack_sizes(sub.params["blocks"]) == caps
    # the submodel keeps attention at the period's offset
    assert [n for n, _ in T.execution_order(sub.cfg, caps)].index(
        "attn_mlp") == 5
    limit = harness.Cell(ROOT, CELL, 0, 1.0, False, True, 0.0) \
        .params["limits"]["group_gap"]
    for k, n in caps.items():
        groups = sub.plan[k]["groups"]
        gap = RH.group_gap(RH.layer_gram(params["blocks"][k], lora[k]),
                           groups, n)
        assert gap is not None and gap <= limit, (k, gap)
        for got, want in ((sub.params["blocks"][k],
                           RH.fuse(params["blocks"][k], groups, 0.1)),
                          (sub.lora[k], RH.fuse(lora[k], groups, 0.1))):
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)
    trained = jax.tree.map(lambda a: a + 1.0, sub.lora)
    back = transfer_stage(lora, trained, sub.plan)
    for k in caps:
        want = RH.broadcast(trained[k], sub.plan[k]["groups"],
                            len(lora[k]["in_proj" if k == "mamba_mlp"
                                        else "wq"]["a"]))
        for a, b in zip(jax.tree.leaves(back[k]), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_group_gap_judges_a_grouping_by_its_spread():
    """``group_gap`` on a similarity of 8 layers in two blocks of four:
    the reference's own grouping reads 0, one that mixes the blocks
    reads above 0, and a list that is no anchor-ordered partition into
    the stage's number of groups reads None."""
    w = np.full((8, 8), 0.1) + 0.6 * np.kron(np.eye(2), np.ones((4, 4)))
    np.fill_diagonal(w, 1.0)
    own = RH.spectral_groups(w, 2)
    assert own == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert RH.group_gap(w, own, 2) == pytest.approx(0.0, abs=1e-12)
    assert RH.group_gap(w, [[0, 1, 4, 5], [2, 3, 6, 7]], 2) > 1.0
    for bad in ([[0, 1, 2, 3], [4, 5, 6]], [[0, 1, 2, 3], [4, 5, 6, 7, 7]],
                [[4, 5, 6, 7], [0, 1, 2, 3]], [[1, 0, 2, 3], [4, 5, 6, 7]],
                [[0, 1, 2, 3, 4, 5, 6, 7]], [[0, 1, 2, 3], [], [4, 5, 6, 7]]):
        assert RH.group_gap(w, bad, 2) is None, bad


def test_the_file_states_what_the_program_runs():
    """Every key of the configuration file that the program depends on
    is checked against it: a file that disagrees is refused."""
    cell = harness.Cell(ROOT, CELL, 0, 1.0, False, False, 0.0)
    m = H._model(cell)
    cfg = H.program_cfg(m)
    assert (cfg.n_layers, cfg.d_model, cfg.attn_period, cfg.attn_offset) \
        == (40, 2048, 10, 5)
    assert cell.config["reduced"] == []
    for key, wrong in (("attention_multiplier", 0.125),
                       ("position_embedding_type", "rope"),
                       ("residual_multiplier", 1.0),
                       ("mamba_n_heads", 32)):
        with pytest.raises(harness.BenchError, match=key):
            H.program_cfg(dict(m, **{key: wrong}))
    types = list(m["layer_types"])
    types[5], types[6] = types[6], types[5]
    with pytest.raises(harness.BenchError, match="layer_types"):
        H.program_cfg(dict(m, layer_types=types))
    # 3.19B parameters, the bf16 base's 6.4 GB
    n = sum(int(np.prod(x.shape))
            for x in jax.tree.leaves(W.base_shapes(m)))
    assert n == 3_191_396_096


def test_operation_counts_follow_the_weights():
    """``flops_hybrid``'s matmul count is twice the projection weights a
    token meets (read off the weights' shapes: every matrix of a layer
    but the depthwise convolution's), plus the output head, attention's
    scores, the SSD and the convolution; a training token costs about
    two forwards (a frozen base); the ``ssd_scan`` count is the SSD's."""
    from chipbench import flops_hybrid as F

    cell = harness.Cell(ROOT, CELL, 0, 1.0, False, False, 0.0)
    m = H._model(cell)
    blocks = W.base_shapes(m)["blocks"]
    mats = sum(int(np.prod(x.shape))
               for path, x in jax.tree_util.tree_leaves_with_path(blocks)
               if x.ndim == 3 and path[-1].key != "conv_w")
    s, kv = 512, 513 / 2
    extra = 36 * (F.ssd_flops(m, s) + 2 * 4352 * 4) + 4 * 4 * 2048 * kv \
        + 2 * 2048 * 100352
    assert F.forward_flops(m, 40, kv) == pytest.approx(2 * mats + extra,
                                                       rel=1e-12)
    fwd = F.forward_flops(m, 40, kv, rank=32)
    assert 2.0 * fwd < F.train_flops(m, 40, s, 32) < 2.2 * fwd
    f, b = F.ssd_scan(m, rows=4, seq=s)
    assert f == 4 * s * F.ssd_flops(m, s)
    assert b == 4 * s * (2 * (2 * 64 * 64 + 2 * 128) + 4 * 64)


def test_the_kernel_readers():
    """``train.ssd_scan_roofline`` on a reduced trace: a schedule holds
    126 Mamba layer-rounds (9, 9, 18, 18, 36, 36), each with 10 local
    steps of two forwards (remat) over 2 clients x 2 x 512 tokens and an
    eval of 16 x 512; ``train.hybrid_lora_roofline`` reads every
    ``lora_matmul`` family. Neither reads a trace without its kernel."""
    from chipbench import round_cell

    cell = harness.Cell(ROOT, CELL, 1, 1.0, True, False, 0.0)
    m = H._model(cell)
    counts = dict(round_cell.schedule_counts(m, cell.params, H.PARTS),
                  schedules=1)
    pk = harness.peaks("TPU v5 lite")
    calls = {"ssd_scan": 0.5, "lora_matmul": 2.0,
             "jvp_jit_lora_matmul__": 0.25}
    ctx = {"trace": {"custom_calls": sorted(calls), "op_s": calls},
           "model": m, "workload": cell.params, "counts": counts,
           "peaks": pk}
    per_token = max(64 * ((128 + 64) * 257 + 4 * 128 * 64)
                    / pk["bf16_flops_per_s"],
                    (2 * (2 * 64 * 64 + 2 * 128) + 4 * 64)
                    / pk["hbm_bytes_per_s"])
    want = 100.0 * 126 * (10 * 2 * 4 * 512 + 16 * 512) * per_token / 0.5
    assert harness.read_metric(ROOT, "train.ssd_scan_roofline", ctx) == \
        pytest.approx(want, rel=1e-12)
    assert 0 < harness.read_metric(ROOT, "train.hybrid_lora_roofline",
                                   ctx) < 100
    bare = dict(ctx, trace={"custom_calls": ["flash_attention"],
                            "op_s": {"flash_attention": 1.0}})
    for name in ("train.ssd_scan_roofline", "train.hybrid_lora_roofline"):
        assert harness.read_metric(ROOT, name, bare) is None
