"""Mesh-sharded round engine: host-mesh parity with the unsharded path,
round-step aggregation-registry routing, eval_every semantics, jit
cache-key / memory-accounting / batch-seeding regressions."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.data import make_federated_data
from repro.data.synthetic import client_round_batches, keyed_rng
from repro.experiments import ExperimentSpec
from repro.federated import FedConfig, FederatedRunner, register_aggregator
from repro.federated.aggregation import _AGGREGATORS, _CANONICAL
from repro.federated.simulator import _memory_bytes
from repro.launch import sharding as shd
from repro.launch.mesh import make_host_mesh, resolve_mesh
from repro.launch.steps import make_federated_round_step
from repro.models import transformer as T


@pytest.fixture(scope="module")
def tiny_setup():
    from tests.conftest import TEST_SPEC
    cfg = dataclasses.replace(
        reduce_config(get_config("llama2-7b-proxy"), TEST_SPEC), n_layers=4)
    data = make_federated_data(cfg.vocab, n_clients=4, alpha=0.5, seed=0)
    return cfg, data


def _fed(method, **kw):
    base = dict(n_clients=4, sample_frac=0.5, k_local=2, local_batch=2,
                seq=16, rounds=4, lora_rank=2, lr=1e-3, method=method,
                n_stages=2)
    base.update(kw)
    return FedConfig(**base)


# ---------------------------------------------------------------------------
# host-mesh parity: the sharded path must reproduce the unsharded
# trajectory BIT-identically (reference backend resolves on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["devft", "fedit"])
def test_host_mesh_roundlogs_bit_identical(tiny_setup, method):
    cfg, data = tiny_setup
    logs_none = FederatedRunner(cfg, _fed(method), data).run()
    logs_mesh = FederatedRunner(cfg, _fed(method), data,
                                mesh=make_host_mesh()).run()
    assert len(logs_none) == len(logs_mesh) == 4
    for a, b in zip(logs_none, logs_mesh):
        assert dataclasses.asdict(a) == dataclasses.asdict(b)


def test_host_mesh_finalized_lora_matches(tiny_setup):
    cfg, data = tiny_setup
    r0 = FederatedRunner(cfg, _fed("devft"), data)
    r1 = FederatedRunner(cfg, _fed("devft"), data, mesh=make_host_mesh())
    r0.run()
    r1.run()
    for a, b in zip(jax.tree.leaves(r0.lora), jax.tree.leaves(r1.lora)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_experiment_mesh_knob(tiny_setup):
    """spec.mesh='host' routes through resolve_mesh and reproduces the
    default-device trajectory."""
    from repro.experiments import run_experiment
    spec = ExperimentSpec(
        reduced={"n_layers": 2, "d_model": 128, "n_heads": 4,
                 "n_kv_heads": 2, "d_ff": 256, "vocab": 256,
                 "n_experts": 4, "top_k": 2},
        layers=4, n_clients=4, sample_frac=0.5, k_local=2, local_batch=2,
        seq=16, rounds=2, lora_rank=2, lr=1e-3, method="fedit")
    a = run_experiment(spec)
    b = run_experiment(spec.replace(mesh="host"))
    assert [dataclasses.asdict(l) for l in a.logs] \
        == [dataclasses.asdict(l) for l in b.logs]


def test_multi_device_mesh_takes_the_reference_kernels(tiny_setup):
    """GSPMD cannot partition a Pallas kernel: on a mesh of more than
    one device ``auto`` runs the reference path and ``pallas`` is an
    error; one device keeps the config."""
    import types

    from repro.federated.simulator import mesh_kernel_backend
    cfg, _ = tiny_setup
    four = types.SimpleNamespace(size=4)
    auto = dataclasses.replace(cfg, kernel_backend="auto")
    pallas = dataclasses.replace(cfg, kernel_backend="pallas")
    assert mesh_kernel_backend(auto, four).kernel_backend == "reference"
    with pytest.raises(ValueError, match="cannot be partitioned"):
        mesh_kernel_backend(pallas, four)
    assert mesh_kernel_backend(pallas, make_host_mesh()) is pallas
    assert mesh_kernel_backend(pallas, None) is pallas


def test_resolve_mesh_names():
    assert resolve_mesh(None) is None
    assert resolve_mesh("none") is None
    assert resolve_mesh("host").shape == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match="unknown mesh"):
        resolve_mesh("16x16")
    with pytest.raises(ValueError, match="unknown mesh"):
        ExperimentSpec(mesh="16x16")


# ---------------------------------------------------------------------------
# eval_every: evaluated rounds match the every-round trajectory, skipped
# rounds carry the last eval forward, the final round always evaluates
# ---------------------------------------------------------------------------


def test_eval_every_carries_forward(tiny_setup):
    cfg, data = tiny_setup
    every = FederatedRunner(cfg, _fed("devft", rounds=5), data).run()
    sparse = FederatedRunner(cfg, _fed("devft", rounds=5, eval_every=3),
                             data).run()
    n = len(every)
    for r, (a, b) in enumerate(zip(every, sparse)):
        if r % 3 == 0 or r == n - 1:
            assert b.eval_loss == a.eval_loss, r    # fresh eval
            assert b.eval_acc == a.eval_acc, r
        else:
            assert b.eval_loss == sparse[r - 1].eval_loss, r
    # non-eval accounting is unaffected by the cadence
    for a, b in zip(every, sparse):
        assert a.comm_bytes_up == b.comm_bytes_up
        assert a.flops == b.flops


def test_eval_every_validation(tiny_setup):
    cfg, data = tiny_setup
    with pytest.raises(ValueError, match="eval_every"):
        FederatedRunner(cfg, _fed("fedit", eval_every=0), data).run()
    with pytest.raises(ValueError, match="eval_every"):
        ExperimentSpec(eval_every=0)


# ---------------------------------------------------------------------------
# launch.steps round step: same local training + the registered
# aggregation (the old copy hardcoded jnp.mean and bypassed the registry)
# ---------------------------------------------------------------------------


def _round_inputs(cfg, n_clients=2, k=2, batch=2, seq=16, rank=2, seed=0):
    key = jax.random.PRNGKey(seed)
    params = T.init_params(cfg, key, jnp.float32)
    lora = T.init_lora(cfg, jax.random.fold_in(key, 1), rank=rank)
    data = make_federated_data(cfg.vocab, n_clients=4, alpha=0.5, seed=0)
    batches = client_round_batches(data, [0, 1][:n_clients], k, batch, seq,
                                   seed=7)
    batches = {k_: jnp.asarray(v) for k_, v in batches.items()}
    return params, lora, batches


def test_round_step_routes_through_aggregation_registry(tiny_setup):
    cfg, _ = tiny_setup
    params, lora, batches = _round_inputs(cfg)
    calls = []

    def doubled_mean(global_lora, stacked):
        calls.append("hit")
        new = jax.tree.map(lambda a: 2.0 * jnp.mean(a, axis=0), stacked)
        return new, 0

    register_aggregator("test-doubled", doubled_mean)
    try:
        base = make_federated_round_step(cfg, k_local=2, remat=False)
        custom = make_federated_round_step(cfg, k_local=2, remat=False,
                                           aggregation="test-doubled")
        ref_lora, ref_loss = jax.jit(base)(params, lora, batches,
                                           jnp.float32(1e-3))
        got_lora, got_loss = jax.jit(custom)(params, lora, batches,
                                             jnp.float32(1e-3))
        assert calls, "registered aggregator was never traced"
        for a, b in zip(jax.tree.leaves(ref_lora), jax.tree.leaves(got_lora)):
            np.testing.assert_allclose(2.0 * np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
        np.testing.assert_allclose(float(ref_loss), float(got_loss))
    finally:
        _AGGREGATORS.pop("test-doubled")
        _CANONICAL.remove("test-doubled")


def test_round_step_lowers_sharded_like_the_dryrun(tiny_setup):
    """The dry-run's federated branch (mesh + shardings + abstract
    shapes) lowers and compiles the registry-routed round step."""
    cfg, _ = tiny_setup
    mesh = make_host_mesh()
    params, lora, batches = _round_inputs(cfg)
    p_specs = jax.eval_shape(lambda: params)
    l_specs = jax.eval_shape(lambda: lora)
    b_specs = jax.eval_shape(lambda: batches)
    in_sh = (shd.params_shardings(mesh, p_specs),
             shd.params_shardings(mesh, l_specs),
             shd.batch_shardings(mesh, b_specs),
             jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
    fn = make_federated_round_step(cfg, k_local=2)
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh).lower(
            p_specs, l_specs, b_specs,
            jax.ShapeDtypeStruct((), jnp.float32)).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    assert cost.get("flops", 0) > 0


def test_round_step_matches_simulator_round(tiny_setup):
    """One fedavg round via launch.steps == one round of the simulator's
    jitted program (same local train, same aggregation)."""
    cfg, data = tiny_setup
    fed = _fed("fedit", rounds=1)
    runner = FederatedRunner(cfg, fed, data)
    logs = runner.run()
    assert len(logs) == 1

    params = runner.params
    # rebuild the identical round inputs the runner consumed
    rng = keyed_rng(fed.seed, "cohort")
    clients = rng.choice(fed.n_clients, 2, replace=False)
    batches = client_round_batches(data, clients, fed.k_local,
                                   fed.local_batch, fed.seq,
                                   seed=(fed.seed, 0))
    batches = {k: jnp.asarray(v) for k, v in batches.items()}
    lora0 = T.init_lora(cfg, jax.random.fold_in(
        jax.random.PRNGKey(fed.seed), 1), rank=fed.lora_rank)
    step = make_federated_round_step(cfg, k_local=fed.k_local, remat=False)
    new_lora, _ = jax.jit(step)(params, lora0, batches,
                                jnp.float32(fed.lr))
    for a, b in zip(jax.tree.leaves(new_lora), jax.tree.leaves(runner.lora)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# regressions: jit cache key, memory accounting, batch seeding
# ---------------------------------------------------------------------------


def test_jit_cache_key_covers_full_subconfig(tiny_setup):
    """(n_layers, arch_id, backend) collided for sub-configs differing
    in any other field; the full-config key must not."""
    cfg, _ = tiny_setup
    wider = dataclasses.replace(cfg, d_ff=cfg.d_ff * 2)
    assert cfg.n_layers == wider.n_layers and cfg.arch_id == wider.arch_id
    assert FederatedRunner._jit_key(cfg) != FederatedRunner._jit_key(wider)
    # same config -> same key (cache still shares within a stage)
    assert FederatedRunner._jit_key(cfg) == \
        FederatedRunner._jit_key(dataclasses.replace(cfg))


def test_memory_estimate_scales_with_submodel_depth(tiny_setup):
    """A 4-layer stage submodel must NOT report the same activation
    bytes as the full-depth model (the old estimate hardcoded 8 layers
    of the full d_model)."""
    cfg, _ = tiny_setup
    params = {"blocks": {}, "embed": jnp.zeros((8, 8))}
    lora = {"wq": jnp.zeros((2, 2))}
    shallow = _memory_bytes(params, lora, 2, 16, dataclasses.replace(
        cfg, n_layers=1))
    deep = _memory_bytes(params, lora, 2, 16, cfg)  # 4 layers
    assert shallow < deep
    assert deep - shallow == 2 * 16 * cfg.d_model * 4 * 3


def test_devft_stage1_memory_below_final_stage(tiny_setup):
    cfg, data = tiny_setup
    logs = FederatedRunner(cfg, _fed("devft"), data).run()
    assert logs[0].capacity < logs[-1].capacity
    assert logs[0].memory_bytes < logs[-1].memory_bytes


def test_client_batches_order_independent(tiny_setup):
    """A client's round data must not depend on its position in the
    sampled-client list (old code threaded ONE RandomState through all
    clients sequentially)."""
    _, data = tiny_setup
    fwd = client_round_batches(data, [0, 1, 2], 2, 2, 16, seed=123)
    rev = client_round_batches(data, [2, 1, 0], 2, 2, 16, seed=123)
    np.testing.assert_array_equal(fwd["tokens"][0], rev["tokens"][2])
    np.testing.assert_array_equal(fwd["tokens"][2], rev["tokens"][0])
    np.testing.assert_array_equal(fwd["labels"][1], rev["labels"][1])
    # different clients still see different data
    assert not np.array_equal(fwd["tokens"][0], fwd["tokens"][1])
    # and different seeds re-roll the same client
    other = client_round_batches(data, [0], 2, 2, 16, seed=124)
    assert not np.array_equal(fwd["tokens"][0], other["tokens"][0])
