"""Block-size autotuning: cache round-trip/keying/invalidation,
dispatch consultation (hit, miss, explicit-kwarg precedence), candidate
enumeration through the declared layouts, and determinism of the
selected config under an injected measurement."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, dispatch, ops
from repro.kernels.autotune import DEFAULTS, TUNABLES, TuningCache


@pytest.fixture(autouse=True)
def _isolate_dispatch_cache():
    """Never let a test leave a tuning cache installed (or consume the
    developer's on-disk one)."""
    dispatch.set_tuning_cache(TuningCache(path="/nonexistent"))
    yield
    dispatch.set_tuning_cache(None)


def _filled_cache(tmp_path, platform, kernel="lora_matmul",
                  key="16x32:float32|32x24:float32",
                  config=None) -> TuningCache:
    cache = TuningCache(path=str(tmp_path / "tuning.json"))
    cache.store(platform, kernel, autotune.layout_signature(kernel), key,
                config or {"block_m": 64, "block_n": 128, "block_k": 128},
                us=10.0, default_us=20.0)
    return cache


# ---------------------------------------------------------------------------
# cache semantics
# ---------------------------------------------------------------------------


def test_cache_json_round_trip(tmp_path):
    cache = _filled_cache(tmp_path, "tpu")
    path = cache.save()
    loaded = TuningCache.load(path)
    assert loaded.data == cache.data
    with open(path) as f:            # the artifact is plain JSON
        assert json.load(f) == cache.data


def test_cache_load_missing_or_corrupt_is_empty(tmp_path):
    assert TuningCache.load(str(tmp_path / "nope.json")).data == {}
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert TuningCache.load(str(bad)).data == {}


def test_cache_is_platform_keyed(tmp_path):
    cache = _filled_cache(tmp_path, "tpu")
    sig = autotune.layout_signature("lora_matmul")
    key = "16x32:float32|32x24:float32"
    assert cache.lookup("tpu", "lora_matmul", key, sig) is not None
    # same kernel+shape on another platform: a miss, never a crossover
    assert cache.lookup("cpu", "lora_matmul", key, sig) is None


def test_stale_layout_signature_invalidates(tmp_path):
    cache = _filled_cache(tmp_path, "tpu")
    key = "16x32:float32|32x24:float32"
    real_sig = autotune.layout_signature("lora_matmul")
    assert cache.lookup("tpu", "lora_matmul", key, real_sig) is not None
    # the adapter grew/renamed a knob -> every old entry is unusable
    assert cache.lookup("tpu", "lora_matmul", key,
                        real_sig + ", new_knob=1") is None
    # storing under the new signature drops the stale bucket wholesale
    cache.store("tpu", "lora_matmul", "sig2", "other", {"block_m": 64},
                us=1.0, default_us=1.0)
    bucket = cache.data["tpu"]["lora_matmul"]
    assert bucket["layout_sig"] == "sig2"
    assert list(bucket["entries"]) == ["other"]


def test_env_var_overrides_default_path(monkeypatch, tmp_path):
    p = str(tmp_path / "env.json")
    monkeypatch.setenv(autotune.CACHE_ENV, p)
    assert autotune.default_cache_path() == p


def test_default_path_is_inside_the_package(monkeypatch):
    # the winners travel with the checkout; nothing under $HOME steers
    # kernel resolution
    import os

    import repro.kernels
    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    path = autotune.default_cache_path()
    pkg = os.path.dirname(os.path.abspath(repro.kernels.__file__))
    assert os.path.dirname(path) == pkg
    assert not path.startswith(os.path.expanduser("~") + os.sep + ".cache")


# ---------------------------------------------------------------------------
# dispatch consultation
# ---------------------------------------------------------------------------


def _lora_args(m=16, k=32, n=24, r=4):
    key = jax.random.PRNGKey(0)
    return (jax.random.normal(key, (m, k)),
            jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 0.1,
            jax.random.normal(jax.random.fold_in(key, 2), (k, r)) * 0.1,
            jax.random.normal(jax.random.fold_in(key, 3), (r, n)) * 0.1)


def test_dispatch_applies_tuned_config(tmp_path):
    args = _lora_args()
    key = autotune.shape_key(args)
    platform = jax.default_backend()
    cfg = {"block_m": 8, "block_n": 128, "block_k": 128}
    dispatch.set_tuning_cache(_filled_cache(tmp_path, platform, key=key,
                                            config=cfg))
    assert dispatch.tuned_config("lora_matmul", args) == cfg
    # the wrapped pallas resolution produces the same numerics as the
    # raw default-block kernel (block sizes are schedule, not math)
    fn = dispatch.get_kernel("lora_matmul", "pallas")
    raw = dispatch.get_kernel("lora_matmul", "pallas", tuned=False)
    np.testing.assert_allclose(
        np.asarray(fn(*args, interpret=True)),
        np.asarray(raw(*args, interpret=True)), rtol=2e-5, atol=2e-5)
    # explicit caller kwargs beat the cache entry
    got = fn(*args, block_m=16, interpret=True)
    assert got.shape == (16, 24)


def test_dispatch_falls_back_to_defaults_on_miss(tmp_path):
    args = _lora_args()
    # empty cache -> miss -> default blocks (wrapper passes nothing)
    dispatch.set_tuning_cache(TuningCache(path=str(tmp_path / "e.json")))
    assert dispatch.tuned_config("lora_matmul", args) is None
    fn = dispatch.get_kernel("lora_matmul", "pallas")
    raw = dispatch.get_kernel("lora_matmul", "pallas", tuned=False)
    assert getattr(fn, "__wrapped__", None) is ops.lora_matmul
    np.testing.assert_allclose(
        np.asarray(fn(*args, interpret=True)),
        np.asarray(raw(*args, interpret=True)), rtol=0, atol=0)


def test_reference_resolutions_never_consult_cache(tmp_path):
    dispatch.set_tuning_cache(_filled_cache(tmp_path,
                                            jax.default_backend()))
    ref = dispatch.get_kernel("lora_matmul", "reference")
    assert not hasattr(ref, "__wrapped__")


# ---------------------------------------------------------------------------
# candidate enumeration + autotuner selection
# ---------------------------------------------------------------------------


def test_defaults_mirror_wrapper_signatures():
    import inspect
    for name, defaults in DEFAULTS.items():
        fn = dispatch.get_kernel(name, "pallas", platform="tpu",
                                 tuned=False)
        sig = inspect.signature(fn)
        for knob, value in defaults.items():
            assert sig.parameters[knob].default == value, (name, knob)
        assert set(TUNABLES[name]) == set(defaults)


def test_candidates_are_default_first_lint_valid_and_deduped():
    layout_fn = dispatch.kernel_layouts()["lora_matmul"]
    # the contract family's small case (rank 8 = one sublane granule;
    # rank 4 would fail the lint and yield zero candidates)
    args = [jax.ShapeDtypeStruct((16, 128), jnp.float32),
            jax.ShapeDtypeStruct((128, 128), jnp.float32),
            jax.ShapeDtypeStruct((128, 8), jnp.float32),
            jax.ShapeDtypeStruct((8, 128), jnp.float32)]
    cands = autotune.candidate_configs("lora_matmul", layout_fn, args, {})
    assert cands[0] == DEFAULTS["lora_matmul"]
    # tiny dims cap every block -> heavy dedup, but never zero
    sweep = math.prod(len(v) for v in TUNABLES["lora_matmul"].values())
    assert 1 <= len(cands) <= sweep + 1
    from repro.analysis.lowered.layout_lint import lint_layout
    seen = set()
    for cfg in cands:
        layout = layout_fn(*args, **cfg)
        assert lint_layout(layout) == []
        assert repr(layout) not in seen
        seen.add(repr(layout))


def test_autotuner_selection_is_deterministic_under_fixed_measure():
    """With an injected measurement the selected config is a pure
    function of the candidate list: repeated runs agree, the winner is
    the injected optimum, and a tie resolves to the default (the
    never-slower-than-default rule)."""
    args = _lora_args(m=256, k=128, n=128, r=8)
    calls = []

    def fake_measure(fn, a, kw, *, iters):
        del fn, a, kw, iters
        calls.append(None)
        return float(len(calls))          # strictly increasing -> first wins

    r1 = autotune.tune_case("lora_matmul", "t", list(args), {}, {},
                            iters=1, measure=fake_measure)
    calls.clear()
    r2 = autotune.tune_case("lora_matmul", "t", list(args), {}, {},
                            iters=1, measure=fake_measure)
    assert r1.config == r2.config == DEFAULTS["lora_matmul"]
    assert r1.is_default and r1.us == r1.default_us == 1.0

    # now make a specific non-default candidate strictly fastest
    layout_fn = dispatch.kernel_layouts()["lora_matmul"]
    cands = autotune.candidate_configs("lora_matmul", layout_fn, args, {})
    assert len(cands) > 1                 # the sweep is real at this shape
    target = cands[-1]
    idx = [0]

    def biased_measure(fn, a, kw, *, iters):
        us = 5.0 if idx[0] == len(cands) - 1 else 10.0 + idx[0]
        idx[0] += 1
        return us

    r3 = autotune.tune_case("lora_matmul", "t", list(args), {}, {},
                            iters=1, measure=biased_measure)
    assert r3.config == target
    assert not r3.is_default
    assert r3.us == 5.0 and r3.default_us == 10.0


def test_shape_key_ignores_values_uses_avals():
    a = jnp.zeros((4, 8), jnp.float32)
    b = jnp.ones((4, 8), jnp.float32)
    assert autotune.shape_key([a]) == autotune.shape_key([b]) \
        == autotune.shape_key([jax.ShapeDtypeStruct((4, 8), jnp.float32)])
    assert autotune.shape_key([a]) != autotune.shape_key(
        [a.astype(jnp.bfloat16)])


def test_autotune_end_to_end_writes_consumable_cache(tmp_path):
    """One real (interpret-mode) sweep over the lora family's first
    case: the cache gains an entry the dispatch layer resolves."""
    cache = TuningCache(path=str(tmp_path / "t.json"))
    results = autotune.autotune(["lora_matmul"], cache=cache, iters=1,
                                max_cases=1)
    assert len(results) == 1
    res = results[0]
    assert res.kernel == "lora_matmul"
    assert res.us <= res.default_us       # never slower than default
    dispatch.set_tuning_cache(cache)
    assert dispatch.tuned_config("lora_matmul",
                                 key=res.key) == res.config
