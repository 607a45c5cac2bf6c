"""The bring-up path on the CPU: the full-width spec, the base dtype, the
mesh axis types, the compile-cache setup, and ``chip_smoke.py`` itself —
which must refuse to report success anywhere but on a TPU."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType

from repro.experiments import ExperimentSpec
from repro.launch import env
from repro.launch.mesh import make_host_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("chip_smoke", mod)   # its dataclasses look here
    spec.loader.exec_module(mod)
    return mod


def test_full_spec_keeps_published_widths_at_cut_depth():
    cfg = ExperimentSpec(arch="qwen2-7b", full=True, layers=4).build_cfg()
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff,
            cfg.vocab, cfg.qkv_bias) == (3584, 28, 4, 128, 18944, 152064,
                                         True)
    assert cfg.n_layers == 4
    # without a cut the published depth stands
    assert ExperimentSpec(arch="qwen2-7b", full=True).build_cfg() \
        .n_layers == 28


def test_base_dtype_is_the_configs_at_full_width_only():
    assert ExperimentSpec(arch="qwen2-7b", full=True,
                          layers=4).base_dtype() == jnp.bfloat16
    # the reduced CPU path keeps float32, so no golden round log moves
    assert ExperimentSpec(arch="qwen2-7b").base_dtype() == jnp.float32


def test_host_mesh_axes_are_auto():
    mesh = make_host_mesh()
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)


def test_compile_cache_follows_the_env_var_else_the_checkout(monkeypatch,
                                                              tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(env.CACHE_ENV, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert env.setup_environment() == {
            "compilation_cache_dir": str(tmp_path)}
        assert jax.config.jax_compilation_cache_dir is None  # JAX reads it
        monkeypatch.delenv(env.CACHE_ENV)
        got = env.setup_environment()["compilation_cache_dir"]
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_device_check_raises_on_cpu():
    smoke = _load_chip_smoke()
    with pytest.raises(RuntimeError, match="needs a TPU"):
        smoke.require_tpu()


def _run(args, tmp_path, cwd=ROOT, **extra_env):
    e = dict(os.environ, JAX_PLATFORMS="cpu",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
             **extra_env)
    proc = subprocess.run([sys.executable, *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=600)
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    return proc, lines


def _no_ok_line(lines):
    return not any(line.get("ok") is True for line in lines)


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    proc, lines = _run([SCRIPT], tmp_path)
    assert proc.returncode != 0
    assert _no_ok_line(lines)
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_alone_fails(tmp_path):
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(SCRIPT, lone)
    proc, lines = _run([str(lone / "chip_smoke.py")], tmp_path, cwd=lone)
    assert proc.returncode != 0
    assert _no_ok_line(lines)


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal_runs_every_phase_and_refuses_ok(tmp_path,
                                                              chips):
    extra = {}
    if chips == 4:
        extra["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc, lines = _run([SCRIPT, "--rehearse", "--chips", str(chips)],
                       tmp_path, **extra)
    assert proc.returncode == 1, proc.stderr[-4000:]
    assert _no_ok_line(lines)
    phases = {line.get("phase") for line in lines}
    if chips == 1:
        assert {"train", "train_reference", "train_vs_reference", "serve",
                "serve_vs_reference"} <= phases
        train = [l for l in lines if l.get("phase") == "train"
                 and "eval_loss" in l]
        assert sorted({l["capacity"] for l in train}) == [2, 4]
        served = [l for l in lines if "answered" in l]
        assert served and served[0]["answered"] == 4
    else:
        assert {"mesh", "mesh_vs_unsharded"} <= phases
    assert lines[-1] == {"phase": "rehearsal", "done": True,
                         "note": "tiny sizes prove nothing about the "
                                 "chip: no ok line"}
