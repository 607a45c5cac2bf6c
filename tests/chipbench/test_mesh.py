"""The four-chip round cell at its rehearsal size on four CPU devices, in
a subprocess (``XLA_FLAGS`` has to be set before JAX starts): the base and
the adapter made in the mesh's shardings equal the same makers' on one
device bit for bit (and the one-call maker's to the last bit), a sound
run is ``correct`` with nothing compiled in its window, and the faults
of ``test_checks.py`` fail closed on the mesh too."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("jax_cache")))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable,
                           os.path.join(HERE, "mesh_rehearsal.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return [json.loads(x) for x in proc.stdout.splitlines()
            if x.startswith("{")]


@pytest.mark.parametrize("seed", [5, 3000000000])
def test_sharded_weights_are_the_one_device_makers(lines, seed):
    leaves = [x for x in lines if x["check"] == "weights"
              and x["seed"] == seed][0]["leaves"]
    assert len(leaves) == 19            # 15 of the base, 4 of the adapter
    assert all(x["equal"] for x in leaves), leaves
    assert all(x["placed"] for x in leaves), leaves
    # the matrices are split over the chips (norms and biases are whole)
    assert sum(x["split"] for x in leaves) >= 10, leaves
    # the one-call maker of one-chip cells adds the same float32 fields in
    # a program XLA fuses otherwise: a few elements round the other way
    assert all(x["one_call_ulps"] <= 1 for x in leaves), leaves
    assert sum(x["one_call_off"] for x in leaves) \
        <= 1e-3 * sum(x["size"] for x in leaves), leaves


@pytest.mark.parametrize("fault", [None, "_unchanged_round",
                                   "_half_batch_round"])
def test_mesh_runs_pass_and_faults_fail(lines, fault):
    run = [x for x in lines if x["check"] == "run"
           and x["fault"] == fault][0]
    assert run["count"] == 4
    assert run["window_compiles"] == 0
    assert run["correct"] is (fault is None), run["checks"]
    assert run["metrics"] == ["setup_s", "train_tokens_per_s"]
