"""The benchmark's entry point and its files, without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _run(root, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3000000000",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_exits_nonzero_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_exits_nonzero_in_a_directory_of_only_the_benchmark(tmp_path):
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    _no_result(proc)


@pytest.mark.parametrize("kind", ["cpu", "TPU v9 imaginary"])
def test_peaks_refuse_an_unknown_device(kind):
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks(kind)


def test_peaks_of_v5e_have_their_source():
    row = harness.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]


def test_every_entry_has_its_files():
    bench_dir = os.path.join(ROOT, "chipbench")
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert cfg[k] != cfg["published"][k]
    for w in BENCH["workloads"]:
        wf = json.load(open(os.path.join(bench_dir, "workloads",
                                         w["name"] + ".json")))
        assert wf["config"] == w["config"] and wf["chips"] == w["chips"]
        assert all(v is not None for v in wf["limits"].values()), w["name"]
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(bench_dir, "metrics",
                                           m["name"] + ".py"))


def test_names_and_units_keep_to_the_contract():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert 0 < BENCH["end_to_end"][0]["bound"] <= 0.25


def test_cells_report_what_the_contract_asks():
    for w in BENCH["workloads"]:
        got = harness.cell_metrics(BENCH, w["name"])
        e2e = {m["name"] for m in got["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert got["per_layer"], w["name"]


def test_meshes_cover_their_chips():
    """A cell that names a mesh spreads it over exactly the chips it asks
    for; a one-chip cell names none."""
    bench_dir = os.path.join(ROOT, "chipbench")
    for w in BENCH["workloads"]:
        wf = json.load(open(os.path.join(bench_dir, "workloads",
                                         w["name"] + ".json")))
        mesh = wf.get("mesh", {})
        assert int(np.prod(list(mesh.values()))) == w["chips"], w["name"]
        assert set(mesh) <= {"data", "model"}, w["name"]
