"""A later change adds a cell, a per-layer metric or a kind of cell as
files only: the harness finds them by name and runs them (the CPU
rehearsal path)."""
import json
import os
import shutil
import sys

import pytest

from chipbench import harness
from chipbench.run import run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_a_cell_and_a_metric_added_as_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    wdir = tmp_path / "chipbench" / "workloads"

    cell = json.load(open(wdir / "qwen2-7b-l4.round.devft.json"))
    cell.update(name="qwen2-7b-l4.round.fedit", method="fedit",
                why="FedIT: every round trains the whole model")
    (wdir / "qwen2-7b-l4.round.fedit.json").write_text(json.dumps(cell))
    (tmp_path / "chipbench" / "metrics" / "train.rounds_per_s.py").write_text(
        '"""Rounds per second of the traced window."""\n\n\n'
        'def read(ctx):\n'
        '    c = ctx["counts"]\n'
        '    return c["schedules"] * c["rounds"] / ctx["trace"]["window_s"]\n')
    bench["workloads"].append({
        "name": "qwen2-7b-l4.round.fedit", "config": "qwen2-7b-l4",
        "traffic": "round.fedit", "chips": 1, "why": cell["why"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("qwen2-7b-l4.round.fedit")
    bench["per_layer"].append({
        "name": "train.rounds_per_s", "unit": "1/s", "better": "higher",
        "source": "device_trace", "layer": "round program",
        "moves": "train_tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    plain = run_cell("qwen2-7b-l4.round.fedit", 5, 0.5, False,
                     root=str(tmp_path), rehearse=True, t_start=0.0)
    assert set(plain["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert plain["window_compiles"] == 0
    traced = run_cell("qwen2-7b-l4.round.fedit", 5, 0.5, True,
                      root=str(tmp_path), rehearse=True, t_start=0.0)
    # metrics that list their cells leave the new one out
    assert set(traced["metrics"]) == {"train.rounds_per_s"}
    assert traced["metrics"]["train.rounds_per_s"]["value"] > 0
    assert list(traced)[-1] == "checks"


KIND = '''"""Round cells of a block that brings its own parts: here the program's
config on its reference kernels and a reference that counts its evals."""
import dataclasses
import types

from chipbench import reference, round_cell

EVALS = []
ref = types.ModuleType("counted_reference")
ref.__dict__.update(vars(reference))


def _eval_loss(*a, **k):
    EVALS.append(1)
    return reference.eval_loss(*a, **k)


ref.eval_loss = _eval_loss
PARTS = round_cell.Parts(
    program_cfg=lambda m: dataclasses.replace(round_cell.program_cfg(m),
                                              kernel_backend="reference"),
    reference=ref)


def run(cell, devices, meter):
    return round_cell.run(cell, devices, meter, PARTS)


def readings(cell, devices, control, meter):
    return round_cell.readings(cell, devices, control, meter, PARTS)
'''


def test_a_kind_of_cell_added_as_a_file(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    (tmp_path / "chipbench" / "round_own_cell.py").write_text(KIND)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    wdir = tmp_path / "chipbench" / "workloads"
    cell = json.load(open(wdir / "qwen2-7b-l4.round.devft.json"))
    cell.update(name="qwen2-7b-l4.round.own", kind="round_own")
    (wdir / "qwen2-7b-l4.round.own.json").write_text(json.dumps(cell))
    bench["workloads"].append({
        "name": "qwen2-7b-l4.round.own", "config": "qwen2-7b-l4",
        "traffic": "round.own", "chips": 1, "why": "its own parts"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("qwen2-7b-l4.round.own")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    r = run_cell("qwen2-7b-l4.round.own", 5, 0.5, False, root=str(tmp_path),
                 rehearse=True, t_start=0.0)
    assert r["correct"] is True, r["checks"]
    assert r["window_compiles"] == 0
    assert set(r["metrics"]) == {"setup_s", "train_tokens_per_s"}
    # the new file ran, with its own reference: one eval per compared round
    mod = sys.modules["chipbench_cell_round_own"]
    assert len(mod.EVALS) == len(cell["check_rounds"])


def test_an_unknown_kind_is_an_error(tmp_path):
    with pytest.raises(harness.BenchError, match="kind 'no_such'"):
        harness.cell_module(str(tmp_path), "no_such")
