"""The program's spans in a traced window, and the three readers that
split the device's idle time by them."""
import functools
import glob
import os
import shutil

import pytest

from chipbench import harness, program_spans, trace
from chipbench.run import run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("train.stage_entry_idle", "train.round_host_idle",
           "train.stage_entry_ms")
CELL = "qwen2-7b-l4.round.devft"


class _Cell:
    def __init__(self, path):
        self.trace_path = path


def _read_all(path, schedules=1):
    ctx = {"cell": _Cell(path), "counts": {"schedules": schedules}}
    return {m: harness.read_metric(ROOT, m, ctx) for m in READERS}


def test_outermost_stage_spans():
    spans = [(0, 10, "repro.stage.enter", {}),
             (2, 4, "repro.devft.transfer", {}),
             (20, 25, "repro.devft.transfer", {}),
             (30, 31, "repro.round.plan", {})]
    got = program_spans.outermost(spans, ("repro.stage.enter",
                                          "repro.devft.transfer"))
    assert [sp[:2] for sp in got] == [(0, 10), (20, 25)]
    assert [program_spans.is_stage(sp[2]) for sp in spans] == \
        [True, True, True, False]


def test_a_trace_without_program_spans(monkeypatch):
    """The checked-in chip trace predates the program's spans: every
    reader reads nothing, and the idle time still adds up to what the
    trace's own reduction finds."""
    path = glob.glob(os.path.join(DATA, "*.xplane.pb"))[0]
    window = "bench.round_schedule"
    r = program_spans.reduce(path, window)
    assert r["spans"] == []
    want = trace.reduce(path, window=window)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["idle_s"] == pytest.approx(want["window_s"] - want["busy_s"],
                                        rel=1e-6)
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(r["idle_s"],
                                                              rel=1e-6)
    monkeypatch.setattr(program_spans, "reduce", functools.partial(
        program_spans.reduce, window=window))
    assert _read_all(path) == {m: None for m in READERS}


def _round_tokens(spans):
    return sum(st["tokens"] for _, _, n, st in spans if n == "repro.round")


def test_a_rehearsed_traced_run(tmp_path):
    """The round cell at its rehearsal size, traced on the CPU: the three
    readers read, the two idle shares lie within the device's idle
    share, and the window's rounds trained what its schedules hold."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    out = run_cell(CELL, 2 ** 31 + 7, 0.5, True, root=str(tmp_path),
                   rehearse=True, t_start=0.0)
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(READERS) <= set(got)
    assert all(got[m] >= 0 for m in READERS)
    stage, rnd = got["train.stage_entry_idle"], got["train.round_host_idle"]
    assert stage + rnd <= got["train.device_idle"] + 1e-6
    assert got["train.stage_entry_ms"] > 0

    path, = glob.glob(str(tmp_path / ".chipbench_trace" / CELL / "**"
                          / "*.xplane.pb"), recursive=True)
    r = program_spans.reduce(path)
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(r["idle_s"],
                                                              rel=1e-6)
    cell = harness.Cell(str(tmp_path), CELL, 1, 1.0, True, True, 0.0)
    w = cell.params
    per_schedule = (int(w["n_clients"] * w["sample_frac"]) * w["k_local"]
                    * w["local_batch"] * w["seq"] * w["rounds"])
    runs = sum(n == "repro.run" for _, _, n, _ in r["spans"])
    assert runs >= 1
    assert _round_tokens(r["spans"]) == runs * per_schedule


def test_a_recorded_chip_trace_with_program_spans():
    """One TPU v5e running one DevFT schedule of the round cell at small
    sizes (4 layers of width 512, 4 heads of 128, 2 clients x 2 steps of
    2 x 128 tokens, stages of 2 and 4 layers) with the program's spans
    (checked in)."""
    path, = glob.glob(os.path.join(DATA, "program_spans", "*.xplane.pb"))
    r = program_spans.reduce(path)
    want = trace.reduce(path)
    assert want["n_devices"] == 1
    assert r["idle_s"] == pytest.approx(want["window_s"] - want["busy_s"],
                                        rel=1e-6)
    names = [n for _, _, n, _ in r["spans"]]
    runs = names.count("repro.run")
    assert runs == 1
    rounds = [st for _, _, n, st in r["spans"] if n == "repro.round"]
    assert [(st["stage"], st["capacity"]) for st in rounds] == \
        [(0, 2), (0, 2), (1, 4), (1, 4)]
    assert _round_tokens(r["spans"]) == runs * 4 * (2 * 2 * 2 * 128)
    enters = [sp for sp in r["spans"] if sp[2] == "repro.stage.enter"]
    assert [sp[3]["stage"] for sp in enters] == [0, 1]
    for name in ("repro.devft.group", "repro.devft.fuse"):
        inside = [e[3]["stage"] for e in enters for sp in r["spans"]
                  if sp[2] == name and e[0] <= sp[0] and sp[1] <= e[1]]
        assert inside == [0], name

    got = _read_all(path, schedules=runs)
    idle = 100.0 * (1.0 - want["busy_s"] / want["window_s"])
    split = got["train.stage_entry_idle"] + got["train.round_host_idle"]
    assert 0.9 * idle <= split <= idle + 1e-6
    outer = program_spans.outermost(r["spans"], ("repro.stage.enter",
                                                 "repro.devft.transfer"))
    # stage 0's and stage 1's entries, and the transfer in finalize
    assert len(outer) == 3
    assert got["train.stage_entry_ms"] == pytest.approx(
        1e3 * sum(e - s for s, e, _, _ in outer))
