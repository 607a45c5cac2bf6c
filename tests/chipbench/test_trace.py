"""The reduction from a profiler trace to the per-layer numbers."""
import glob
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_names():
    assert trace.op_name("%lora_matmul.14 = bf16[4096,512]{1,0} "
                         "custom-call(bf16[4096,512] %x)") == "lora_matmul"
    assert trace.op_name("%while.308 = (s32[]) while(...)") == "while"
    assert trace.op_name("%fusion = f32[2] fusion(...)") == "fusion"
    assert trace.op_name("%copy-start.8 = (f32[2]) copy-start(...)") \
        == "copy-start"
    assert trace.module_name("jit_round_fn(15824259056859467744)") \
        == "jit_round_fn"


def test_self_time_of_nested_events():
    # a loop of 10 enclosing two bodies of 3 and 4, then a lone op of 2
    ev = [(0, 10, "while"), (1, 4, "fusion"), (5, 9, "lora_matmul"),
          (12, 14, "fusion")]
    got = trace._self_times(ev)
    assert got == {"while": 3.0, "fusion": 5.0, "lora_matmul": 4.0}
    assert trace._union([(s, e) for s, e, _ in ev]) == [(0, 10), (12, 14)]


def test_idle_gaps_go_to_the_covering_span():
    spans = [(0, 100, "bench.schedule"), (40, 60, "bench.host_batches")]
    gaps = [(10, 20), (45, 55), (90, 130)]
    got = trace._idle_by_span(gaps, spans)
    assert got["bench.schedule"] == pytest.approx(10e-9)
    assert got["bench.host_batches"] == pytest.approx(10e-9)
    assert got["bench.other"] == pytest.approx(40e-9)


def test_a_recorded_chip_trace():
    """One TPU v5e running a small DevFT schedule (2 layers of width 512,
    heads of 128) inside a ``bench.round_schedule`` span, then three
    engine steps (checked in)."""
    path = glob.glob(os.path.join(DATA, "*.xplane.pb"))[0]
    r = trace.reduce(path, window="bench.round_schedule")
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    # self times add up to no more than the busy time of the device
    assert sum(r["op_s"].values()) <= r["busy_s"] * 1.001
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert "lora_matmul" in r["custom_calls"]
    assert trace.kernel_seconds(r, "lora_matmul") > 0
    assert trace.kernel_seconds(r, "no_such_kernel") is None
    assert {"jit_round_fn", "jit_ev"} <= set(r["module_s"])
    b = trace.breakdown(r)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


@pytest.mark.parametrize("calls,reads", [
    ({"closed_call": 990}, True),
    ({"closed_call": 989}, False),
    ({"closed_call": 990, "closed_call_x": 990}, False),
    ({"moe_expert_ffn": 990, "closed_call": 5}, True),
    ({"flash_attention": 990}, False),
])
def test_moe_reader_needs_the_one_expert_kernel(calls, reads):
    """The expert kernel's roofline is read only from one family of
    custom calls whose launches number what one schedule of the granite
    cell needs (8 rounds over 3, 3, 6, 6, 12, 12, 24, 24 layers, 10 local
    steps and one eval each)."""
    from chipbench import harness, round_cell
    from chipbench.run import ROOT

    cell = harness.Cell(ROOT, "granite-moe-1b-a400m.round.devft", 1, 1.0,
                        True, False, 0.0)
    counts = dict(round_cell.schedule_counts(cell.model, cell.params),
                  schedules=1)
    reduced = {"custom_calls": sorted(calls),
               "op_s": {k: 1.0 for k in calls}, "op_runs": dict(calls)}
    ctx = {"trace": reduced, "model": cell.model, "workload": cell.params,
           "counts": counts, "peaks": harness.peaks("TPU v5 lite")}
    got = harness.read_metric(ROOT, "train.moe_ffn_roofline", ctx)
    assert (got is not None) is reads
    if reads:
        assert 0 < got < 100


def test_collective_share_counts_collectives_only():
    """``train.collective_share`` on a reduced trace: every collective
    and both halves of an asynchronous one count, nothing else does."""
    from chipbench import harness
    from chipbench.run import ROOT

    ops = {"all-gather": 1.0, "all-gather-start": 0.5, "all-gather-done": 0.25,
           "reduce-scatter": 0.125, "all-reduce": 2.0, "all-reduce-start": 1.0,
           "all-reduce-done": 0.5, "collective-permute-start": 0.25,
           "collective-permute-done": 0.125, "all-to-all": 0.25,
           "all-reduce-scatter-fusion": 0.5,
           "fusion": 10.0, "convolution_multiply_fusion": 3.0, "copy-start": 1.0,
           "copy-done": 1.0, "lora_matmul": 2.0, "reduce": 1.0,
           "scatter": 1.0, "dynamic-update-slice": 0.5}
    reduced = {"op_s": ops, "busy_s": 40.0}
    got = harness.read_metric(ROOT, "train.collective_share",
                              {"trace": reduced})
    assert got == pytest.approx(100.0 * 6.5 / 40.0)
    # one chip, no collective: nothing to read
    solo = {"op_s": {"fusion": 10.0, "copy-start": 1.0}, "busy_s": 12.0}
    assert harness.read_metric(ROOT, "train.collective_share",
                               {"trace": solo}) is None
