#!/usr/bin/env python3
"""Run one benchmark cell once, in this process, on the chip it is on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is ``chipbench/workloads/<name>.json``; its model configuration
is ``chipbench/configs/<config>.json``; its ``kind`` names the module
that runs it, ``chipbench/<kind>_cell.py``; the metrics it reports are the
entries of ``BENCHMARK.json`` that name it (or, for a per-layer metric
without a ``workloads`` key, the end-to-end metric it moves), each
per-layer one read by ``chipbench/metrics/<metric>.py``.

Set-up (loading, compiling, warming up every shape the window uses) is
timed from the start of this process to the start of the window. With
``--trace 0`` the result line carries the end-to-end metrics; with
``--trace 1`` the whole window is traced and it carries the per-layer
metrics, the device's busy time and a breakdown. After the window the
outputs of the timed path are compared with the plain reference
(``chipbench/reference.py``), each number beside its limit.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and (traced)
``breakdown``, then ``checks``. Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _paths(root: str) -> None:
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, rehearse: bool = False,
             t_start: float = None) -> dict:
    """One run of one cell; returns the result object. ``rehearse`` runs
    the configuration's tiny ``rehearsal`` sizes on whatever device JAX
    has (the CPU in the tests)."""
    _paths(root)
    from chipbench import harness

    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = harness.Cell(root, name, seed, seconds, trace, rehearse,
                        T_START if t_start is None else t_start)
    devices = harness.cell_devices(cell, rehearse)
    pk = harness.peaks("TPU v5 lite" if rehearse else devices[0].device_kind,
                       os.path.join(root, "chipbench"))
    if not rehearse:
        harness.setup_compile_cache(root)
    meter = harness.CompileMeter()
    mod = harness.cell_module(root, cell.workload["kind"])

    out = mod.run(cell, devices, meter)
    gc.collect()
    wanted = harness.cell_metrics(bench, name)
    metrics = {}
    result = {}
    if trace:
        from chipbench import trace as tr

        reduced = tr.reduce(cell.trace_path)
        ctx = {"trace": reduced, "cell": cell, "model": cell.model,
               "workload": cell.params, "counts": out["counts"],
               "peaks": pk, "n_chips": len(devices)}
        for m in wanted["per_layer"]:
            v = harness.read_metric(root, m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown(reduced)
        extra = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
    else:
        for m in wanted["end_to_end"]:
            if m["name"] not in out["e2e"]:
                raise harness.BenchError(f"the cell does not measure "
                                         f"{m['name']!r}")
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
        extra = {}
    checks = harness.report_checks(out["checks"])
    device = dict(harness.device_info(devices),
                  memory_peak_bytes=out["memory_peak_bytes"], **extra)
    return {"correct": harness.judge(out["checks"]),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device,
            **result, "window_compiles": cell.window_compiles,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
