#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from, in one
process (the benchmark's own runs never run this).

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 10]

For each seed: the program's readings (the numbers the cell compares,
from a run of the timed path, as ``run.py`` makes them). For each
control seed also the control's (the reference with its base rounded to
float8 put in the program's place) and, for round cells, the fault of a
step that trains on half of each batch (the reference so planted). The
readings are the ``readings`` of the cell's module,
``chipbench/<kind>_cell.py``. One JSON line per reading on standard
output, then the fullest chip's peak memory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    first = harness.Cell(ROOT, args.workload, seeds[0], args.seconds, False,
                         args.rehearse, 0.0)
    devices = harness.cell_devices(first, args.rehearse)
    if not args.rehearse:
        harness.setup_compile_cache(ROOT)
    meter = harness.CompileMeter()
    mod = harness.cell_module(ROOT, first.workload["kind"])
    for seed in seeds:
        t0 = time.perf_counter()
        cell = harness.Cell(ROOT, args.workload, seed, args.seconds, False,
                            args.rehearse, t0)
        for what, val in mod.readings(cell, devices, seed in ctrl, meter):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "what": what, "value": val,
                              "wall_s": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
