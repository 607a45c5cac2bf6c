#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from, in one
process (the benchmark's own runs never run this).

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 10]

For each seed: the program's readings (the numbers the cell compares,
from a run of the timed path, as ``run.py`` makes them). For each
control seed also the control's (the reference with its base rounded to
float8 put in the program's place) and, for round cells, the fault of a
step that trains on half of each batch (the reference so planted). One
JSON line per reading on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def round_readings(cell, control: bool) -> list:
    import jax

    from chipbench import harness, round_cell as rc

    m, w = cell.model, cell.params
    runner, base, lora0, tap = rc.setup(cell)
    del runner
    gc.collect()
    if not rc.recorded(w, tap):
        raise harness.BenchError("the set-up schedule was not recorded")
    want = rc.reference_rounds(m, w, base, lora0, tap)
    out = [("program", rc.gaps(rc.program_rounds(tap, want), want))]
    if control:
        out.append(("control", rc.gaps(rc.reference_rounds(
            m, w, base, lora0, tap, control=True), want)))
        out.append(("half_batch", rc.gaps(rc.reference_rounds(
            m, w, base, lora0, tap, half_batch=True), want)))
    out.append(("groups", [r["groups"] for r in want]))
    del base, lora0, tap
    jax.clear_caches()
    return out


def serve_readings(cell, control: bool, meter) -> list:
    import jax

    from chipbench import serve_cell as sc

    m, w = cell.model, cell.params
    engine, base, stacked = sc.setup(cell)
    book = sc.Book(engine)
    run = sc._closed if cell.workload["kind"] == "serve_closed" else sc._open
    res = run(cell, book, m, w, meter)
    finished = [book.reqs[r.rid] for r in engine.finished
                if r.rid in book.reqs]
    del engine, book
    gc.collect()
    out = [("e2e", res["e2e"]),
           ("program", sc.check_served(m, w, base, stacked, finished,
                                       cell.seed))]
    if control:
        out.append(("control", sc.check_served(m, w, base, stacked, finished,
                                               cell.seed, control=True)))
    del base, stacked
    jax.clear_caches()
    return out


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
    import jax

    if not args.rehearse:
        harness.require_tpu(1)
        harness.setup_compile_cache(ROOT)
    meter = harness.CompileMeter()
    for seed in seeds:
        t0 = time.perf_counter()
        cell = harness.Cell(ROOT, args.workload, seed, args.seconds, False,
                            args.rehearse, t0)
        if cell.workload["kind"] == "round":
            out = round_readings(cell, seed in ctrl)
        else:
            out = serve_readings(cell, seed in ctrl, meter)
        for what, val in out:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "what": what, "value": val,
                              "wall_s": time.perf_counter() - t0}),
                  flush=True)
        print(json.dumps({"seed": seed, "peak": harness.memory_peak_bytes(
            jax.devices()[:1])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
