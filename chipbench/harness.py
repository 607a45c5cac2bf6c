"""What every cell shares: finding its files by name, the device and its
peaks, the compile cache, the measured window and its trace, the
per-layer metric readers, and the result line.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file)."""


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def require_tpu(n_chips: int):
    """At least ``n_chips`` TPU devices, or an error. No fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"this benchmark needs a TPU; JAX found "
                         f"{devices[0].platform!r} devices")
    if len(devices) < n_chips:
        raise BenchError(f"the cell needs {n_chips} TPU chips; JAX found "
                         f"{len(devices)}")
    return devices[:n_chips]


def peaks(kind: str, root: str = HERE) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    table = load_json(os.path.join(root, "peaks.json"))
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json "
                         f"(known: {sorted(table)})")
    return table[kind]


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> Optional[int]:
    """The process's peak device memory on the fullest chip."""
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [int(v) for v in vals if v is not None]
    return max(vals) if vals else None


class CompileMeter:
    """Backend compiles (a persistent-cache hit counts only its read) and
    their seconds since the last ``take()``."""

    def __init__(self):
        import jax

        self.seconds, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def take(self) -> dict:
        out = {"compile_s": self.seconds, "compiles": self.programs}
        self.seconds, self.programs = 0.0, 0
        return out


def setup_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every
    program however small or quick to compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# the cell: its files, its window, its spans
# ---------------------------------------------------------------------------


class Cell:
    """One run of one cell: its workload and configuration files, merged
    model sizes, and the measured window."""

    def __init__(self, root: str, name: str, seed: int, seconds: float,
                 trace: bool, rehearse: bool, t_start: float):
        self.root, self.name, self.seed = root, name, seed
        self.seconds, self.trace, self.rehearse = seconds, trace, rehearse
        self.t_start = t_start
        bench = os.path.join(root, "chipbench")
        wpath = os.path.join(bench, "workloads", name + ".json")
        if not os.path.exists(wpath):
            raise BenchError(f"no workload file {wpath}")
        self.workload = load_json(wpath)
        self.config = load_json(os.path.join(
            bench, "configs", self.workload["config"] + ".json"))
        w = dict(self.workload)
        m = {k: v for k, v in self.config.items()
             if not isinstance(v, (dict, list)) or k == "published"}
        if rehearse:
            m.update(self.config.get("rehearsal", {}))
            w.update(self.workload.get("rehearsal", {}))
        m["lora_rank"], m["lora_alpha"] = w["lora_rank"], w["lora_alpha"]
        self.model, self.params = m, w
        self.t_window = None
        self.window_s = None
        self.window_compiles = None
        self.trace_path = None

    @contextlib.contextmanager
    def window(self, meter: CompileMeter):
        """The measured window: counts compiles inside it and, with
        ``trace``, records the device trace of all of it."""
        import jax

        out = os.path.join(self.root, ".chipbench_trace", self.name)
        if self.trace:
            import shutil
            shutil.rmtree(out, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(out, profiler_options=opts)
        meter.take()
        try:
            self.t_window = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                yield self
            self.window_s = time.perf_counter() - self.t_window
        finally:
            self.window_compiles = meter.take()["compiles"]
            if self.trace:
                jax.profiler.stop_trace()
                found = [os.path.join(d, f) for d, _, fs in os.walk(out)
                         for f in fs if f.endswith(".xplane.pb")]
                self.trace_path = found[0] if found else None

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_start


def span(name: str):
    """A host span in the profiler's trace (``bench.<name>``)."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


# ---------------------------------------------------------------------------
# metrics and the result line
# ---------------------------------------------------------------------------


def cell_metrics(bench: dict, cell: str) -> Dict[str, list]:
    """The cell's end-to-end and per-layer metric entries of
    ``BENCHMARK.json``: those that list the cell, and those without a
    ``workloads`` key whose (moved) end-to-end metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": layer}


def cell_module(root: str, kind: str):
    """``chipbench/<kind>_cell.py``, the module that runs cells of that
    ``kind`` (``run(cell, devices, meter)``, ``readings(cell, devices,
    control, meter)``)."""
    path = os.path.join(root, "chipbench", kind + "_cell.py")
    if not os.path.exists(path):
        raise BenchError(f"no module {path} for cells of kind {kind!r}")
    if os.path.abspath(os.path.dirname(path)) == HERE:
        return importlib.import_module("chipbench." + kind + "_cell")
    # another checkout's file: loaded under a name of its own
    return _load(path, "chipbench_cell_" + kind)


def _load(path: str, name: str):
    """The module in the file ``path``, run under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # where dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


def cell_devices(cell, rehearse: bool):
    """The devices a cell runs on: as many TPU chips as it asks for, or,
    rehearsing, as many of whatever devices JAX has."""
    import jax

    chips = cell.workload["chips"]
    if not rehearse:
        return require_tpu(chips)
    devices = jax.devices()[:chips]
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} devices; JAX found "
                         f"{len(devices)}")
    return devices


def read_metric(root: str, name: str, ctx: dict):
    """Run ``chipbench/metrics/<name>.py``'s ``read(ctx)``; None where
    it found nothing to read."""
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no reader {path} for metric {name!r}")
    return _load(path, "chipbench_metric_" + name.replace(".", "_")
                 .replace("-", "_")).read(ctx)


def judge(checks: Dict[str, tuple]) -> bool:
    """Every number compared is finite and within its limit."""
    return all(v is not None and lim is not None and math.isfinite(v)
               and v <= lim for v, lim in checks.values())


def report_checks(checks: Dict[str, tuple]) -> dict:
    """Print each number compared beside its limit as the last lines on
    standard error; returns them for the result line."""
    out = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, d in out.items():
        print(f"check {k}: {d['value']} limit {d['limit']}",
              file=sys.stderr, flush=True)
    return out
