"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

Device planes are ``/device:TPU:<n>``. Their ``XLA Ops`` line holds one
event per operation, named by its HLO text (``%lora_matmul.14 = ...``);
a loop or call is an event that encloses the events of its body, so
operation time is counted as self time (an event's duration less that of
the events directly inside it). The ``XLA Modules`` line holds one event
per program run, named ``jit_<function>(<fingerprint>)``. Host planes
carry the benchmark's own ``TraceAnnotation`` spans, whose names start
with ``bench.``; the one named ``bench.window`` bounds the window.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW = "bench.window"

_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s*=|$)")
_MODULE_NAME = re.compile(r"^(.*?)(?:\(\d+\))?$")


def op_name(event_name: str) -> str:
    """``'%lora_matmul.14 = bf16[...] custom-call(...)'`` -> ``'lora_matmul'``."""
    m = _OP_NAME.match(event_name.strip())
    return m.group(1) if m else event_name.split()[0]


def module_name(event_name: str) -> str:
    """``'jit_round_fn(1582...)'`` -> ``'jit_round_fn'``."""
    return _MODULE_NAME.match(event_name).group(1)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self time by operation name of properly nested ``(start, end,
    name)`` events."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []            # [end, name, own duration, children]

    def close(frame):
        out[frame[1]] += max(frame[2] - frame[3], 0.0)
        if stack:
            stack[-1][3] += frame[2]

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, e - s, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(path: str, window: str = WINDOW) -> dict:
    """The reduced trace of the span named ``window``. Times in seconds;
    ``busy_s`` and the per-operation, per-kernel and per-program times
    are averaged over the device planes that ran anything in it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
    windows = [sp for sp in spans if sp[2] == window]
    if not windows:
        raise ValueError(f"{path}: no {window!r} span in the trace")
    lo, hi = windows[0][0], windows[0][1]

    per_dev, custom = [], set()

    def clipped(ev, name):
        s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
        return (s, e, name) if e > s else None

    for plane in devices:
        lines, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = []
                for ev in line.events:
                    x = clipped(ev, op_name(ev.name))
                    if x:
                        ops.append(x)
                        if "tpu_custom_call" in ev.name:
                            custom.add(x[2])
                lines.append(ops)
            elif line.name == "XLA Modules":
                mods += [x for x in (clipped(ev, module_name(ev.name))
                                     for ev in line.events) if x]
        if any(lines):
            per_dev.append((lines, mods))
    if not devices:
        # no accelerator (the CPU rehearsal): the host threads' XLA
        # operations, which carry their op and program as stats, stand
        # in for one device
        lines, mods = [], []
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                ops = []
                for ev in line.events:
                    st = dict(ev.stats)
                    if "hlo_op" in st and "hlo_module" in st:
                        x = clipped(ev, op_name(st["hlo_op"]))
                        if x:
                            ops.append(x)
                            mods.append((x[0], x[1], st["hlo_module"]))
                if ops:
                    lines.append(ops)
        if lines:
            per_dev.append((lines, mods))
    if not per_dev:
        raise ValueError(f"{path}: no device operation in the window")

    n = len(per_dev)
    op_time: Dict[str, float] = collections.defaultdict(float)
    op_runs: Dict[str, int] = collections.defaultdict(int)
    mod_time: Dict[str, float] = collections.defaultdict(float)
    mod_count: Dict[str, int] = collections.defaultdict(int)
    busy_ns = 0.0
    gaps: List[Tuple[float, float]] = []
    for i, (lines, mods) in enumerate(per_dev):
        busy = _union([(s, e) for ops in lines for s, e, _ in ops])
        busy_ns += sum(e - s for s, e in busy)
        for ops in lines:
            for k, v in _self_times(ops).items():
                op_time[k] += v / n
            for _, _, k in ops:
                op_runs[k] += 1
        for s, e, name in mods:
            mod_time[name] += (e - s) / n
            mod_count[name] += 1
        if i == 0:                       # gaps of the first device
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]

    inner = [sp for sp in spans if sp[2] != window]
    gap_by_span = _idle_by_span(gaps, inner)

    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_ns / n * ns,
        "n_devices": n,
        "op_s": {k: v * ns for k, v in op_time.items()},
        "op_runs": {k: v // n for k, v in op_runs.items()},
        "module_s": {k: v * ns for k, v in mod_time.items()},
        "module_runs": {k: v // n for k, v in mod_count.items()},
        "custom_calls": sorted(custom),
        "idle_by_span_s": dict(gap_by_span),
        "spans": [(s * ns, e * ns, name) for s, e, name in spans],
    }


def _idle_by_span(gaps, spans) -> Dict[str, float]:
    """Seconds of device idle time by what the host was doing: each gap
    goes to the innermost benchmark span that covers most of it, or to
    ``bench.other`` where none covers half of it."""
    import numpy as np

    out: Dict[str, float] = collections.defaultdict(float)
    if not gaps:
        return {}
    if not spans:
        return {"bench.other": sum(e - s for s, e in gaps) * 1e-9}
    ss = np.array([s for s, _, _ in spans])
    se = np.array([e for _, e, _ in spans])
    names = [n for _, _, n in spans]
    for i in range(0, len(gaps), 512):
        g = np.array(gaps[i:i + 512])
        cover = np.minimum(g[:, 1:2], se[None]) - np.maximum(g[:, :1], ss[None])
        length = g[:, 1] - g[:, 0]
        ok = cover >= 0.5 * length[:, None]
        # most cover first, then the shortest span
        score = np.where(ok, cover - 1e-6 * (se - ss)[None], -np.inf)
        best = np.argmax(score, axis=1)
        for j, b in enumerate(best):
            name = names[b] if ok[j, b] else "bench.other"
            out[name] += float(length[j]) * 1e-9
    return dict(out)


def kernel_seconds(reduced: dict, kernel: str) -> Optional[float]:
    """Device self time of the custom calls (Pallas kernels) whose name
    contains ``kernel``, or None where none ran."""
    t = sum(reduced["op_s"][k] for k in reduced["custom_calls"]
            if kernel in k)
    return t if t > 0 else None


def kernel_runs(reduced: dict, kernel: str) -> int:
    """How many times the custom calls whose name contains ``kernel``
    ran in the window (per device)."""
    return sum(reduced["op_runs"].get(k, 0) for k in reduced["custom_calls"]
               if kernel in k)


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_by_span_s"].items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
