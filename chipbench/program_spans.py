"""The program's own host spans in a traced window, and the device idle
time that each of them explains.

The program marks its host work with ``jax.profiler.TraceAnnotation``
spans named ``repro.*`` (``repro.round``, ``repro.stage.enter``,
``repro.devft.group``, ...), whose keyword arguments are stored as the
events' stats. They share the device trace's clock, so each gap in
which the first device ran nothing can be given to the host work that
covered it, by the rule ``trace._idle_by_span`` applies to the
benchmark's own spans: the innermost span that covers most of the gap,
or ``bench.other`` where no program span covers half of it. The window
and the device's busy time are those of ``trace.reduce``, so the idle
seconds here add up to its ``window_s - busy_s``.

A program without such spans (one older than them) yields no spans, and
the readers built on this module read nothing.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

from chipbench.trace import WINDOW, _clip, _idle_by_span, _union

PREFIX = "repro."
#: spans of stage construction (``strat.on_stage``, ``DevFTController``)
STAGE = ("repro.stage.", "repro.devft.")


def is_stage(name: str) -> bool:
    return name.startswith(STAGE)


@functools.lru_cache(maxsize=4)
def reduce(path: str, window: str = WINDOW) -> dict:
    """The program spans of the window named ``window`` and the device
    idle time they explain. Times in seconds; ``spans`` holds ``(start,
    end, name, stats)`` clipped to the window, in start order;
    ``idle_by_span_s`` gives each gap's seconds to a span name or to
    ``bench.other``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    windows = [(ev.start_ns, ev.start_ns + ev.duration_ns)
               for p in host for line in p.lines for ev in line.events
               if ev.name == window]
    if not windows:
        raise ValueError(f"{path}: no {window!r} span in the trace")
    lo, hi = windows[0]

    def clipped(ev):
        s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
        return (s, e) if e > s else None

    busy: List[Tuple[float, float]] = []
    for plane in devices:
        ops = [x for line in plane.lines if line.name == "XLA Ops"
               for x in map(clipped, line.events) if x]
        if ops:                          # the first device that ran
            busy = ops
            break
    if not devices:
        # no accelerator (the CPU rehearsal): the host threads' XLA
        # operations stand in for one device, as in ``trace.reduce``
        for p in host:
            for line in p.lines:
                for ev in line.events:
                    st = dict(ev.stats)
                    x = clipped(ev)
                    if "hlo_op" in st and "hlo_module" in st and x:
                        busy.append(x)
    if not busy:
        raise ValueError(f"{path}: no device operation in the window")
    merged = _union(busy)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]

    spans = []
    for p in host:
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    x = clipped(ev)
                    if x:
                        spans.append((*x, ev.name, dict(ev.stats)))
    spans.sort(key=lambda sp: (sp[0], -sp[1]))
    by_span = _idle_by_span(gaps, [sp[:3] for sp in spans])
    ns = 1e-9
    return {"window_s": (hi - lo) * ns,
            "idle_s": sum(e - s for s, e in gaps) * ns,
            "idle_by_span_s": by_span,
            "spans": [(s * ns, e * ns, n, st) for s, e, n, st in spans]}


def idle_share(ctx: dict, stage: bool):
    """Percent of the window in which the device was idle while the host
    was in a stage-construction span (``stage``) or in another program
    span; None where the window holds no program span."""
    r = reduce(ctx["cell"].trace_path)
    if not r["spans"]:
        return None
    idle = sum(v for k, v in r["idle_by_span_s"].items()
               if k.startswith(PREFIX) and is_stage(k) == stage)
    return 100.0 * idle / r["window_s"]


def outermost(spans, names) -> List[Tuple[float, float, str, Dict]]:
    """The spans named in ``names`` that no other of them encloses."""
    mine = [sp for sp in spans if sp[2] in names]
    return [sp for sp in mine
            if not any(o is not sp and o[0] <= sp[0] and sp[1] <= o[1]
                       for o in mine)]
