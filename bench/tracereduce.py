"""Reduction of a profiler trace to the benchmark's per-layer numbers.

A trace is first flattened into a plain dict, so the arithmetic below
can be tested on a trace written by hand:

    {"host":   [(thread, name, start_ns, dur_ns), ...],
     "device": {plane: {line: [(name, start_ns, dur_ns), ...]}}}

``from_profile`` builds it from the ``.xplane.pb`` file that
``jax.profiler`` writes.  Host events are the ``TraceAnnotation`` spans
and runtime events of every host thread; device planes are the
``/device:...`` planes, whose ``XLA Ops`` line holds one event per
operation run on the chip and whose ``XLA Modules`` line holds one event
per program run.  Host and device events share one clock.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def from_profile(path: str) -> dict:
    """Flatten the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host: List[tuple] = []
    device: Dict[str, Dict[str, list]] = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((line.name, ev.name, ev.start_ns, ev.duration_ns)
                            for ev in line.events)
        elif plane.name.startswith("/device:"):
            device[plane.name] = {
                line.name: [(ev.name, ev.start_ns, ev.duration_ns)
                            for ev in line.events]
                for line in plane.lines}
    return {"host": host, "device": device}


def summary(trace: dict) -> List[str]:
    """One line per device plane and line: event count and time span."""
    out = []
    for plane, lines in sorted(trace["device"].items()):
        for line, evs in sorted(lines.items()):
            lo = min((s for _, s, _ in evs), default=0)
            hi = max((s + d for _, s, d in evs), default=0)
            out.append(f"{plane} / {line}: {len(evs)} events, "
                       f"{lo:.0f}..{hi:.0f} ns")
    threads = sorted({t for t, *_ in trace["host"]})
    out.append(f"host: {len(trace['host'])} events on threads {threads}")
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals`` (start, end)."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def device_planes(trace: dict) -> List[str]:
    """Planes of chips that ran operations (one per chip used)."""
    return sorted(p for p, lines in trace["device"].items()
                  if lines.get(OPS_LINE))


def busy(trace: dict, plane: str) -> List[Interval]:
    """Union of the intervals in which an operation ran on ``plane``."""
    return union((s, s + d) for _, s, d in trace["device"][plane][OPS_LINE])


def busy_seconds(trace: dict, window: Interval) -> Optional[float]:
    """Device busy time inside ``window`` (ns), averaged over chips."""
    planes = device_planes(trace)
    if not planes:
        return None
    return sum(length(clip(busy(trace, p), *window))
               for p in planes) / len(planes) * 1e-9


def module_op_seconds(trace: dict, prefix: str) -> Optional[float]:
    """Device seconds of the operations run inside programs whose name
    starts with ``prefix`` (such as ``jit__cells_tables_kernel``),
    summed over chips; None when no such program ran."""
    total = 0.0
    found = False
    for plane in device_planes(trace):
        lines = trace["device"][plane]
        mods = union((s, s + d) for name, s, d in lines.get(MODULES_LINE, ())
                     if name.startswith(prefix))
        if not mods:
            continue
        found = True
        ops = busy(trace, plane)
        total += sum(length(clip(ops, s, e)) for s, e in mods)
    return total * 1e-9 if found else None


def spans(trace: dict, name: str) -> List[tuple]:
    """Host events called ``name``: (thread, start_ns, end_ns)."""
    return [(t, s, s + d) for t, n, s, d in trace["host"] if n == name]


def self_seconds(trace: dict, name: str, others: Sequence[str]
                 ) -> Optional[float]:
    """Summed duration of the ``name`` spans, less the parts covered by
    spans of the ``others`` names nested in them on the same thread;
    None when there is no such span."""
    own = spans(trace, name)
    if not own:
        return None
    inner = [sp for o in others if o != name for sp in spans(trace, o)]
    total = 0.0
    for t, s, e in own:
        covered = union((max(cs, s), min(ce, e)) for ct, cs, ce in inner
                        if ct == t and cs >= s and ce <= e)
        total += (e - s) - length(covered)
    return total * 1e-9


def op_name(event_name: str) -> str:
    """An operation's HLO name from its event name, which on the TPU is
    the whole instruction (``%fusion.7 = f32[...] fusion(...), ...``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def top_ops(trace: dict, limit: int = 10) -> List[list]:
    """The device operations that took most time: [name, seconds]."""
    per: Dict[str, float] = {}
    for plane in device_planes(trace):
        for name, _, d in trace["device"][plane][OPS_LINE]:
            name = op_name(name)
            per[name] = per.get(name, 0.0) + d * 1e-9
    return [[k, v] for k, v in
            sorted(per.items(), key=lambda kv: -kv[1])[:limit]]


def idle_by_host(trace: dict, window: Interval, names: Sequence[str],
                 limit: int = 10) -> List[list]:
    """Device idle seconds inside ``window``, split by which of the host
    spans ``names`` was running (the innermost, the latest started);
    idle time under none of them is "other".  [[name, seconds]]."""
    planes = device_planes(trace)
    if not planes:
        return []
    lo, hi = window
    marks = sorted((s, e, n) for n in names for _, s, e in spans(trace, n))
    out: Dict[str, float] = {}
    for plane in planes:
        cursor = lo
        gaps = []
        for s, e in clip(busy(trace, plane), lo, hi):
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < hi:
            gaps.append((cursor, hi))
        for gs, ge in gaps:
            # cut the gap at every span boundary inside it
            cuts = sorted({gs, ge} | {x for s, e, _ in marks
                                      for x in (s, e) if gs < x < ge})
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                inside = [(s, n) for s, e, n in marks if s <= mid < e]
                label = max(inside)[1] if inside else "other"
                out[label] = out.get(label, 0.0) \
                    + (b - a) * 1e-9 / len(planes)
    return [[k, v] for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])[:limit]]
