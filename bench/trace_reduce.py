"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

* ``busy_s`` -- the union of the intervals in which an operation ran on
  a device, inside the traced window, averaged over the devices that
  ran anything; ``window_s`` -- the length of the traced window, the
  host span ``bench.traced_window`` that the harness opens around the
  traced part;
* ``kernels`` -- for each named kernel, the number of its events and
  their summed device time (an event matches when its name contains the
  kernel's key);
* ``top_ops`` -- the ten operation names with the most self time (time
  not covered by an operation nested inside them);
* ``idle_gaps`` -- the ten longest stretches of the window in which the
  first device ran nothing, each labelled with the innermost host span
  of the benchmark (``bench.*``) that covers most of it.

Only events on a device plane's ``XLA Ops`` line count as operations;
an operation is named by its HLO instruction name (``fusion.12``).
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.traced_window"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def union(iv: List[Interval]) -> List[Interval]:
    """Merge intervals (start, end) into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv
            if e > lo and s < hi]


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self time per name of possibly nested (start, end, name) events."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [end, name, child_time]

    def pop():
        end, name, child, dur = stack.pop()
        out[name] = out.get(name, 0.0) + dur - child
        if stack:
            stack[-1][2] += dur

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            pop()
        stack.append([e, name, 0.0, e - s])
    while stack:
        pop()
    return out


def host_spans(planes) -> List[Tuple[float, float, str]]:
    spans = []
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def device_ops(planes) -> Dict[str, List[Tuple[float, float, str]]]:
    out = {}
    for pl in planes:
        if not pl.name.startswith("/device:"):
            continue
        for ln in pl.lines:
            if ln.name == OPS_LINE:
                out[pl.name] = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 op_name(ev.name)) for ev in ln.events]
    return {k: v for k, v in out.items() if v}


def label_gap(gap: Interval, spans) -> str:
    """The innermost ``bench.*`` span covering most of ``gap``."""
    best, best_key = WINDOW_SPAN, None
    for s, e, name in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0.5 * (gap[1] - gap[0]):
            continue
        key = e - s                  # innermost: the shortest covering
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes, kernels: Dict[str, str]) -> dict:
    planes = list(planes)
    spans = host_spans(planes)
    wins = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    lo, hi = wins[0]
    devs = device_ops(planes)
    if not devs:
        raise ValueError("trace has no device operations")
    busy, first = [], None
    totals: Dict[str, float] = {}
    kern = {k: {"count": 0, "seconds": 0.0} for k in kernels}
    for name in sorted(devs):
        evs = [(max(s, lo), min(e, hi), n) for s, e, n in devs[name]
               if e > lo and s < hi]
        merged = union([(s, e) for s, e, _ in evs])
        busy.append(sum(e - s for s, e in merged))
        if first is None:
            first = merged
        for n, t in self_times(evs).items():
            totals[n] = totals.get(n, 0.0) + t
        for key, sub in kernels.items():
            for s, e, n in evs:
                if sub in n:
                    kern[key]["count"] += 1
                    kern[key]["seconds"] += (e - s) * 1e-9
    gaps, prev = [], lo
    for s, e in first:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    top = sorted(totals.items(), key=lambda kv: kv[1], reverse=True)[:10]
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "devices": len(devs),
        "kernels": kern,
        "top_ops": [[n, t * 1e-9] for n, t in top],
        "idle_gaps": [[label_gap(g, spans), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:10]],
    }


def reduce(path: Path, kernels: Dict[str, str]) -> dict:
    """Reduce the trace file at ``path``; ``kernels`` maps a kernel's
    key to the substring its events' names contain."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes, kernels)
