"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace here is what ``jax.profiler`` writes: device planes whose op line
holds one event per operation that ran on the chip, and host planes whose
thread lines hold the harness's spans (``jax.profiler.TraceAnnotation``).
Every time is in seconds on the trace's one clock.

``load`` reads an ``.xplane.pb`` file into a ``Trace``; everything else works
on plain lists, so the reduction can be checked on hand-made events.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench:"   # host spans the harness emits into the trace
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
# collectives as XLA names them in the op line of a device plane
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|psum)", re.I)


@dataclass
class Event:
    name: str
    start: float      # seconds
    dur: float        # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    device_ops: Dict[int, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    def window(self) -> Optional[Tuple[float, float]]:
        """The measured window, as the harness marked it."""
        w = [s for s in self.spans if s.name == "window"]
        return (w[0].start, w[0].end) if w else None


def op_name(text: str) -> str:
    """An op event's name is its whole HLO instruction on the TPU
    (``%wsum_q8.1 = f32[...] custom-call(...)``): keep the op's own name."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OP_LINE:
                tr.device_ops.setdefault(int(m.group(1)), []).extend(
                    Event(op_name(e.name), e.start_ns * 1e-9,
                          e.duration_ns * 1e-9)
                    for e in line.events)
            elif not m:
                tr.spans.extend(
                    Event(e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                          e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for evs in tr.device_ops.values():
        evs.sort(key=lambda e: e.start)
    return tr


# --------------------------------------------------------------------------- #
# Interval arithmetic
# --------------------------------------------------------------------------- #

def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """The parts of ``events`` inside [lo, hi]."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals of possibly overlapping events."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_s(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of the device inside [lo, hi]."""
    out, t = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def kernel_s(events: Sequence[Event], pattern: str, lo: float, hi: float
             ) -> float:
    """Summed device time of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(e.dur for e in clip(events, lo, hi) if rx.search(e.name))


def collective_s(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(e.dur for e in clip(events, lo, hi) if COLLECTIVE.search(e.name))


def host_label(spans: Sequence[Event], a: float, b: float) -> str:
    """What the host was doing in [a, b]: the innermost harness span that
    covers most of it, else ``other``."""
    best, best_key = "other", (0.0, 0.0)
    for s in spans:
        if s.name == "window":
            continue
        ov = min(b, s.end) - max(a, s.start)
        # more overlap wins; at equal overlap the shorter (inner) span wins
        key = (round(ov, 9), -s.dur)
        if ov > 0 and key > best_key:
            best, best_key = s.name, key
    return best


# --------------------------------------------------------------------------- #
# The numbers a traced run reports
# --------------------------------------------------------------------------- #

def device_summary(tr: Trace, chips: Sequence[int], lo: float, hi: float,
                   top: int = 10) -> Dict:
    """busy_s (mean over ``chips``), window_s and the breakdown lists."""
    busy = [busy_s(tr.device_ops.get(c, []), lo, hi) for c in chips]
    ops0 = clip(tr.device_ops.get(chips[0], []), lo, hi)
    by_name: Dict[str, float] = {}
    for e in ops0:
        by_name[e.name] = by_name.get(e.name, 0.0) + e.dur
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    idle: Dict[str, float] = {}
    for a, b in gaps(ops0, lo, hi):
        lab = host_label(tr.spans, a, b)
        idle[lab] = idle.get(lab, 0.0) + (b - a)
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / len(busy), "window_s": hi - lo,
            "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                          "idle_gaps": [[n, s] for n, s in top_idle]}}
