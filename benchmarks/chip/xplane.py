"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-program
device times, top device operations, and idle gaps named by host spans.

Device planes are ``/device:TPU:<n>``: the ``XLA Ops`` line holds every
operation the chip ran, the ``XLA Modules`` line every program execution,
named ``jit_<function>(<fingerprint>)``. Host spans are the benchmark's own
``TraceAnnotation`` events on the host plane. Host and device events share
one clock in the file.
"""
from __future__ import annotations

import bisect
import collections
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # (start, end) in seconds

WINDOW_SPAN = "bench.window"


@dataclass
class Reduction:
    window: Interval
    busy_s: float  # union of device-op intervals inside the window, mean over chips
    chips: int
    modules: Dict[str, List[float]]  # program name -> device seconds per execution, in order
    ops: List[Tuple[str, float]]  # "program/op" -> device seconds, most first
    gaps: List[Tuple[str, float]]  # host span -> idle seconds, most first

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def complement(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def module_name(event_name: str) -> str:
    return event_name.split("(")[0]


def op_name(event_name: str) -> str:
    return event_name.split(" = ")[0].lstrip("%")


def _label(spans: Dict[str, List[Interval]], t: float) -> str:
    """The innermost (shortest) host span that holds time t."""
    best, best_len = "host:outside-spans", float("inf")
    for name, ivs in spans.items():
        i = bisect.bisect_right(ivs, (t, float("inf"))) - 1
        for j in range(i, max(-1, i - 4), -1):  # spans of one name may nest a little
            a, b = ivs[j]
            if a <= t < b and b - a < best_len:
                best, best_len = name, b - a
    return best


def reduce(path: str, span_names: Sequence[str], chips: int = 1,
           min_gap_s: float = 0.0) -> Optional[Reduction]:
    """The reduction of one trace file; None where it holds no device op or
    no ``bench.window`` span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: Dict[str, List[Interval]] = collections.defaultdict(list)
    want = set(span_names) | {WINDOW_SPAN}
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in want:
                        t = ev.start_ns * 1e-9
                        spans[ev.name].append((t, t + ev.duration_ns * 1e-9))
    devices = sorted(devices, key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
    if not devices or not spans.get(WINDOW_SPAN):
        return None
    for ivs in spans.values():
        ivs.sort()
    lo, hi = spans.pop(WINDOW_SPAN)[0]
    busy_total = 0.0
    modules: Dict[str, List[float]] = collections.defaultdict(list)
    op_time: Dict[str, float] = collections.defaultdict(float)
    gap_time: Dict[str, float] = collections.defaultdict(float)
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        mods = [
            (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, module_name(ev.name))
            for ev in lines["XLA Modules"].events
        ] if "XLA Modules" in lines else []
        mods = [m for m in mods if m[1] > lo and m[0] < hi]
        for a, b, name in mods:
            if plane is devices[0]:
                modules[name].append(b - a)
        starts = [m[0] for m in mods]
        ops: List[Interval] = []
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else []:
            a = ev.start_ns * 1e-9
            b = a + ev.duration_ns * 1e-9
            if b <= lo or a >= hi:
                continue
            ops.append((a, b))
            if plane is devices[0]:
                i = bisect.bisect_right(starts, a) - 1
                prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
                op_time[f"{prog}/{op_name(ev.name)}"] += b - a
        busy = clip(union(ops), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        if plane is devices[0]:
            for a, b in complement(busy, lo, hi):
                if b - a > min_gap_s:
                    gap_time[_label(spans, (a + b) / 2)] += b - a
    return Reduction(
        window=(lo, hi),
        busy_s=busy_total / len(devices),
        chips=len(devices),
        modules=dict(modules),
        ops=sorted(op_time.items(), key=lambda kv: -kv[1]),
        gaps=sorted(gap_time.items(), key=lambda kv: -kv[1]),
    )
