"""Host spans around the calls a serving round makes, from outside.

`Spans.wrap` shadows one bound method on one instance with a wrapper that
opens a ``jax.profiler.TraceAnnotation`` of the span's name and times the
call on the host clock. No program file changes. A method that no longer
exists is noted in `missing`, and the metrics that read it stay silent.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax


class Spans:
    def __init__(self) -> None:
        self.times: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(list)
        self.missing: set = set()
        # per-call records of the work the call was asked to do, kept while
        # `recording` is on (the traced seconds)
        self.records: Dict[str, List[Any]] = collections.defaultdict(list)
        self.recording = False

    def wrap(self, obj: Any, attr: str, name: str,
             record: Optional[Callable[..., Any]] = None) -> None:
        fn = getattr(obj, attr, None)
        if fn is None:
            self.missing.add(name)
            return
        times = self.times[name]
        recs = self.records[name]

        def wrapper(*args, **kwargs):
            if record is not None and self.recording:
                recs.append(record(*args, **kwargs))
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args, **kwargs)
            times.append((t0, time.perf_counter()))
            return out

        setattr(obj, attr, wrapper)

    def total(self, name: str, lo: float = float("-inf"), hi: float = float("inf")) -> float:
        return sum(b - a for a, b in self.times.get(name, ()) if lo <= a < hi)
