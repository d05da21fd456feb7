"""The benchmark's own tracer object, handed to the program in traced runs.

The program's tracer is duck-typed (``begin``/``end``/``span``/
``name_track``/``bump``/``totals``, truthy when on), so the benchmark
passes this one: it keeps every span on the host clock for the per-layer
readers, and mirrors each span into a ``jax.profiler.TraceAnnotation``
named ``@<cat>:<name>``, which puts it on the profiler's clock beside the
device's operations.  ``trace_reduce`` then names each idle gap of the
device by the host span it falls in.  Untraced runs pass no tracer.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax


@dataclasses.dataclass
class Span:
    name: str
    cat: str
    t0: float
    t1: float = -1.0
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    annotation: Optional[Any] = None

    @property
    def duration(self) -> float:
        return self.t1 - self.t0 if self.t1 >= 0 else 0.0


class Spans:
    """Records spans and counters; with ``mirror``, also into the
    profiler's trace."""

    def __init__(self, mirror: bool = True) -> None:
        self.mirror = mirror
        self.spans: List[Span] = []
        self.meta: Dict[str, Any] = {}
        self._totals: Dict[str, Dict[str, float]] = {}

    def __bool__(self) -> bool:
        return True

    def name_track(self, track: int, name: str) -> None:
        pass

    def begin(self, name: str, cat: str = "", track: int = 0,
              **args: Any) -> Span:
        sp = Span(name=name, cat=cat, t0=time.perf_counter(),
                  args=dict(args))
        if self.mirror:
            sp.annotation = jax.profiler.TraceAnnotation(f"@{cat}:{name}")
            sp.annotation.__enter__()
        self.spans.append(sp)
        return sp

    def end(self, span: Span) -> None:
        span.t1 = time.perf_counter()
        if span.annotation is not None:
            span.annotation.__exit__(None, None, None)
            span.annotation = None

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "", track: int = 0, **args: Any):
        sp = self.begin(name, cat, track, **args)
        try:
            yield sp
        finally:
            self.end(sp)

    def bump(self, name: str, values: Dict[str, float],
             track: int = 0) -> None:
        tot = self._totals.setdefault(name, {})
        for k, v in values.items():
            tot[str(k)] = tot.get(str(k), 0.0) + v

    def totals(self, name: str) -> Dict[str, float]:
        return dict(self._totals.get(name, {}))

    def closed(self, cat: str) -> List[Span]:
        """Closed spans of one category, in the order they began."""
        return [s for s in self.spans if s.cat == cat and s.t1 >= 0]
