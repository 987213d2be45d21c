"""The harness's own spans: host-clock intervals around its calls into
the program, and the same intervals as ``jax.profiler.TraceAnnotation``
when a trace is being taken, so that idle gaps on the device can be
named by what the host was doing."""
from __future__ import annotations

import time
from typing import List, Tuple


class _Span:
    __slots__ = ("owner", "name", "t0", "note")

    def __init__(self, owner, name):
        self.owner, self.name, self.note = owner, name, None

    def __enter__(self):
        if self.owner.annotate is not None:
            self.note = self.owner.annotate(self.name)
            self.note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.note is not None:
            self.note.__exit__(*exc)
        self.owner.log.append((self.name, self.t0, t1))
        return False


class Spans:
    """``with spans("bench.step"): ...`` — kept in memory, read after
    the window."""

    def __init__(self):
        self.log: List[Tuple[str, float, float]] = []
        self.annotate = None

    def __call__(self, name: str) -> _Span:
        return _Span(self, name)

    def clear(self) -> None:
        self.log = []

    def durations(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.log if n == name]
