"""From a profiler trace to numbers.

A trace is reduced to a small plain structure first,

    {"planes": [{"name": ..., "lines": [{"name": ...,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

(``load_xplane`` makes it from the ``.xplane.pb`` the JAX profiler
writes, with nothing but JAX), and every number is computed from that
structure, so the arithmetic can be checked on a small recorded trace
(``tests/perfbench/fixtures/``) without a chip.

On a TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops``
holds one event for each operation that ran on the chip and ``XLA
Modules`` one for each execution of a compiled program, under the
program's name (``jit_<function>(<fingerprint>)``).  The harness's own
spans (``bench.*``) are host events on the same clock.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_PREFIX = "bench."


def load_xplane(path: str) -> Dict:
    """The chips' operation and program lines, and the harness's own
    host spans; everything else in the file is dropped."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(HOST_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """What ``busy`` (sorted, disjoint) leaves uncovered in [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


# ---------------------------------------------------------------------
# reading the structure
# ---------------------------------------------------------------------
def device_planes(trace: Dict) -> List[Dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def _events(plane: Dict, line_name: str) -> List[List]:
    return [e for ln in plane["lines"] if ln["name"] == line_name
            for e in ln["events"]]


def host_spans(trace: Dict) -> List[List]:
    return [e for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
            for ln in p["lines"] for e in ln["events"]
            if e[0].startswith(HOST_PREFIX)]


def window(trace: Dict) -> Optional[Interval]:
    """The measured window: the harness's ``bench.window`` span."""
    for name, start, dur in host_spans(trace):
        if name == WINDOW:
            return (start, start + dur)
    return None


def busy_seconds(trace: Dict) -> Optional[Dict[str, float]]:
    """``{"busy_s", "window_s"}``: the seconds in which an operation
    ran on a chip inside the window, averaged over the chips; None
    where the trace holds no chip or no window."""
    win, planes = window(trace), device_planes(trace)
    if win is None or not planes:
        return None
    busy = [total(_busy(p, win)) for p in planes]
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (win[1] - win[0]) / 1e9}


def _busy(plane: Dict, win: Interval) -> List[Interval]:
    return union(clip(((s, s + d) for _, s, d in _events(plane, OPS_LINE)),
                      *win))


def module_seconds(trace: Dict, pattern: str) -> Optional[Dict]:
    """Device time of the compiled programs whose name matches the
    regular expression, inside the window, on the first chip:
    ``{"seconds", "runs"}``; None where nothing matched."""
    win, planes = window(trace), device_planes(trace)
    if win is None or not planes:
        return None
    rx = re.compile(pattern)
    spans = clip(((s, s + d) for n, s, d in
                  _events(planes[0], MODULES_LINE) if rx.search(n)), *win)
    if not spans:
        return None
    return {"seconds": total(spans) / 1e9, "runs": len(spans)}


_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_OPCODE = re.compile(r"^\S+ = (?:\(.*?\)|\S+) ([\w\-]+)\(")
_SHAPE = re.compile(r"\w+\[[\d,]*\]")


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO line; keep the
    instruction's name, its opcode, the largest array it writes and
    the computation it calls."""
    head = name.split(" = ", 1)[0]
    if head == name:
        return name[:96]
    op, calls = _OPCODE.search(name), _CALLS.search(name)
    parts = [head]
    if op:
        written = _SHAPE.findall(name[:op.start(1)])
        parts += [op.group(1)] + ([max(written, key=_elements)]
                                  if written else [])
    if calls:
        parts.append(calls.group(1))
    return " ".join(parts)[:96]


def _elements(shape: str) -> int:
    n = 1
    for d in shape[shape.index("[") + 1:-1].split(","):
        n *= int(d) if d else 1
    return n


def top_ops(trace: Dict, n: int = 10) -> List[List]:
    """The operations that took most device time on the first chip
    inside the window: ``[[name, seconds], ...]``."""
    win, planes = window(trace), device_planes(trace)
    if win is None or not planes:
        return []
    by_name: Dict[str, float] = {}
    for name, s, d in _events(planes[0], OPS_LINE):
        a, b = max(s, win[0]), min(s + d, win[1])
        if b > a:
            name = short_name(name)
            by_name[name] = by_name.get(name, 0.0) + (b - a)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(trace: Dict, n: int = 10) -> List[List]:
    """Idle time of the first chip inside the window, by what the host
    was doing: each gap goes to the harness span that covers most of it
    (``(none)`` where no span does): ``[[span, seconds], ...]``."""
    win, planes = window(trace), device_planes(trace)
    if win is None or not planes:
        return []
    spans = [(name, s, s + d) for name, s, d in host_spans(trace)
             if name != WINDOW]
    spans.sort(key=lambda t: t[1])
    by_name: Dict[str, float] = {}
    lo = 0
    for a, b in gaps(_busy(planes[0], win), *win):
        while lo < len(spans) and spans[lo][2] <= a:
            lo += 1
        cover: Dict[str, float] = {}
        i = lo
        while i < len(spans) and spans[i][1] < b:
            name, s, e = spans[i]
            part = min(e, b) - max(s, a)
            if part > 0:
                cover[name] = cover.get(name, 0.0) + part
            i += 1
        best = max(cover, key=cover.get) if cover else "(none)"
        by_name[best] = by_name.get(best, 0.0) + (b - a)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]
