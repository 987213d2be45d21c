"""What the program itself says about a traced window: the scopes its
device operations were traced under, and its own host spans.

The trace as ``trace_reduce`` keeps it names an operation by its HLO
line and nothing else.  The program names its work where it happens
(``jax.named_scope``: the MXNet operator, the gluon block, the
transformer's ``attn`` / ``mlp`` / ..., ``optimizer``, ``cast``) and
hands out the map from instruction to scope
(``mxnet_tpu.traceview.program_scopes``); it keeps its host spans
(``mx.step``, ``mx.step.feed``, ``mx.step.launch``, ``mx.compile``,
``mx.tick``, ``mx.prefill``) in a ring on ``time.perf_counter()``
(``mxnet_tpu.profiler.spans_between``), the clock of the harness's own
``spans.log``.  The readers under ``metrics/`` call the functions here.

A program without the map or the ring (a commit before they existed)
gives None from every function, and so does a trace with no chip; the
result line then leaves the metric out.  Everything is computed once a
run and kept in ``ctx``.
"""
from __future__ import annotations

import bisect
import heapq
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from . import trace_reduce as tr

#: a class with a metric of its own, by the scope that puts work there
#: (an operator's scope is its name in the registry: ``elemwise_add``,
#: ``broadcast_add`` and ``+`` are all ``_binary_add`` there)
CLASS_OF = {"attn": "attn", "attn_proj": "attn_proj", "mlp": "mlp",
            "head_loss": "head_loss", "Convolution": "conv",
            "BatchNorm": "bn_act", "Activation": "bn_act",
            "elemwise_add": "bn_act", "_binary_add": "bn_act",
            "Pooling": "bn_act", "optimizer": "optimizer"}
#: vocabulary with no metric of its own (besides every other operator)
OTHER = re.compile(r"^(embed|norm|cast|layer\d\d|mxbkt\d{3})$")
CLASSES = ("attn", "attn_proj", "mlp", "head_loss", "conv", "bn_act",
           "optimizer", "other", "unscoped")

REPORT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".perfbench_out", "program_trace.json")
_KEY = "_program_trace"


def _once(ctx: Dict, name: str, make):
    kept = ctx.setdefault(_KEY, {})
    if name not in kept:
        kept[name] = make()
    return kept[name]


# ---------------------------------------------------------------------
# what the program hands out
# ---------------------------------------------------------------------
def program_maps(ctx: Dict) -> Optional[Dict[str, Dict[str, str]]]:
    """``{program: {instruction: op_name}}``; None where the program
    has no such map."""
    def make():
        try:
            from mxnet_tpu.traceview import program_scopes
        except ImportError:
            return None
        return program_scopes() or None
    return _once(ctx, "maps", make)


def operators() -> frozenset:
    from mxnet_tpu.ops import registry
    return frozenset(registry.list_ops(include_aliases=True))


def scope_class(op_name: str, ops: frozenset) -> str:
    """The class of the LAST vocabulary name on the path, forward,
    ``jvp(...)`` and ``transpose(...)`` alike."""
    from mxnet_tpu.traceview import scope_path

    for part in reversed(scope_path(op_name)):
        if part in CLASS_OF:
            return CLASS_OF[part]
        if OTHER.match(part) or part in ops:
            return "other"
    return "unscoped"


def window_on_host(ctx: Dict) -> Optional[Tuple[float, float]]:
    """The measured window on ``perf_counter()``."""
    for name, t0, t1 in ctx["spans"].log:
        if name == tr.WINDOW:
            return t0, t1
    return None


def ring_spans(ctx: Dict) -> Optional[List]:
    """The program's spans that overlap the window (``name``, ``t0``,
    ``t1``, ``thread``, ``depth``, ``args``); None where the program
    keeps no ring."""
    def make():
        win = window_on_host(ctx)
        try:
            from mxnet_tpu.profiler import spans_between
        except ImportError:
            return None
        return None if win is None else spans_between(*win)
    return _once(ctx, "ring", make)


def mean_span_ms(ctx: Dict, name: str) -> Optional[float]:
    """Mean length of the program's spans of that name that start
    inside the window; None on a trace with no chip."""
    spans = spans_in_window(ctx, name)
    if not spans:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(spans)


def spans_in_window(ctx: Dict, name: str) -> Optional[List]:
    """The program's spans of that name that start inside the window;
    None on a trace with no chip or for a program with no ring."""
    spans, win = ring_spans(ctx), window_on_host(ctx)
    if ctx["busy"] is None or spans is None:
        return None
    return [s for s in spans if s.name == name and win[0] <= s.t0 <= win[1]]


def slot_occupancy_pct(spans: Iterable, lo: float, hi: float
                       ) -> Optional[float]:
    """Share of the decode slots that held a live sequence, weighted by
    tick time, over the engine's ``mx.tick`` spans that start in
    ``[lo, hi]`` (``perf_counter()`` seconds); None where none does."""
    ticks = [s for s in spans if s.name == "mx.tick" and lo <= s.t0 <= hi]
    full = sum((s.t1 - s.t0) * s.args["slots"] for s in ticks)
    if not full:
        return None
    return 100.0 * sum((s.t1 - s.t0) * s.args["live"] for s in ticks) / full


def count_spans(ctx: Dict, name: str) -> Optional[int]:
    spans = spans_in_window(ctx, name)
    return None if spans is None else len(spans)


# ---------------------------------------------------------------------
# the two clocks
# ---------------------------------------------------------------------
def clock_offset(ctx: Dict) -> Optional[Dict[str, float]]:
    """The profiler's clock minus ``perf_counter()``, in ns: each
    ``bench.window`` and ``bench.step`` is in the trace (its annotation)
    and in ``spans.log`` (the harness's own readings around it).
    ``offset_ns`` is the window's; ``scatter_ns`` is how far the
    ``bench.step`` pairs lie apart (largest minus smallest)."""
    def make():
        if ctx["trace"] is None:
            return None
        traced: Dict[str, List[float]] = {}
        for name, start, _ in sorted(tr.host_spans(ctx["trace"]),
                                     key=lambda e: e[1]):
            traced.setdefault(name, []).append(start)
        logged: Dict[str, List[float]] = {}
        for name, t0, _ in sorted(ctx["spans"].log, key=lambda e: e[1]):
            logged.setdefault(name, []).append(t0 * 1e9)
        if len(traced.get(tr.WINDOW, ())) != 1 \
                or len(logged.get(tr.WINDOW, ())) != 1:
            return None
        offset = traced[tr.WINDOW][0] - logged[tr.WINDOW][0]
        a, b = traced.get("bench.step", []), logged.get("bench.step", [])
        pairs = [x - y for x, y in zip(a, b)] if len(a) == len(b) else []
        return {"offset_ns": offset, "pairs": len(pairs),
                "scatter_ns": max(pairs) - min(pairs) if pairs else 0.0}
    return _once(ctx, "offset", make)


# ---------------------------------------------------------------------
# device time by scope
# ---------------------------------------------------------------------
def innermost(events: Iterable[Tuple[float, float, str]]
              ) -> Dict[str, float]:
    """``{label: time}`` where every instant covered by an event goes
    to ONE label: that of the latest-started event covering it (the
    innermost of a ``while`` and its children; the later of two that
    overlap).  The times sum to the union of the events."""
    events = sorted(e for e in events if e[1] > e[0])
    out: Dict[str, float] = {}
    heap: List[Tuple[float, float, str]] = []   # (-start, end, label)
    i, t = 0, 0.0
    while i < len(events) or heap:
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if not heap:
            if i == len(events):
                break
            t = max(t, events[i][0])
        else:
            until = min(heap[0][1], events[i][0]) if i < len(events) \
                else heap[0][1]
            if until > t:
                out[heap[0][2]] = out.get(heap[0][2], 0.0) + until - t
                t = until
                continue
        while i < len(events) and events[i][0] <= t:
            start, end, label = events[i]
            heapq.heappush(heap, (-start, end, label))
            i += 1
    return out


def instruction(hlo_line: str) -> str:
    """``fusion.12`` from the HLO line a trace names an operation by."""
    return hlo_line.split(" = ", 1)[0].lstrip("%")


def _labelled(ctx: Dict, label_of) -> Optional[List]:
    """The first chip's operations inside the window as ``(start, end,
    label_of(program, HLO line))``."""
    trace = ctx["trace"]
    if ctx["busy"] is None or trace is None:
        return None
    win, plane = tr.window(trace), tr.device_planes(trace)[0]
    modules = sorted((s, s + d, n.split("(", 1)[0]) for n, s, d in
                     tr._events(plane, tr.MODULES_LINE))
    starts = [m[0] for m in modules]
    out = []
    for name, s, d in tr._events(plane, tr.OPS_LINE):
        a, b = max(s, win[0]), min(s + d, win[1])
        if b <= a:
            continue
        at = bisect.bisect_right(starts, s) - 1
        program = modules[at][2] if at >= 0 and s < modules[at][1] else ""
        out.append((a, b, label_of(program, name)))
    return out


def scope_ms(ctx: Dict) -> Optional[Dict[str, float]]:
    """Device milliseconds a finished step by class (``CLASSES``); the
    classes sum to the device-busy time a step.  An operation belongs
    to the program whose ``XLA Modules`` event contains its start, its
    scope comes from that program's map, and one outside every mapped
    program is ``unscoped``.  None where the program has no map or the
    trace no chip."""
    def make():
        maps = program_maps(ctx)
        if maps is None or ctx["busy"] is None:
            return None
        ops, classes = operators(), {}

        def class_of(program, hlo_line):
            key = (program, instruction(hlo_line))
            if key not in classes:
                classes[key] = scope_class(
                    maps.get(program, {}).get(key[1], ""), ops)
            return classes[key]

        ns = innermost(_labelled(ctx, class_of))
        steps = ctx["counters"]["steps"]
        table = {c: ns.get(c, 0.0) / 1e6 / steps for c in CLASSES}
        _write_report(ctx, table, maps, class_of)
        return table
    return _once(ctx, "scope_ms", make)


def class_ms(ctx: Dict, name: str) -> Optional[float]:
    table = scope_ms(ctx)
    return None if table is None else table[name]


# ---------------------------------------------------------------------
# idle time by what the program was doing
# ---------------------------------------------------------------------
def self_intervals(spans: List) -> List[Tuple[str, float, float]]:
    """Each span's own time: its interval less what its children (the
    spans one deeper on its thread, inside it) cover."""
    out = []
    by_thread: Dict[int, List] = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    for same in by_thread.values():
        same.sort(key=lambda s: (s.t0, s.depth))
        for s in same:
            kids = tr.union((k.t0, k.t1) for k in same
                            if k.depth == s.depth + 1
                            and s.t0 <= k.t0 and k.t1 <= s.t1)
            out += [(s.name, a, b) for a, b in tr.gaps(kids, s.t0, s.t1)]
    return out


def idle_by_program_span(ctx: Dict, n: int = 10) -> Optional[List[List]]:
    """``trace_reduce.idle_gaps``' arithmetic over the program's own
    spans: each idle gap of the first chip goes to the span whose own
    time covers most of it, ``(outside)`` where none does:
    ``[[span, seconds], ...]``."""
    spans, offset = ring_spans(ctx), clock_offset(ctx)
    if ctx["busy"] is None or spans is None or offset is None:
        return None
    trace = ctx["trace"]
    win, plane = tr.window(trace), tr.device_planes(trace)[0]
    own = sorted((a * 1e9 + offset["offset_ns"],
                  b * 1e9 + offset["offset_ns"], name)
                 for name, a, b in self_intervals(spans))
    by_name: Dict[str, float] = {}
    lo = 0
    for a, b in tr.gaps(tr._busy(plane, win), *win):
        while lo < len(own) and own[lo][1] <= a:
            lo += 1
        cover: Dict[str, float] = {}
        for s, e, name in own[lo:]:
            if s >= b:
                break
            part = min(e, b) - max(s, a)
            if part > 0:
                cover[name] = cover.get(name, 0.0) + part
        best = max(cover, key=cover.get) if cover else "(outside)"
        by_name[best] = by_name.get(best, 0.0) + (b - a)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def _write_report(ctx: Dict, table: Dict[str, float], maps: Dict,
                  class_of) -> None:
    """The tables of a traced run that are no metric (``PERF.md`` §5),
    beside the trace: ``.perfbench_out/program_trace.json``."""
    def unscoped(program, hlo_line):
        if class_of(program, hlo_line) != "unscoped":
            return ""
        scope = maps.get(program, {}).get(instruction(hlo_line), "")
        return "%s | %s" % (tr.short_name(hlo_line), scope or program)

    steps = ctx["counters"]["steps"]
    largest = innermost(_labelled(ctx, unscoped))
    largest.pop("", None)
    report = {
        "cell": ctx["cell"]["name"], "steps": steps, "scope_ms": table,
        "busy_ms_a_step": 1e3 * ctx["busy"]["busy_s"] / steps,
        "clock_offset": clock_offset(ctx),
        "idle_by_program_span": idle_by_program_span(ctx),
        "largest_unscoped_ms": [
            [k, v / 1e6 / steps] for k, v in
            sorted(largest.items(), key=lambda kv: -kv[1])[:10]],
    }
    os.makedirs(os.path.dirname(REPORT), exist_ok=True)
    with open(REPORT, "w") as f:
        json.dump(report, f, indent=1)
