"""A served chip's idle time, put down to the serving engine's phases.

The engine's thread records its host time in the program's ring
(``mxnet_tpu.profiler``) by phase, a few records an engine step whatever
the number of riders: ``mx.serve.loop`` (one iteration of the server's
generation worker that did work), inside it ``mx.engine.prepare`` (the
host work before a compiled call), the call's own ``mx.tick`` or
``mx.prefill`` (the dispatch), inside that ``mx.tick.readback`` or
``mx.prefill.readback`` (the wait for the run, the logits to the host
and their argmax), and ``mx.engine.stream`` (the tokens to their callers
and the retirements); ``mx.serve.idle`` covers each run of iterations
that found no work.

Every instant of the first chip's idle time inside the window goes to
the phase whose OWN time (a record's interval less what the records
inside it cover) covers it: ``no work`` under ``mx.serve.idle`` and
``(outside)`` where no record does.  Instants, not whole gaps: a gap
between two decode runs spans the readback's tail, the stream, the
loop, the next preparation and the dispatch, and given whole to the
largest of them it would leave every short phase at 0.  Own time is
found in one sorted pass per thread: a served window holds some 20,000
records, too many to compare each with every other.  A metric is the
seconds given to its phase, in milliseconds over the ``mx.tick``
records that start inside the window.

Two clocks are placed on one.  The ring goes onto the trace's host clock
by ``program_trace.clock_offset`` (the harness's own ``bench.window``,
read on both: a few microseconds).  The chip's operations are on that
clock only to a millisecond or so, which is the whole of a phase here:
in a traced run of ``sarvam_serve_reason`` 99 % of the decode runs
started up to 0.89 ms (0.41 at the median) BEFORE the ``mx.tick`` that
launched them.  The chip's lag is bounded by what causality allows,
each decode run (``jit_decode_fn``) paired with the ``mx.tick`` whose
readback is the first to end after the run does:

- at LEAST the lag that puts every run's start at or after its tick's
  start (and never below 0: the trace's clocks are taken as they are
  unless a run says otherwise);
- at MOST the lag under which ``INSIDE`` of the runs still end before
  their ``mx.tick.readback`` does (the logits reach the host only after
  the run).

The metrics read the least lag, the split the trace's clocks allow
with the smallest correction; the report gives the split at the most
lag beside it.  The least placing is then checked against the ends:
where under ``INSIDE`` of the runs lie inside their tick's start and
their readback's end, or the least lag passes ``MAX_LAG_NS``, every
metric reads None.  A shift of the ring moves the least lag by as much
and leaves the split as it was, until that lag reaches 0 or
``MAX_LAG_NS``: shifted later than the bound, None; earlier than 0, the
runs move into their dispatch, and once they end after their readback
does, None.  None too for a program without these records (the ring holds no
``mx.engine.prepare``) and for a trace with no chip.

A traced run writes ``.perfbench_out/engine_idle.json`` beside the
trace: both lags, the share found inside, and each phase's seconds at
both, ``no work`` and ``(outside)``, which sum to the device's idle
seconds in the window.
"""
from __future__ import annotations

import bisect
import json
import os
from typing import Dict, List, Optional, Tuple

from . import program_trace as pt
from . import program_trace_serve as pts
from . import trace_reduce as tr

#: the phase each record's own time belongs to
PHASE = {"mx.serve.loop": "loop", "mx.engine.prepare": "prepare",
         "mx.tick": "launch", "mx.prefill": "launch",
         "mx.tick.readback": "readback", "mx.prefill.readback": "readback",
         "mx.engine.stream": "stream", "mx.serve.idle": "no work"}
PHASES = ("prepare", "launch", "readback", "stream", "loop")
#: the least share of the window's decode runs that must lie inside
#: their tick's start and their readback's end for any metric to read
INSIDE = 0.99
#: the largest lag of the chip's clock behind the ring's that is taken
#: for the profiler's (0.89 ms seen); more, and the ring is misplaced
MAX_LAG_NS = 5e6

REPORT = os.path.join(os.path.dirname(pt.REPORT), "engine_idle.json")


def own_time(spans) -> List[Tuple[float, float, str]]:
    """``(start, end, phase)`` pieces of every phase record's own time:
    its interval less the records that lie inside it on its thread."""
    out: List[Tuple[float, float, str]] = []
    by_thread: Dict[int, List] = {}
    for s in spans:
        if s.name in PHASE:
            by_thread.setdefault(s.thread, []).append(s)
    for same in by_thread.values():
        same.sort(key=lambda s: (s.t0, s.depth))
        open_: List[List] = []      # [phase, end, where its own time resumes]
        for s in same:
            while open_ and open_[-1][1] <= s.t0:
                phase, end, at = open_.pop()
                if end > at:
                    out.append((at, end, phase))
            if open_:
                parent = open_[-1]
                if s.t0 > parent[2]:
                    out.append((parent[2], s.t0, parent[0]))
                parent[2] = max(parent[2], s.t1)
            open_.append([PHASE[s.name], s.t1, s.t0])
        while open_:
            phase, end, at = open_.pop()
            if end > at:
                out.append((at, end, phase))
    return out


def calls(spans) -> List[Tuple[float, float]]:
    """``(start, readback end)`` of every ``mx.tick`` with its
    ``mx.tick.readback`` inside it (a failed call has none), sorted."""
    ticks = sorted((s.t0, s.t1) for s in spans if s.name == "mx.tick")
    backs = sorted(s.t1 for s in spans if s.name == "mx.tick.readback")
    out, at = [], 0
    for a, b in ticks:
        at = bisect.bisect_left(backs, a, at)
        if at < len(backs) and backs[at] <= b:
            out.append((a, backs[at]))
    return out


def paired(runs: List[Tuple[float, float]],
           called: List[Tuple[float, float]], lag: float = 0.0):
    """``(run, call)`` for each run, ``lag`` later, that a call read: the
    first call whose readback ends at or after the run does (both
    sorted, on one clock).  Not the call that starts nearest: a
    dispatch can hold the host for longer than a tick."""
    backs = [c[1] for c in called]
    for s, e in runs:
        at = bisect.bisect_left(backs, e + lag)
        if at < len(called):
            yield (s + lag, e + lag), called[at]


def lag_bounds(runs: List[Tuple[float, float]],
               called: List[Tuple[float, float]]
               ) -> Tuple[float, float]:
    """The least and the most lag of the chip's clock behind the
    calls' (see the module's text); none at all where no call read a
    run."""
    lead, tail = [], []
    for (s, e), (a, back) in paired(runs, called):
        lead.append(a - s)
        tail.append(back - e)
    if not lead:
        return 0.0, 0.0
    tail.sort()
    return max(0.0, max(lead)), tail[int((1 - INSIDE) * len(tail))]


def inside_share(runs: List[Tuple[float, float]],
                 called: List[Tuple[float, float]], lag: float) -> float:
    """Share of the runs, ``lag`` later, that start at or after the
    start of the call that read them and end by its readback's end."""
    inside = sum(s >= a for (s, _), (a, _) in paired(runs, called, lag))
    return inside / len(runs)


def split(idle: List[Tuple[float, float]],
          own: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of the ``idle`` intervals under each phase's ``own``
    pieces (both sorted, ns on one clock); ``(outside)`` under none."""
    seconds = dict.fromkeys(PHASES + ("no work", "(outside)"), 0.0)
    lo = 0
    for a, b in idle:
        while lo < len(own) and own[lo][1] <= a:
            lo += 1
        left, i = b - a, lo
        while i < len(own) and own[i][0] < b:
            s, e, phase = own[i]
            part = min(e, b) - max(s, a)
            if part > 0:
                seconds[phase] += part / 1e9
                left -= part
            i += 1
        seconds["(outside)"] += max(left, 0.0) / 1e9
    return seconds


def engine_idle(ctx: Dict) -> Optional[Dict]:
    """The report (see the module's text), with ``idle_ms``: each
    phase's milliseconds a decode tick at the least lag, or None where
    the check failed; None where there is nothing to read."""
    def make():
        spans, offset = pt.ring_spans(ctx), pt.clock_offset(ctx)
        trace = ctx["trace"]
        if ctx["busy"] is None or spans is None or offset is None \
                or not any(s.name == "mx.engine.prepare" for s in spans):
            return None
        win, plane = tr.window(trace), tr.device_planes(trace)[0]
        runs = sorted((s, s + d) for n, s, d in
                      tr._events(plane, tr.MODULES_LINE)
                      if pts.DECODE.search(n) and win[0] <= s < win[1])
        shift = offset["offset_ns"]                 # the ring on the trace
        called = [(a * 1e9 + shift, b * 1e9 + shift)
                  for a, b in calls(spans)]
        n_ticks = len(pt.spans_in_window(ctx, "mx.tick"))
        if not runs or not called or not n_ticks:
            return None
        least, most = lag_bounds(runs, called)
        share = inside_share(runs, called, least)
        own = sorted((a * 1e9 + shift, b * 1e9 + shift, phase)
                     for a, b, phase in own_time(spans))
        idle = tr.gaps(tr._busy(plane, win), *win)
        # the chip ``lag`` later is the ring ``lag`` earlier
        seconds = {lag: split(idle, [(a - lag, b - lag, p)
                                     for a, b, p in own])
                   for lag in (least, most)}
        ok = share >= INSIDE and least <= MAX_LAG_NS
        report = {
            "cell": ctx["cell"]["name"], "clock_offset": offset,
            "lag_ns": {"least": least, "most": most},
            "decode_runs": len(runs), "inside_share": share,
            "ticks": n_ticks,
            "device_idle_s": ctx["busy"]["window_s"] - ctx["busy"]["busy_s"],
            "seconds": seconds[least], "sum_s": sum(seconds[least].values()),
            "seconds_at_most_lag": seconds[most],
            "idle_ms": {p: 1e3 * seconds[least][p] / n_ticks
                        for p in PHASES} if ok else None,
            "idle_ms_at_most_lag": {p: 1e3 * seconds[most][p] / n_ticks
                                    for p in PHASES} if ok else None}
        os.makedirs(os.path.dirname(REPORT), exist_ok=True)
        with open(REPORT, "w") as f:
            json.dump(report, f, indent=1)
        return report
    return pt._once(ctx, "engine_idle", make)


def idle_ms(ctx: Dict, phase: str) -> Optional[float]:
    report = engine_idle(ctx)
    if report is None or report["idle_ms"] is None:
        return None
    return report["idle_ms"][phase]
