"""Device time of a served model's DECODE programs under the scopes its
blocks name: ``attn`` (the gather of the cached rows and the attention
over them), ``moe_route``, ``moe_expert``, ``moe_shared``.

A server compiles one decode program for each cell of its plan
(``gen_decode:<model>:v<n>:<riders>x<span>``) and all of them are called
``jit_decode_fn``, so ``program_trace``'s map by program name would hold
one cell's instructions for all.  Here each is kept apart: the trace
names every execution ``jit_decode_fn(<fingerprint>)``, one fingerprint
a cell, and an execution's operations name their instruction and its
shape (``%fusion.12 = bf16[48,1,4096]...``); the cell whose optimized
HLO holds the most of a fingerprint's (instruction, shape) pairs is its
cell, and that cell's map from instruction to scope classes its
operations.  Every instant of busy time inside a decode execution goes
to the innermost operation covering it; a name gets the time of every
operation whose scope path holds it.  None where the program records no
such steps, the trace holds no chip, or no decode program ran.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Set, Tuple

from . import program_trace as pt
from . import trace_reduce as tr

NAMES = ("attn", "moe_route", "moe_expert", "moe_shared")
DECODE = re.compile(r"decode_fn")
STEP = "gen_decode:"
# the grouped products over the held experts: the chip's compiler makes
# them custom calls of this name and, inside a block that is a ``jit``
# of its own, names them by that block alone, without ``mlp/moe_expert``
GROUPED = "ragged-dot"
# ``%fusion.12 = bf16[48,1,4096]{...`` or ``... = (bf16[48,...``
_SIGNATURE = re.compile(
    r"^(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+\(?([a-z]\w*\[[\d,]*\])")


def signature(hlo_line: str) -> Optional[Tuple[str, str]]:
    """``(instruction, first shape written)`` of an HLO line."""
    m = _SIGNATURE.match(hlo_line.strip())
    return m.groups() if m else None


def plan_cells() -> Optional[List[Tuple[Set, Dict[str, str]]]]:
    """For every decode step the program recorded: the signatures of
    its optimized HLO and its map from instruction to ``op_name``."""
    try:
        from mxnet_tpu import diagnostics
        from mxnet_tpu.traceview import scopes
    except ImportError:
        return None
    out = []
    for name, (step, specs, _) in diagnostics.recorded_steps().items():
        if not name.startswith(STEP):
            continue
        # the specs are those of the step's own compile: jax hands the
        # executable back
        text = step.lower(*specs).compile().as_text()
        marks = {s for s in map(signature, text.splitlines()) if s}
        out.append((marks, scopes.parse_hlo_scopes(text)[1]))
    return out or None


def decode_scope_ms(ctx: Dict) -> Optional[Dict[str, float]]:
    """``{name: device ms a decode execution}`` for ``NAMES``, and
    ``runs``, over the decode executions that start inside the
    window."""
    def make():
        trace = ctx["trace"]
        if ctx["busy"] is None or trace is None:
            return None
        cells = plan_cells()
        if cells is None:
            return None
        from mxnet_tpu.traceview import scope_path

        win, plane = tr.window(trace), tr.device_planes(trace)[0]
        runs = sorted((s, s + d, n) for n, s, d in
                      tr._events(plane, tr.MODULES_LINE)
                      if DECODE.search(n) and win[0] <= s < win[1])
        if not runs:
            return None
        starts = [r[0] for r in runs]
        ops: Dict[str, List] = {}           # program -> its operations
        for name, s, d in tr._events(plane, tr.OPS_LINE):
            at = bisect.bisect_right(starts, s) - 1
            if at >= 0 and s < runs[at][1]:
                ops.setdefault(runs[at][2], []).append((s, s + d, name))
        events = []
        for program, mine in ops.items():
            seen = {s for s in (signature(n) for _, _, n in mine) if s}
            scopes = max(cells, key=lambda c: len(seen & c[0]))[1]
            labels: Dict[str, str] = {}
            for a, b, name in mine:
                if name not in labels:
                    at = pt.instruction(name)
                    path = scope_path(scopes.get(at, ""))
                    if at.startswith(GROUPED):
                        path += ("moe_expert",)
                    labels[name] = ",".join(n for n in NAMES if n in path)
                events.append((a, min(b, win[1]), labels[name]))
        ns = pt.innermost(events)
        table = {name: sum(v for k, v in ns.items()
                           if name in k.split(",")) / 1e6 / len(runs)
                 for name in NAMES}
        return dict(table, runs=len(runs))
    return pt._once(ctx, "decode_scope_ms", make)


def name_ms(ctx: Dict, name: str) -> Optional[float]:
    table = decode_scope_ms(ctx)
    if table is None or not table[name]:
        return None
    return table[name]
