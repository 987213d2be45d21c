"""The map from a compiled program's instructions to the scopes they
were traced under.

A device trace names an operation by its HLO instruction
(``%fusion.1198``); the program names its work by ``jax.named_scope``
(the operator names of ``ndarray.invoke``, the block names of
``gluon.Block.__call__``, the transformer's ``attn`` / ``mlp`` / ...,
``optimizer``, ``cast``, ``mxbkt%03d``), which reaches the compiled
program as each instruction's ``metadata={op_name="jit(step)/.../
layer03/attn/while"}``.  ``parse.load_op_index`` reads that map from an
``.xplane.pb``; :func:`program_scopes` reads it from the program
itself, for whoever holds a trace without the metadata: every compiled
step that ``diagnostics.instrument_jit`` wrapped is lowered again from
the argument specs pinned at its compile, and the optimized HLO text of
the executable is parsed.  The specs carry the arguments' shardings, so
jax hands back the lowering and the executable the step itself runs (a
millisecond); with other specs it would lower and compile anew, which
gives the same instruction names a minute later.  So the map is that
of the executable that RUNS: one that the persistent cache served from
a tree without these names shows none (``compile_cache.enable`` keys
the cache on them for that reason).

Never done on the step path: only on request, once per compiled step,
and printing a large program's text takes a second or two.
"""
from __future__ import annotations

import logging
import re
import weakref
from typing import Dict, List, Tuple

_log = logging.getLogger("mxnet_tpu.traceview")

__all__ = ["parse_hlo_scopes", "program_scopes", "scope_path"]

_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_COMPUTATION = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(?:\(.*?\)|\S+)\s+([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REFERENCE = re.compile(r"%([\w.\-]+)")
# computations that never run as operations of their own: the bodies of
# fusions, and the reducers, comparators and combiners that reduce,
# sort, scatter or all-reduce apply (a ``call`` does run its target)
_INLINED = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
# instructions that do no work of their own on the device
_BOOKKEEPING = frozenset(("parameter", "constant", "tuple",
                          "get-tuple-element", "bitcast", "after-all",
                          "partition-id", "replica-id"))
# what jax wraps around the first scope of a transformed region
_TRANSFORMS = frozenset(("jvp", "transpose", "vmap", "pmap", "remat",
                         "checkpoint", "custom_jvp", "custom_vjp"))
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")

# wrapper -> (program name, {instruction: op_name}); dropped with the
# wrapper, which diagnostics.recorded_steps() pins
_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def parse_hlo_scopes(text: str) -> Tuple[str, Dict[str, str]]:
    """``(program name, {instruction: op_name})`` from optimized HLO
    text (``compiled.as_text()``).  Every instruction that does work is
    listed, a fusion by its own metadata and not by what it fused (the
    bodies of fused computations and of reducers are left out: they
    never appear in a trace).  An instruction the compiler made and
    gave no scope of its own (a layout ``copy`` of a weight, the
    ``dynamic-update-slice`` fusions that build a flat buffer; on the
    TPU three instructions in four) takes the ``op_name`` of the first
    scoped instruction that reads it, through any chain of unscoped
    ones, else of the first it reads from; one that reaches neither
    keeps what it has, ``""`` where that is nothing.  Instruction
    names carry no ``%``."""
    lines = text.splitlines()
    program = ""
    inlined = set()
    for line in lines:
        if not program:
            m = _MODULE.match(line)
            if m:
                program = m.group(1)
        m = _INSTRUCTION.match(line)
        if m and m.group(2) != "call":
            inlined.update(_INLINED.findall(line))
    scopes: Dict[str, str] = {}
    body: List[Tuple[str, str, str, str]] = []
    skip = False
    for line in lines + ["}"]:
        if line.startswith("}"):
            if not skip:
                scopes.update(_computation_scopes(body))
            body = []
            continue
        m = _COMPUTATION.match(line)
        if m:
            skip = m.group(1) in inlined
            continue
        m = None if skip else _INSTRUCTION.match(line)
        if m:
            name = _OP_NAME.search(line)
            body.append((m.group(1), m.group(2),
                         name.group(1) if name else "",
                         line[m.end():]))
    return program, scopes


def _computation_scopes(body) -> Dict[str, str]:
    """One computation's ``{instruction: op_name}``, the unscoped
    taking their reader's or their source's (``parse_hlo_scopes``)."""
    own = {name: op_name for name, _, op_name, _ in body}
    reads = {name: [r for r in _REFERENCE.findall(rest) if r in own]
             for name, _, _, rest in body}
    read_by: Dict[str, List[str]] = {name: [] for name in own}
    for name, sources in reads.items():
        for source in sources:
            read_by[source].append(name)
    scoped = {name for name, op_name in own.items() if scope_path(op_name)}

    def nearest(start: str, edges: Dict[str, List[str]]) -> str:
        seen, queue = {start}, list(edges[start])
        for at in queue:                       # breadth first
            if at in scoped:
                return own[at]
            if at not in seen and len(seen) < 256:
                seen.add(at)
                queue.extend(edges[at])
        return ""

    out = {}
    for name, opcode, op_name, _ in body:
        if opcode in _BOOKKEEPING:
            continue
        if name not in scoped:
            op_name = nearest(name, read_by) or nearest(name, reads) \
                or op_name
        out[name] = op_name
    return out


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The named scopes of an ``op_name``, outermost first: the path
    without its ``jit(...)`` components and its last component (the
    primitive), with jax's transform markers taken off
    (``transpose(jvp(layer03))`` is ``layer03``), and a name that
    repeats its parent's (a gluon block and the unprefixed sequence
    inside it) given once."""
    out = []
    for part in op_name.split("/")[:-1]:
        while True:
            m = _WRAPPED.match(part)
            if not m or m.group(1) not in _TRANSFORMS:
                break
            part = m.group(2)
        if part and "(" not in part and (not out or out[-1] != part):
            out.append(part)
    return tuple(out)


def program_scopes() -> Dict[str, Dict[str, str]]:
    """``{program name: {instruction: op_name}}`` for every compiled
    step of ``diagnostics.recorded_steps()``.  A program's name is its
    HLO module's (``jit_step``), which is what a device trace's ``XLA
    Modules`` line calls it; of two steps that compile to one name the
    later recorded wins.  A step that cannot be lowered again is left
    out and logged."""
    from .. import diagnostics as _diag

    out: Dict[str, Dict[str, str]] = {}
    for name, (wrapper, specs, _meta) in _diag.recorded_steps().items():
        found = _cache.get(wrapper)
        if found is None:
            try:
                text = wrapper.lower(*specs).compile().as_text()
            except Exception as exc:
                _log.warning("traceview: cannot lower %r again for its "
                             "scope map: %r", name, exc)
                continue
            found = _cache[wrapper] = parse_hlo_scopes(text)
        out[found[0]] = found[1]
    return out
