"""Executor — a bound Symbol lowered to jit-compiled XLA programs.

TPU rebuild of GraphExecutor (ref: src/executor/graph_executor.cc:512-1375,
include/mxnet/executor.h).  The reference's bind pipeline — gradient-graph
augmentation, PlaceDevice, PlanMemory, op-exec attachment, cached engine ops,
bulk segments — collapses into three jit-compiled functions over one pure
graph evaluator:

  * ``_fwd_eval``   : inference forward        (training=False)
  * ``_fwd_train``  : training forward         (training=True, aux updates)
  * ``_train_step`` : forward + vjp backward   (the fused hot path)

``jax.grad``/``jax.vjp`` replace the nnvm Gradient pass; XLA's scheduler +
allocator replace PlanMemory/InitDataEntryMemory; jit caching per input
shape replaces the bucketing executors' shared memory pools
(ref: graph_executor.cc:913 shared_pool).

``Module.forward_backward`` drives ``run_train_step`` — one compiled program
per iteration, matching the reference's cached-opr fast path
(graph_executor.cc:1440 RunOps).
"""
from __future__ import annotations

import functools
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from .base import MXNetError, np_dtype
from .context import Context, current_context
from .ndarray import NDArray
from .ndarray import ndarray as _nd_mod
from .ops import registry as _op_registry

__all__ = ["Executor"]


def _jax():
    import jax

    return jax


# ---------------------------------------------------------------------------
# PlaceDevice: ctx_group → per-node device assignment
# ---------------------------------------------------------------------------
def place_nodes(symbol, default_ctx: Context,
                group2ctx: Optional[Dict[str, Context]]):
    """The PlaceDevice pass (ref: src/executor/graph_executor.cc:406,
    nnvm PlaceDevice): assign every graph node a Context.

    Op nodes take their ``__ctx_group__`` attribute's mapped context;
    variables inherit the context of their first consumer (the reference
    allocates inputs on the consuming op's device); everything else gets
    ``default_ctx``.  Returns ``None`` when placement is trivial (no group
    maps away from the default) so callers keep the single-program jit
    path."""
    if not group2ctx:
        return None
    topo = symbol._topo()
    placement: Dict[int, Context] = {}
    nontrivial = False
    for node in topo:
        group = node.attrs.get("__ctx_group__", node.attrs.get("ctx_group"))
        if node.is_variable and group is None:
            continue  # un-grouped variables inherit a consumer below
        ctx = group2ctx.get(str(group), default_ctx) if group else default_ctx
        placement[id(node)] = ctx
        if ctx != default_ctx:
            nontrivial = True
    if not nontrivial:
        return None
    # un-grouped variables inherit first consumer's placement
    # (cross_device_copy boundaries then only appear between op groups,
    # ref: src/operator/cross_device_copy.cc)
    for node in topo:
        if node.is_variable:
            continue
        for parent, _ in node.inputs:
            if parent.is_variable and id(parent) not in placement:
                placement[id(parent)] = placement[id(node)]
    for node in topo:
        placement.setdefault(id(node), default_ctx)
    return placement


# ---------------------------------------------------------------------------
# scoped remat on the symbol path (MXNET_REMAT_POLICY=stage/conv_block)
# ---------------------------------------------------------------------------
_STAGE_RE = re.compile(r"(stage\d+)_")


def _stage_keys(topo):
    """Per-node stage key for remat segmentation, or None (boundary).

    A node's stage is read from its own name (hand-written symbols name
    ops ``stage1_unit1_conv1``), else from the stage prefix of its
    parameter variables (gluon-exported symbols carry it only on param
    names, ``...stage1_conv0_weight``), else inherited from its
    producers when they agree (relu/pool/add between parameterized
    nodes).  Parameterized nodes whose params carry no stage (stem
    conv, FC head) or mix stages are boundaries."""
    key_of: Dict[int, Optional[str]] = {}
    for node in topo:
        m = _STAGE_RE.search(node.name or "")
        if node.is_variable:
            key_of[id(node)] = m.group(1) if m else None
            continue
        if m:
            key_of[id(node)] = m.group(1)
            continue
        var_in = [p for p, _ in node.inputs if p.is_variable]
        vkeys = {key_of[id(p)] for p in var_in} - {None}
        if len(vkeys) == 1:
            key_of[id(node)] = vkeys.pop()
        elif vkeys or var_in:
            key_of[id(node)] = None
        else:
            akeys = {key_of[id(p)] for p, _ in node.inputs} - {None}
            key_of[id(node)] = akeys.pop() if len(akeys) == 1 else None
    return key_of


class _RematSegment:
    """One contiguous same-stage run of op nodes, executed under ONE
    ``jax.checkpoint``: only the values crossing the segment boundary
    (``in_refs`` consumed from outside, ``out_refs`` exported to
    outside or to the graph outputs, plus aux-state writebacks) survive
    as backward residuals — everything inside is rematerialized."""

    __slots__ = ("key", "nodes", "node_ids", "in_refs", "out_refs",
                 "aux_out_names")

    def __init__(self, key, nodes):
        self.key = key
        self.nodes = nodes
        self.node_ids = {id(n) for n in nodes}
        self.in_refs: List[Tuple[int, int]] = []
        self.out_refs: List[Tuple[int, int]] = []
        self.aux_out_names: List[str] = []


def _remat_plan(topo, flat_outputs, aux_names):
    """Segment the topo order into ('node', n) / ('seg', _RematSegment)
    entries covering every non-variable node, or None when the graph
    carries no stage structure (then the plain inline loop runs).
    Correct for ANY grouping — each segment threads its exact boundary
    values — so an imperfect name heuristic only costs memory, never
    numerics."""
    key_of = _stage_keys(topo)
    op_nodes = [n for n in topo if not n.is_variable]
    if not any(key_of[id(n)] for n in op_nodes):
        return None
    runs: List[Tuple[Optional[str], List[Any]]] = []
    for n in op_nodes:
        k = key_of[id(n)]
        if runs and runs[-1][0] == k:
            runs[-1][1].append(n)
        else:
            runs.append((k, [n]))
    # global consumer map: which op nodes read each (producer, out_idx)
    consumers: Dict[Tuple[int, int], set] = {}
    for n in op_nodes:
        for p, oi in n.inputs:
            consumers.setdefault((id(p), oi), set()).add(id(n))
    out_positions = {(id(n), oi) for n, oi in flat_outputs}
    from .ops import registry as _reg

    plan: List[Tuple[str, Any]] = []
    for k, nodes in runs:
        if k is None or len(nodes) < 2:
            plan.extend(("node", n) for n in nodes)
            continue
        seg = _RematSegment(k, nodes)
        seen_in = set()
        aux_out = set()
        for n in nodes:
            for p, oi in n.inputs:
                ref = (id(p), oi)
                if (p.is_variable or id(p) not in seg.node_ids) \
                        and ref not in seen_in:
                    seen_in.add(ref)
                    seg.in_refs.append(ref)
            for pos in _reg.get(n.op).mutate_aux:
                if pos < len(n.inputs):
                    parent, _ = n.inputs[pos]
                    if parent.is_variable and parent.name in aux_names:
                        aux_out.add(parent.name)
        seg.aux_out_names = sorted(aux_out)
        pos_of = {id(n): i for i, n in enumerate(nodes)}
        exported = set()
        for (pid, oi), readers in consumers.items():
            if pid in seg.node_ids and readers - seg.node_ids:
                exported.add((pid, oi))
        for pid, oi in out_positions:
            if pid in seg.node_ids:
                exported.add((pid, oi))
        seg.out_refs = sorted(exported, key=lambda r: (pos_of[r[0]], r[1]))
        plan.append(("seg", seg))
    if not any(kind == "seg" for kind, _ in plan):
        return None
    return plan


# ---------------------------------------------------------------------------
# pure graph evaluator
# ---------------------------------------------------------------------------
def build_graph_eval(symbol, collect_internals: bool = False,
                     placement: Optional[Dict[int, Context]] = None) -> Callable:
    """Build fn(arg_vals, aux_vals, rng_key, training) ->
    (outputs: list, aux_updates: dict name→val).  Pure; jit-traceable.

    With collect_internals=True the function returns a third value: a
    dict name→val of every non-variable node's outputs (named
    ``<node>_output`` / ``<node>_output<k>`` like the reference's
    executor output naming) — the data source for Monitor taps
    (ref: GraphExecutor::ExecuteMonCallback, graph_executor.cc:1418).

    With ``placement`` (id(node) → Context, from :func:`place_nodes`) the
    evaluator inserts a ``jax.device_put`` whenever a value crosses a
    device boundary — the cross_device_copy analogue (ref:
    src/operator/cross_device_copy.cc).  ``device_put`` is linear with a
    transpose rule, so the vjp replays the copies in reverse exactly like
    the reference's backward copy nodes."""
    import jax

    topo = symbol._topo()
    flat_outputs = symbol._flat_outputs()
    aux_names = set(symbol.list_auxiliary_states())

    node_index = {id(n): i for i, n in enumerate(topo)}

    # scoped remat (MXNET_REMAT_POLICY=stage/conv_block): segment the
    # graph by stage and run each segment under jax.checkpoint.  The
    # monitor tap needs every internal alive, and placed graphs run
    # op-by-op on their own devices — both keep the inline loop.  On
    # the symbol path residual units share one stage prefix, so both
    # conv policies checkpoint at stage granularity.
    remat_plan = None
    if not collect_internals and placement is None:
        from .remat import CONV_SCOPES, remat_policy

        if remat_policy() in CONV_SCOPES:
            remat_plan = _remat_plan(topo, flat_outputs, aux_names)

    def apply_node(node, args, rng_key, training):
        """One op node → (visible outputs, [(aux name, value)])."""
        op = _op_registry.get(node.op)
        params = {k: _op_registry.coerce_attr(v)
                  for k, v in node.attrs.items()
                  if not k.startswith("__")}
        if op.train_aware:
            params["_training"] = training
        if op.rng:
            args = [jax.random.fold_in(rng_key, node_index[id(node)])] + args
        with jax.named_scope(op.name):  # as ndarray.invoke names it
            out = op.fn(*args, **params)
        outs = list(out) if isinstance(out, tuple) else [out]
        if op.nondiff:
            # the reference registers NO gradient for these ops
            # (MultiBoxTarget, samplers, ...): jax must not
            # differentiate through their internals — argmax/where/
            # division inside target-assignment produces NaN
            # cotangents that poison every upstream gradient
            outs = [jax.lax.stop_gradient(o) for o in outs]
        n_vis = len(outs) - len(op.mutate_aux)
        # aux writebacks route to the feeding variable's name
        aux_writes = []
        for k, pos in enumerate(op.mutate_aux):
            if pos < len(node.inputs):
                parent, _ = node.inputs[pos]
                if parent.is_variable and parent.name in aux_names:
                    aux_writes.append((parent.name, outs[n_vis + k]))
        return outs[:n_vis], aux_writes

    def eval_fn(arg_vals: Dict[str, Any], aux_vals: Dict[str, Any], rng_key,
                training: bool):
        env: Dict[int, List[Any]] = {}
        aux_updates: Dict[str, Any] = {}
        internals: Dict[str, Any] = {}
        for node in topo:
            if not node.is_variable:
                continue
            if node.name in aux_vals:
                val = aux_vals[node.name]
            elif node.name in arg_vals:
                val = arg_vals[node.name]
            else:
                raise MXNetError("unbound variable %r" % node.name)
            env[id(node)] = [val]

        def run_inline(node):
            args = [env[id(p)][oi] for p, oi in node.inputs]
            if placement is not None:
                # pin every input to the node's device: cross-group edges
                # get a real transfer, same-device edges a no-op.  Pinning
                # unconditionally (rather than only on static group
                # boundaries) also repairs buffers that drifted to the
                # default device through host-side writes (initializers,
                # set_params)
                dev = placement[id(node)].jax_device()
                args = [jax.device_put(a, dev) for a in args]
            outs, aux_writes = apply_node(node, args, rng_key, training)
            env[id(node)] = outs
            if collect_internals:
                for k in range(len(outs)):
                    suffix = "_output" if len(outs) == 1 else "_output%d" % k
                    internals[node.name + suffix] = outs[k]
            for name, val in aux_writes:
                aux_updates[name] = val

        def run_segment(seg):
            ext = [env[pid][oi] for pid, oi in seg.in_refs]

            def seg_fn(key, *ext_vals):
                local = dict(zip(seg.in_refs, ext_vals))
                aux_up = {}
                for node in seg.nodes:
                    args = [local[(id(p), oi)] for p, oi in node.inputs]
                    outs, aux_writes = apply_node(node, args, key, training)
                    for oi, v in enumerate(outs):
                        local[(id(node), oi)] = v
                    for name, val in aux_writes:
                        aux_up[name] = val
                return (tuple(local[r] for r in seg.out_refs),
                        tuple(aux_up[n] for n in seg.aux_out_names))

            outs, auxs = jax.checkpoint(seg_fn)(rng_key, *ext)
            for (pid, oi), v in zip(seg.out_refs, outs):
                slot = env.setdefault(pid, [])
                while len(slot) <= oi:
                    slot.append(None)
                slot[oi] = v
            for name, v in zip(seg.aux_out_names, auxs):
                aux_updates[name] = v

        if remat_plan is None:
            for node in topo:
                if not node.is_variable:
                    run_inline(node)
        else:
            for kind, item in remat_plan:
                if kind == "node":
                    run_inline(item)
                else:
                    run_segment(item)
        outputs = [env[id(n)][oi] for n, oi in flat_outputs]
        if collect_internals:
            return outputs, aux_updates, internals
        return outputs, aux_updates

    return eval_fn


_ALLOC_ALL = None


def _alloc_all_jit():
    """Single jitted zero-fill over a static tuple of (shape, dtype)
    specs — shared process-wide so identical binds hit the jit cache."""
    global _ALLOC_ALL
    if _ALLOC_ALL is None:
        jax = _jax()
        import jax.numpy as jnp

        def _alloc_all(specs):
            return tuple(jnp.zeros(s, dtype=d) for s, d in specs)

        _ALLOC_ALL = jax.jit(_alloc_all, static_argnums=0)
    return _ALLOC_ALL


class Executor:
    """ref: python/mxnet/executor.py Executor."""

    def __init__(self, symbol, ctx: Context, arg_dict: Dict[str, NDArray],
                 grad_dict: Dict[str, Optional[NDArray]],
                 aux_dict: Dict[str, NDArray], grad_req, group2ctx=None,
                 placement=None, out_shapes=None):
        self._symbol = symbol
        self._ctx = ctx or current_context()
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        if isinstance(grad_req, str):
            grad_req = {k: grad_req for k in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self._arg_names, grad_req))
        self._grad_req = grad_req
        self._rng_counter = 0
        self._group2ctx = dict(group2ctx) if group2ctx else None
        self._placement = (placement if placement is not None else
                           place_nodes(symbol, self._ctx, self._group2ctx))

        eval_fn = build_graph_eval(symbol, placement=self._placement)
        jax = _jax()

        def fwd(training):
            def f(arg_vals, aux_vals, key):
                return eval_fn(arg_vals, aux_vals, key, training)

            # model-parallel (placed) graphs execute op-by-op so every
            # node really runs on its ctx_group device, matching the
            # reference's per-device engine streams; the single-device
            # path stays one fused XLA program
            return f if self._placement is not None else jax.jit(f)

        self._fwd_eval = fwd(False)
        self._fwd_train = fwd(True)

        grad_names = [k for k in self._arg_names if self._grad_req.get(k, "null") != "null"]
        self._grad_names = grad_names
        self._train_step = self._build_train_step(collect_internals=False)

        # outputs are STABLE buffers allocated at bind time and updated
        # in place by forward/backward — reference code captures
        # ``exec.outputs`` once and reads it after every forward (e.g.
        # example/model-parallel/lstm/lstm.py:248-263 seq_outputs), so
        # identity must survive across calls (ref: GraphExecutor output
        # NDArrays live for the executor's lifetime).
        self.outputs: List[NDArray] = []
        try:
            if out_shapes is None:  # bind() path: infer once here
                from .symbol.infer import infer_shape

                shapes = {k: tuple(v.shape) for k, v in arg_dict.items()}
                _, out_shapes, _ = infer_shape(symbol, **shapes)
            self.outputs = [_nd_mod.zeros(s, ctx=self._ctx)
                            for s in out_shapes if s is not None]
            if len(self.outputs) != len(self._output_names):
                self.outputs = []
        except Exception:
            self.outputs = []  # first forward materializes them
        # bind-time buffers hold zeros until a forward runs; consumers
        # that lazily materialize outputs key off this flag, not
        # list-emptiness (the buffers must pre-exist for identity)
        self._forward_done = False
        self._cached_grads: Optional[Dict[str, Any]] = None
        self._monitor_callback = None
        self._monitor_all = False
        self._monitor_eval = None
        self._monitor_train_fn = None

    # -- binding entry points ------------------------------------------
    @staticmethod
    def simple_bind(symbol, ctx=None, grad_req="write", type_dict=None,
                    shared_exec=None, group2ctx=None, **kwargs) -> "Executor":
        from .symbol.infer import infer_shape, infer_type

        ctx = ctx or current_context()
        shapes = {k: v for k, v in kwargs.items() if isinstance(v, (tuple, list))}
        arg_shapes, out_shapes, aux_shapes = infer_shape(symbol, **shapes)
        type_dict = type_dict or {}
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        # per-variable contexts from the PlaceDevice pass (reference
        # allocates each input on its consumer's device,
        # graph_executor.cc InitArguments)
        placement = place_nodes(symbol, ctx, group2ctx)
        var_ctx = {}
        if placement is not None:
            for node in symbol._topo():
                if node.is_variable:
                    var_ctx[node.name] = placement[id(node)]

        jax = _jax()

        # one consolidated zero-fill program instead of one tiny
        # compiled program PER buffer: a resnet50 bind allocates ~320
        # arrays, and per-array dispatch costs (one compile each)
        # dominate bind time; a single fused allocation is one compile
        plan = []  # (kind, name, shape, dtype, actx)
        for name, shape in zip(arg_names, arg_shapes):
            if shape is None:
                raise MXNetError("simple_bind: could not infer shape of %r" % name)
            dt = np_dtype(type_dict.get(name, _np.float32))
            actx = var_ctx.get(name, ctx)
            plan.append(("arg", name, tuple(shape), dt, actx))
            req = grad_req if isinstance(grad_req, str) else grad_req.get(name, "null")
            if req != "null":
                plan.append(("grad", name, tuple(shape), dt, actx))
        for name, shape in zip(aux_names, aux_shapes):
            plan.append(("aux", name, tuple(shape), _np.dtype(_np.float32),
                         var_ctx.get(name, ctx)))

        specs = tuple((p[2], _np.dtype(p[3]).name) for p in plan)
        bufs = _alloc_all_jit()(specs)
        arg_dict: Dict[str, NDArray] = {}
        grad_dict: Dict[str, Optional[NDArray]] = {}
        aux_dict: Dict[str, NDArray] = {}
        for (kind, name, shape, dt, actx), raw in zip(plan, bufs):
            if actx is not ctx:  # placed variable: commit the buffer too
                raw = jax.device_put(raw, actx.jax_device())
            cell = NDArray.from_raw(raw, actx)
            if kind == "arg":
                arg_dict[name] = cell
            elif kind == "grad":
                grad_dict[name] = cell
            else:
                aux_dict[name] = cell
        for name in arg_names:
            grad_dict.setdefault(name, None)
        # out_shapes rides along: the constructor must not re-run the
        # whole-graph inference this bind just performed
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, grad_req,
                        group2ctx=group2ctx, placement=placement,
                        out_shapes=out_shapes)

    @staticmethod
    def bind(symbol, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None) -> "Executor":
        """ref: python/mxnet/symbol.py bind.  ``shared_exec`` (reference:
        workspace/memory-pool sharing, graph_executor.cc:913) is accepted
        for API parity but has no effect — XLA owns buffer allocation, so
        there is no user-visible pool to share."""
        ctx = ctx or current_context()
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, args))
        else:
            arg_dict = dict(args or {})
        # reference grad semantics (symbol.py:1638, "one can give up
        # gradient by using a dict in args_grad and only specify
        # gradient they interested in"): args_grad=None means NO
        # gradients; a dict grants them only to the listed names —
        # everything else is effectively grad_req='null' (the
        # autoencoder example's Solver iterates grad_arrays expecting
        # None for data inputs)
        if isinstance(args_grad, (list, tuple)):
            grad_dict = dict(zip(arg_names, args_grad))
        else:
            grad_dict = dict(args_grad or {})

        def _declared_req(name):
            if isinstance(grad_req, str):
                return grad_req
            if isinstance(grad_req, (list, tuple)):
                return dict(zip(arg_names, grad_req)).get(name, "null")
            return grad_req.get(name, "null")

        eff_req = {}
        for name in arg_names:
            if name in grad_dict and grad_dict[name] is not None:
                eff_req[name] = _declared_req(name)
            else:
                grad_dict[name] = None
                eff_req[name] = "null"
        grad_req = eff_req
        if isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, aux_states))
        else:
            aux_dict = dict(aux_states or {})
        for name in aux_names:
            if name not in aux_dict:
                from .symbol.infer import infer_shape

                raise MXNetError("bind: missing aux state %r" % name)
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, grad_req,
                        group2ctx=group2ctx)

    # -- execution ------------------------------------------------------
    def _next_key(self):
        from . import random as _random

        self._rng_counter += 1
        return _random._next_key()

    def _arg_vals(self):
        return {k: v._data for k, v in self.arg_dict.items()}

    def _aux_vals(self):
        return {k: v._data for k, v in self.aux_dict.items()}

    def debug_str(self) -> str:
        """Execution-plan dump whose tail carries the planned memory
        total — the reference's nnvm memory-plan debug string
        (graph_executor debug_str; example/memcost/inception_memcost.py
        reads ``debug_str().split('\\n')[-3]`` for the
        'Total N MB allocated' line).  The figure here is XLA's
        compiled-program memory analysis (temp + output buffers) of the
        program this executor would run: the fused forward+vjp step
        when any gradient is requested, else the forward program."""
        jax = _jax()
        lines = ["Symbol Outputs:"]
        lines += ["\toutput[%d]=%s" % (i, n)
                  for i, n in enumerate(self._output_names)]
        alloc_mb = 0
        try:
            # a fixed key, NOT _next_key(): a diagnostics print must not
            # advance the global RNG stream (only shapes matter here)
            key = _jax().random.PRNGKey(0)
            has_grad = any(g is not None for g in self.grad_dict.values())
            if has_grad and hasattr(self._train_step, "lower"):
                n_out = len(self._output_names)
                lowered = self._train_step.lower(
                    self._arg_vals(), self._aux_vals(), key,
                    [None] * n_out, n_out)
            elif hasattr(self._fwd_eval, "lower"):
                lowered = self._fwd_eval.lower(
                    self._arg_vals(), self._aux_vals(), key)
            else:  # placement executors run op-by-op, no single program
                lowered = None
            if lowered is not None:
                ma = lowered.compile().memory_analysis()
                if ma is not None:
                    alloc = (getattr(ma, "temp_size_in_bytes", 0) +
                             getattr(ma, "output_size_in_bytes", 0))
                    alloc_mb = int(round(alloc / (1 << 20)))
        except Exception:
            pass  # a diagnostics string must never fail the caller
        lines.append("Total %d MB allocated" % alloc_mb)
        lines.append("Total 0 MB TempSpace resource requested")
        return "\n".join(lines) + "\n"

    def forward(self, is_train: bool = False, **kwargs) -> List[NDArray]:
        """ref: GraphExecutor::Forward (graph_executor.cc:81)."""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("forward: unknown argument %r" % k)
            if isinstance(v, NDArray):
                self.arg_dict[k]._data = v._data.astype(self.arg_dict[k].dtype)
            else:
                self.arg_dict[k][:] = v
        from . import profiler as _profiler

        _profiler.sample_memory()  # HBM high-water pre-sample (profile_memory)
        with _profiler.span("Forward<%s>" % (self._output_names[0]
                                             if self._output_names else "?"),
                            cat="symbolic"):
            if self._monitor_callback is not None:
                outs, aux_upd = self._forward_monitored(is_train)
            else:
                fn = self._fwd_train if is_train else self._fwd_eval
                outs, aux_upd = fn(self._arg_vals(), self._aux_vals(),
                                   self._next_key())
            if _profiler.sync_enabled():
                _jax().block_until_ready(outs)  # true span, not dispatch
            if is_train:
                self._write_aux(aux_upd)
        _profiler.sample_memory()
        self._cached_grads = None
        self._set_outputs(outs)
        return self.outputs

    # -- monitor tap (ref: MXExecutorSetMonitorCallback →
    #    GraphExecutor::ExecuteMonCallback, graph_executor.cc:1418) ------
    def set_monitor_callback(self, callback, monitor_all: bool = False):
        """Install a (name, NDArray) callback fired for every internal
        node output after each forward. monitor_all additionally reports
        the input arrays (as ``<name>_data``)."""
        self._monitor_callback = callback
        self._monitor_all = monitor_all
        self._monitor_eval = None
        self._monitor_train_fn = None

    def _forward_monitored(self, is_train):
        jax = _jax()
        if self._monitor_eval is None:
            eval_int = build_graph_eval(self._symbol, collect_internals=True,
                                        placement=self._placement)

            def f(arg_vals, aux_vals, key, training):
                return eval_int(arg_vals, aux_vals, key, training)

            self._monitor_eval = (f if self._placement is not None
                                  else jax.jit(f, static_argnums=3))
        outs, aux_upd, internals = self._monitor_eval(
            self._arg_vals(), self._aux_vals(), self._next_key(),
            bool(is_train))
        self._fire_monitor(internals)
        return outs, aux_upd

    def _fire_monitor(self, internals):
        if self._monitor_all:
            for k, v in self.arg_dict.items():
                self._monitor_callback(k + "_data",
                                       NDArray.from_raw(v._data, self._ctx))
        for name, val in internals.items():
            self._monitor_callback(name, NDArray.from_raw(val, self._ctx))

    def _build_train_step(self, collect_internals: bool):
        """Fused fwd+vjp step; with collect_internals it additionally
        materializes every internal node output for the Monitor tap, so
        mod.fit(monitor=...) sees the *actual* training-step values
        (same rng, same batch)."""
        jax = _jax()
        eval_fn = build_graph_eval(self._symbol,
                                   collect_internals=collect_internals,
                                   placement=self._placement)
        grad_names = self._grad_names

        def train_step(arg_vals, aux_vals, key, out_cots, n_given):
            diff = {k: arg_vals[k] for k in grad_names}
            rest = {k: v for k, v in arg_vals.items() if k not in diff}

            def pure(diff_args):
                return eval_fn({**rest, **diff_args}, aux_vals, key, True)

            # MXNET_BACKWARD_DO_MIRROR: recompute cheap activations in
            # backward instead of storing them (remat.py; ref mirror
            # pass graph_executor.cc:249)
            from .remat import maybe_checkpoint

            res, vjp_fn = jax.vjp(maybe_checkpoint(pure), diff)
            outs = res[0]
            jnp = jax.numpy
            # reference head-grad semantics (GraphExecutor::Backward):
            # None → implicit ones (loss outputs); a list shorter than
            # the output count (n_given, static) leaves the tail
            # gradient-free (BlockGrad'd state outputs, e.g.
            # model-parallel lstm.py head_grad); a (1,)-shaped head grad
            # broadcasts over the output
            cots = []
            for i, o in enumerate(outs):
                c = out_cots[i] if i < len(out_cots) else None
                if i >= n_given:
                    cots.append(jnp.zeros_like(o))
                elif c is None:
                    cots.append(jnp.ones_like(o))
                else:
                    cots.append(jnp.broadcast_to(c, o.shape).astype(o.dtype))
            zero_rest = jax.tree.map(jnp.zeros_like, res[1:])
            (grads,) = vjp_fn((cots,) + tuple(zero_rest))
            return (outs, grads) + tuple(res[1:])

        return train_step if self._placement is not None else \
            jax.jit(train_step, static_argnums=4)

    def _train_step_monitored(self, cots, n_given):
        if self._monitor_train_fn is None:
            self._monitor_train_fn = self._build_train_step(
                collect_internals=True)
        outs, grads, aux_upd, internals = self._monitor_train_fn(
            self._arg_vals(), self._aux_vals(), self._next_key(), cots,
            n_given)
        self._fire_monitor(internals)
        return outs, grads, aux_upd

    def backward(self, out_grads=None) -> None:
        """ref: GraphExecutor::Backward (graph_executor.cc:94).  Runs the
        fused forward+vjp step (forward is recomputed inside the same XLA
        program — one fusion, no host round-trip)."""
        self.run_train_step(out_grads=out_grads, update_outputs=False)

    def run_train_step(self, out_grads=None, update_outputs: bool = True):
        n_out = len(self._output_names)
        if out_grads is None:
            cots = [None] * n_out
            n_given = n_out
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cots = [g._data if g is not None else None for g in out_grads]
            n_given = len(cots)
            cots += [None] * (n_out - n_given)
        from . import profiler as _profiler

        _profiler.sample_memory()  # HBM high-water pre-sample (profile_memory)
        with _profiler.span("Backward<%s>" % (self._output_names[0]
                                              if self._output_names
                                              else "?"), cat="symbolic"):
            # fire the monitor tap only on the fused-step path (fit's
            # forward_backward); a manual forward() already fired it
            if self._monitor_callback is not None and update_outputs:
                outs, grads, aux_upd = self._train_step_monitored(cots,
                                                                  n_given)
            else:
                outs, grads, aux_upd = self._train_step(
                    self._arg_vals(), self._aux_vals(), self._next_key(),
                    cots, n_given)
            if _profiler.sync_enabled():
                _jax().block_until_ready(outs)
        _profiler.sample_memory()
        self._write_aux(aux_upd)
        if update_outputs or not self._forward_done:
            self._set_outputs(outs)
        for name in self._grad_names:
            buf = self.grad_dict.get(name)
            if buf is None:
                continue
            req = self._grad_req.get(name, "write")
            g = grads[name]
            if req == "add":
                buf._data = buf._data + g.astype(buf.dtype)
            else:
                buf._data = g.astype(buf.dtype)
        return self.outputs

    def _set_outputs(self, outs) -> None:
        """Write forward results into the stable output cells (identity
        preserved); (re)materialize cells only on first use or when a
        shape changed."""
        self._forward_done = True
        if len(self.outputs) != len(outs):
            self.outputs = [NDArray.from_raw(o, self._ctx) for o in outs]
            return
        for i, o in enumerate(outs):
            cell = self.outputs[i]
            if tuple(cell.shape) == tuple(o.shape):
                cell._data = o
                cell._vt = object()
            else:
                self.outputs[i] = NDArray.from_raw(o, self._ctx)

    def _write_aux(self, aux_upd) -> None:
        for name, val in aux_upd.items():
            cell = self.aux_dict.get(name)
            if cell is not None:
                cell._data = val.astype(cell.dtype)
                cell._vt = object()

    # -- parameter management ------------------------------------------
    @property
    def grad_arrays(self) -> List[Optional[NDArray]]:
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def arg_arrays(self) -> List[NDArray]:
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def aux_arrays(self) -> List[NDArray]:
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self) -> Dict[str, NDArray]:
        return dict(zip(self._output_names, self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params: bool = False) -> None:
        """ref: Executor::CopyParams."""
        for name, arr in (arg_params or {}).items():
            if name in self.arg_dict:
                arr.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("copy_params_from: unknown argument %r" % name)
        for name, arr in (aux_params or {}).items():
            if name in self.aux_dict:
                arr.copyto(self.aux_dict[name])
            elif not allow_extra_params:
                raise MXNetError("copy_params_from: unknown aux state %r" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new shapes — jit specialises per shape, so this is a
        cheap cache hit after the first call (the bucketing fast path,
        ref: graph_executor.cc:1572 Reshape sharing memory pools)."""
        new_shapes = {k: tuple(v) for k, v in kwargs.items()}
        ex = Executor.simple_bind(self._symbol, ctx=self._ctx,
                                  grad_req=self._grad_req,
                                  group2ctx=self._group2ctx, **new_shapes)
        # unchanged-shape arrays are SHARED, not copied — the reference
        # Reshape keeps the same NDArray chunks (graph_executor.cc:1572),
        # and callers rely on it: e.g. the DQN example's target network
        # forwards through a reshaped executor while copy_params_to
        # writes the ORIGINAL param arrays in place
        # (example/reinforcement-learning/dqn/base.py:297); a copy here
        # would freeze that executor's parameters forever.
        for name, arr in self.arg_dict.items():
            tgt = ex.arg_dict.get(name)
            if tgt is None or tgt.shape != arr.shape:
                continue
            if tgt.dtype == arr.dtype:
                ex.arg_dict[name] = arr
            else:  # dtype changed under the new shapes: copy-with-cast
                arr.copyto(tgt)
        for name, arr in self.grad_dict.items():
            if arr is not None and ex.grad_dict.get(name) is not None \
                    and ex.grad_dict[name].shape == arr.shape \
                    and ex.grad_dict[name].dtype == arr.dtype:
                ex.grad_dict[name] = arr
        for name, arr in self.aux_dict.items():
            tgt = ex.aux_dict.get(name)
            if tgt is None or tgt.shape != arr.shape:
                continue
            if tgt.dtype == arr.dtype:
                ex.aux_dict[name] = arr
            else:
                arr.copyto(tgt)
        return ex
