"""Gluon Block / HybridBlock / CachedOp.

ref: python/mxnet/gluon/block.py (Block :121, HybridBlock :321, hybridize →
CachedOp :381-384, name_scope :277) and src/imperative/cached_op.cc.

TPU design: ``hybridize()`` makes the whole ``hybrid_forward`` ONE traced
XLA program — jit keyed by input shapes/dtypes, gradients via the same
``jax.vjp`` tape node mechanism every op uses, so a hybridized block behaves
exactly like a single fused operator (the reference's CachedOp re-executor,
cached_op.cc:179-332, with XLA doing the graph optimisation nnvm did).
Deferred parameter shapes resolve through symbolic ``infer_shape_partial``
exactly like the reference's _deferred_infer_shape.
"""
from __future__ import annotations

import re
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as _np

from .. import autograd
from ..base import MXNetError
from ..context import Context, current_context
from .. import ndarray as _nd_mod  # generated-op namespace (F for eager)
from ..ndarray import NDArray
from ..ops.registry import Op
from .parameter import DeferredInitializationError, Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp"]

_naming = threading.local()


class _BlockScope:
    """Name scoping (ref: gluon/block.py _BlockScope)."""

    @staticmethod
    def create(prefix, params, hint) -> Tuple[str, ParameterDict]:
        current = getattr(_naming, "current", None)
        if current is None:
            if prefix is None:
                counters = getattr(_naming, "counters", None)
                if counters is None:
                    counters = _naming.counters = {}
                idx = counters.get(hint, 0)
                counters[hint] = idx + 1
                prefix = "%s%d_" % (hint, idx)
            if params is None:
                params = ParameterDict(prefix)
            return prefix, params
        block = current
        if prefix is None:
            idx = block._counters.get(hint, 0)
            block._counters[hint] = idx + 1
            prefix = "%s%d_" % (hint, idx)
        if params is None:
            params = ParameterDict(block.prefix + prefix,
                                   shared=block._params._shared)
        return block.prefix + prefix, params


class _NameScopeCtx:
    def __init__(self, block):
        self._block = block

    def __enter__(self):
        self._prev = getattr(_naming, "current", None)
        _naming.current = self._block
        return self

    def __exit__(self, *exc):
        _naming.current = self._prev


class Block:
    """ref: gluon/block.py Block:121."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._children: Dict[str, Block] = {}
        self._reg_params: Dict[str, Parameter] = {}
        self._counters: Dict[str, int] = {}
        self._scope = _NameScopeCtx(self)

    def _alias(self) -> str:
        return self.__class__.__name__.lower()

    # -- attribute registration (ref: block.py __setattr__) -------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            self.__dict__.setdefault("_children", {})[name] = value
        elif isinstance(value, Parameter):
            self.__dict__.setdefault("_reg_params", {})[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self) -> ParameterDict:
        return self._params

    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        """ref: block.py collect_params (regex ``select``)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._all_params())
        else:
            pattern = re.compile(select)
            ret.update({k: v for k, v in self._all_params().items()
                        if pattern.match(k)})
        return ret

    def _all_params(self) -> Dict[str, Parameter]:
        out = dict(self._params.items())
        for p in self._reg_params.values():
            out.setdefault(p.name, p)
        for child in self._children.values():
            out.update(child._all_params())
        return out

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        from .. import initializer as _init

        self.collect_params().initialize(
            init if init is not None else _init.Uniform(), ctx,
            verbose=verbose, force_reinit=force_reinit,
        )

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for param in self._reg_params.values():
            param.cast(dtype)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- persistence ----------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """ref: block.py save_parameters (strips the block prefix)."""
        params = self._all_params()
        from ..ndarray import save as nd_save

        arg_dict = {_strip(self.prefix, name): p.data()
                    for name, p in params.items() if p._data is not None}
        nd_save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        from ..ndarray import load as nd_load

        loaded = nd_load(filename, ctx=ctx)
        params = self._all_params()
        by_stripped = {_strip(self.prefix, name): p for name, p in params.items()}
        if not allow_missing:
            for name, p in by_stripped.items():
                if name not in loaded:
                    raise MXNetError("parameter %s missing in %s" % (name, filename))
        for name, value in loaded.items():
            if name not in by_stripped:
                if ignore_extra:
                    continue
                raise MXNetError("unknown parameter %s in %s" % (name, filename))
            p = by_stripped[name]
            if p._data is None:
                p.shape = tuple(value.shape)
                if p._deferred_init is not None:
                    init, pctx = p._deferred_init
                    p._finish_init(init, pctx)
                else:
                    p.initialize(ctx=ctx)
            p.set_data(value)

    save_params = save_parameters
    load_params = load_parameters

    # -- execution ------------------------------------------------------
    def __call__(self, *args):
        # scoped remat (MXNET_REMAT_POLICY=stage/conv_block): blocks that
        # declare a ``_remat_scope`` (resnet stages / residual units) get
        # their forward wrapped in jax.checkpoint when traced under a
        # CachedOp — eager/settle calls fall through untouched
        import jax

        scope = getattr(self, "_remat_scope", None)
        # the block's name on every operator under it, so that a device
        # trace reads .../stage1/.../conv0/Convolution
        with jax.named_scope(self.name):
            if scope is not None:
                from ..remat import checkpoint_block_call

                out = checkpoint_block_call(self, scope, args)
                if out is not NotImplemented:
                    return out
            return self.forward(*args)

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        out = self(*inputs)
        lines = ["%s: %d parameters" % (self.name, sum(
            int(_np.prod(p.shape)) for p in self._all_params().values()
            if p.shape is not None))]
        return "\n".join(lines)

    def __repr__(self):
        s = "{name}(\n{children})".format(
            name=self.__class__.__name__,
            children="".join("  (%s): %r\n" % (k, v)
                             for k, v in self._children.items()),
        )
        return s


def _strip(prefix, name):
    return name[len(prefix):] if prefix and name.startswith(prefix) else name


class HybridBlock(Block):
    """ref: gluon/block.py HybridBlock:321."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_op: Optional["CachedOp"] = None

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._cached_op = None
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._cached_op = None
        super().cast(dtype)

    def infer_shape(self, *args):
        self._deferred_infer_shape(*args)

    # -- deferred shape inference (ref: block.py _deferred_infer_shape) --
    def _deferred_infer_shape(self, *args):
        from .. import symbol as sym_mod

        # carry the real input dtypes into the trace: a bf16 batch into
        # a cast("bfloat16") net must not infer against f32 data vars
        data_syms = [sym_mod.Variable("__data%d" % i, dtype=str(a.dtype))
                     for i, a in enumerate(args)]
        out = self._symbolic_forward(*data_syms)
        shapes = {"__data%d" % i: a.shape for i, a in enumerate(args)}
        arg_shapes, _, aux_shapes = out.infer_shape_partial(**shapes)
        shape_by_name = dict(zip(out.list_arguments(), arg_shapes))
        shape_by_name.update(dict(zip(out.list_auxiliary_states(), aux_shapes)))
        for name, p in self._all_params().items():
            if p._deferred_init is not None and name in shape_by_name and \
                    shape_by_name[name] is not None:
                p._finish_deferred_init(shape_by_name[name])

    def _collect_reg_params(self):
        return self._reg_params

    def _symbolic_forward(self, *data_syms):
        """Run hybrid_forward with F=symbol, params as variables."""
        from .. import symbol as sym_mod

        kwargs = {name: p.var() for name, p in self._reg_params.items()}
        return self.hybrid_forward(sym_mod, *data_syms, **kwargs)

    # -- execution ------------------------------------------------------
    def forward(self, x, *args):
        if isinstance(x, NDArray):
            if self._active:
                try:
                    return self._call_cached(x, *args)
                except DeferredInitializationError:
                    self._deferred_infer_shape(x, *args)
                    return self._call_cached(x, *args)
            try:
                params = {name: p.data() for name, p in self._reg_params.items()}
            except DeferredInitializationError:
                self._deferred_infer_shape(x, *args)
                params = {name: p.data() for name, p in self._reg_params.items()}
            return self.hybrid_forward(_nd_mod, x, *args, **params)
        # symbol input → compose symbolically (hybrid blocks are symbols too)
        from .. import symbol as sym_mod

        kwargs = {name: p.var() for name, p in self._reg_params.items()}
        return self.hybrid_forward(sym_mod, x, *args, **kwargs)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- CachedOp path ---------------------------------------------------
    def _call_cached(self, *inputs):
        if self._cached_op is None:
            self._cached_op = CachedOp(self)
        return self._cached_op(inputs)

    def export(self, path, epoch=0):
        """Symbol + params export (ref: block.py export; aux states carry
        the 'aux:' prefix so model.load_checkpoint/Module round-trip)."""
        from .. import symbol as sym_mod
        from ..ndarray import save as nd_save

        data = sym_mod.Variable("data")
        out = self(data)
        out.save("%s-symbol.json" % path)
        aux_names = set(out.list_auxiliary_states())
        params = {}
        for name, p in self._all_params().items():
            if p._data is not None:
                prefix = "aux:" if name in aux_names else "arg:"
                params[prefix + name] = p.data()
        nd_save("%s-%04d.params" % (path, epoch), params)


class CachedOp:
    """Whole-block traced executor (ref: src/imperative/cached_op.cc:179,332;
    gluon hybridize).  The block's forward becomes a single tape node whose
    pullback is the jax.vjp of the traced program — identical autograd
    semantics to any primitive op, one XLA computation per input signature."""

    def __init__(self, block: HybridBlock):
        self._block = block
        # ordered flat list of every param in the block tree that forward
        # will read (leaf blocks read their own _reg_params)
        self._param_cells: List[Tuple[Block, str, Parameter]] = []
        seen = set()

        def collect(b):
            for name, p in b._reg_params.items():
                if id(p) not in seen:
                    seen.add(id(p))
                    self._param_cells.append((b, name, p))
            for c in b._children.values():
                collect(c)

        collect(block)
        # non-differentiable params are aux states (BatchNorm running stats
        # et al.): ops mutate them during the trace, so the traced program
        # returns their updated values and invoke() writes them back into
        # the live cells — the CachedOp-level version of the reference's
        # aux-array update (cached_op.cc forward aux handling)
        self._aux_positions = [i for i, (_, _, p) in enumerate(self._param_cells)
                               if p.grad_req == "null"]
        self._op = Op("CachedOp_" + block.name, self._raw_fn, rng=True,
                      input_names=())
        # the block trace is the mirror/remat boundary
        # (MXNET_BACKWARD_DO_MIRROR, remat.py)
        self._op.remat = True

    def _raw_fn(self, key, *arrays, _training=True, _n_inputs=1):
        """Pure function over raw jax arrays: rebuild NDArray shells, run the
        block's unhybridized forward, unwrap.  Parameter *cells* keep their
        identity (and autograd marks); only their buffers are swapped for
        the traced values during the trace."""
        from .. import random as _random

        n_in = _n_inputs
        inputs = [NDArray.from_raw(a) for a in arrays[:n_in]]
        param_vals = arrays[n_in:]
        saved_bufs = []
        for (_, _, p), val in zip(self._param_cells, param_vals):
            saved_bufs.append(p._data._data)
            p._data._data = val
        saved_active = []

        def deactivate(b):
            if isinstance(b, HybridBlock):
                saved_active.append((b, b._active))
                b._active = False
            for c in b._children.values():
                deactivate(c)

        deactivate(self._block)
        try:
            with autograd._RecordingScope(False, _training):
                with _random.trace_key_scope(key):
                    out = self._block.forward(*inputs)
            # post-forward buffers of aux params (ops mutated them in-trace)
            aux_out = tuple(self._param_cells[i][2]._data._data
                            for i in self._aux_positions)
        finally:
            for b, a in saved_active:
                b._active = a
            for (_, _, p), old in zip(self._param_cells, saved_bufs):
                p._data._data = old
        outs = tuple(o._data for o in out) if isinstance(out, (list, tuple)) \
            else (out._data,)
        return outs + aux_out if (aux_out or len(outs) > 1) else outs[0]

    def __call__(self, inputs: Sequence[NDArray]):
        from ..ndarray.ndarray import invoke

        params = [p.data() for (_, _, p) in self._param_cells]
        all_inputs = list(inputs) + params
        # mutate_aux positions index invoke's input list: inputs come first
        self._op.mutate_aux = tuple(len(inputs) + i for i in self._aux_positions)
        out = invoke(
            self._op,
            all_inputs,
            {"_training": autograd.is_training(), "_n_inputs": len(inputs)},
        )
        return out


class SymbolBlock(HybridBlock):
    """Wrap an arbitrary Symbol as a Block (ref: gluon/block.py SymbolBlock)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=None)
        from .. import symbol as sym_mod

        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(outputs)
        self._out_symbol = outputs
        self._in_names = [s.name for s in (inputs if isinstance(inputs, (list, tuple))
                                           else [inputs])]
        arg_names = outputs.list_arguments()
        aux_names = set(outputs.list_auxiliary_states())
        for name in arg_names + list(aux_names):
            if name in self._in_names:
                continue
            p = self.params.get(name, allow_deferred_init=True,
                                grad_req="null" if name in aux_names else "write")
            self._reg_params[name] = p

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        from ..ndarray import load as nd_load

        outputs = sym_mod.load(symbol_file)
        inputs = [sym_mod.Variable(n) for n in (input_names if
                  isinstance(input_names, (list, tuple)) else [input_names])]
        block = SymbolBlock(outputs, inputs)
        if param_file is not None:
            loaded = nd_load(param_file, ctx=ctx)
            for k, v in loaded.items():
                name = k.split(":", 1)[-1]
                if name in block._reg_params:
                    p = block._reg_params[name]
                    p.shape = tuple(v.shape)
                    p.initialize(ctx=ctx)
                    p.set_data(v)
        return block

    def forward(self, *args):
        if isinstance(args[0], NDArray):
            try:
                arg_vals = {name: p.data() for name, p in self._reg_params.items()}
            except DeferredInitializationError:
                shapes = {n: a.shape for n, a in zip(self._in_names, args)}
                arg_shapes, _, aux_shapes = self._out_symbol.infer_shape_partial(**shapes)
                by_name = dict(zip(self._out_symbol.list_arguments(), arg_shapes))
                by_name.update(zip(self._out_symbol.list_auxiliary_states(), aux_shapes))
                for name, p in self._reg_params.items():
                    if p._deferred_init is not None and by_name.get(name) is not None:
                        p._finish_deferred_init(by_name[name])
                arg_vals = {name: p.data() for name, p in self._reg_params.items()}
            for n, a in zip(self._in_names, args):
                arg_vals[n] = a
            ex = self._out_symbol.bind(ctx=args[0].ctx, args=arg_vals,
                                       grad_req="null",
                                       aux_states={n: arg_vals[n] for n in
                                                   self._out_symbol.list_auxiliary_states()
                                                   if n in arg_vals})
            outs = ex.forward(is_train=autograd.is_training())
            return outs[0] if len(outs) == 1 else outs
        raise MXNetError("SymbolBlock expects NDArray inputs")
