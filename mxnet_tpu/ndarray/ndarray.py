"""NDArray — a mutable tensor cell over an immutable ``jax.Array``.

TPU rebuild of the reference NDArray (ref: include/mxnet/ndarray.h:59-63,
src/ndarray/ndarray.cc).  The reference's ``Chunk`` owns device storage plus
an engine variable serialising reads/writes
(ref: src/engine/threaded_engine.h:115-217 ThreadedVar).  On XLA both jobs
collapse: device buffers are immutable and every op yields a fresh buffer,
so *mutation* = swapping the buffer held by this Python cell, and *ordering*
comes free from data dependencies inside XLA's async runtime.  ``WaitToRead``
becomes ``jax.block_until_ready``.

Async semantics match the reference: ops return immediately (XLA dispatch is
async on TPU); only ``asnumpy()``/``wait_to_read()`` block
(ref: SURVEY.md §3.1 "Python never blocks until .asnumpy()").
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple, Union

import numpy as _np

from .. import autograd
from ..base import MXNetError, as_shape, default_dtype, dtype_name, np_dtype
from ..context import Context, current_context
from ..ops import registry as _op_registry

__all__ = ["NDArray", "invoke", "array", "zeros", "ones", "full", "empty", "arange", "concatenate"]


def _jax():
    import jax

    return jax


def _jnp():
    import jax.numpy as jnp

    return jnp


class NDArray:
    """Mutable tensor handle (ref: python/mxnet/ndarray/ndarray.py NDArray)."""

    __slots__ = (
        "_data",
        "_ctx",
        "_grad",
        "_grad_req",
        "_fresh_grad_node",
        "_is_ag_variable",
        "_vt",
        "__weakref__",
    )

    # make NDArray win against numpy in mixed dunders
    __array_priority__ = 1000.0

    @staticmethod
    def _is_traced(x) -> bool:
        import jax.core as _jc

        return isinstance(x, _jc.Tracer)

    def __init__(self, data, ctx: Optional[Context] = None):
        jax = _jax()
        if ctx is None:
            ctx = current_context()
        if not isinstance(data, jax.Array):
            data = jax.device_put(_np.asarray(data), ctx.jax_device())
        self._data = data
        self._ctx = ctx
        self._grad = None
        self._grad_req = "null"
        self._fresh_grad_node = None
        self._is_ag_variable = False
        self._vt = object()  # value-version token (see autograd tape keying)

    # ------------------------------------------------------------------
    @classmethod
    def from_raw(cls, data, ctx: Optional[Context] = None) -> "NDArray":
        out = cls.__new__(cls)
        out._data = data
        out._ctx = ctx if ctx is not None else current_context()
        out._grad = None
        out._grad_req = "null"
        out._fresh_grad_node = None
        out._is_ag_variable = False
        out._vt = object()
        return out

    # -- basic properties ----------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def size(self) -> int:
        return int(self._data.size)

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def context(self) -> Context:
        return self._ctx

    ctx = context

    @property
    def stype(self) -> str:
        return "default"

    @property
    def T(self) -> "NDArray":
        return invoke("transpose", [self])

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad

    def __repr__(self) -> str:
        return "\n%s\n<NDArray %s @%s>" % (
            _np.asarray(self._data),
            "x".join(str(s) for s in self.shape),
            self._ctx,
        )

    def __len__(self) -> int:
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __bool__(self) -> bool:
        if self.size != 1:
            raise ValueError("ambiguous truth value of multi-element NDArray")
        return bool(_np.asarray(self._data))

    # -- sync / conversion ---------------------------------------------
    def wait_to_read(self) -> None:
        """ref: NDArray::WaitToRead (include/mxnet/ndarray.h)."""
        self._data.block_until_ready()

    wait_to_write = wait_to_read

    def asnumpy(self) -> _np.ndarray:
        """An OWNED, WRITABLE copy — the reference contract
        (ndarray.py asnumpy copies device memory into a fresh array;
        example code mutates the result in place, e.g.
        example/numpy-ops/custom_softmax.py:39 backward)."""
        out = _np.asarray(self._data)
        if not out.flags.writeable:
            out = _np.array(out)
        return out

    def asscalar(self):
        if self.size != 1:
            raise ValueError("the array is not scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def tolist(self):
        return self.asnumpy().tolist()

    def astype(self, dtype, copy: bool = True) -> "NDArray":
        if not copy and _np.dtype(self._data.dtype) == np_dtype(dtype):
            return self
        return invoke("Cast", [self], {"dtype": dtype_name(dtype)})

    def copy(self) -> "NDArray":
        return invoke("_copy", [self])

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        """ref: CopyFromTo (src/ndarray/ndarray.cc)."""
        if isinstance(other, Context):
            jax = _jax()
            return NDArray.from_raw(
                jax.device_put(self._data, Context(other).jax_device()), Context(other)
            )
        other._data = _jax().device_put(self._data, other._ctx.jax_device()).astype(
            other._data.dtype
        )
        # full version bump (token + stale producer node), same as every
        # other in-place write path — version-token consumers
        # (FusedTrainStep fast path) and autograd both must observe
        other._bump_version()
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    def as_nd_ndarray(self) -> "NDArray":
        return self

    def detach(self) -> "NDArray":
        return NDArray.from_raw(self._data, self._ctx)

    def _bump_version(self) -> None:
        self._vt = object()
        self._fresh_grad_node = None

    def tostype(self, stype: str) -> "NDArray":
        if stype == "default":
            return self
        from . import sparse as _sp

        return _sp.cast_storage(self, stype)

    # -- autograd -------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype: Optional[str] = None) -> None:
        """ref: python/mxnet/ndarray/ndarray.py attach_grad → MarkVariables."""
        jnp = _jnp()
        grad = NDArray.from_raw(jnp.zeros_like(self._data), self._ctx)
        autograd.mark_variables([self], [grad], grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True) -> None:
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph, train_mode)

    # -- shape ops as methods ------------------------------------------
    def reshape(self, *shape, **kwargs) -> "NDArray":
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        return invoke("Reshape", [self], {"shape": tuple(shape),
                                          "reverse": bool(kwargs.get("reverse", False))})

    def reshape_like(self, other) -> "NDArray":
        return invoke("reshape_like", [self, other])

    def expand_dims(self, axis) -> "NDArray":
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None) -> "NDArray":
        return invoke("squeeze", [self], {"axis": axis})

    def flatten(self) -> "NDArray":
        return invoke("Flatten", [self])

    def transpose(self, *axes) -> "NDArray":
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return invoke("transpose", [self], {"axes": tuple(axes)})

    def swapaxes(self, dim1, dim2) -> "NDArray":
        return invoke("SwapAxis", [self], {"dim1": dim1, "dim2": dim2})

    def flip(self, axis) -> "NDArray":
        return invoke("reverse", [self], {"axis": axis})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("SliceChannel", [self],
                      {"num_outputs": num_outputs, "axis": axis,
                       "squeeze_axis": squeeze_axis})

    def slice(self, begin, end, step=None) -> "NDArray":
        return invoke("slice", [self], {"begin": tuple(begin), "end": tuple(end),
                                        "step": tuple(step) if step else ()})

    def slice_axis(self, axis, begin, end) -> "NDArray":
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip") -> "NDArray":
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def pick(self, index, axis=-1, keepdims=False) -> "NDArray":
        return invoke("pick", [self, index], {"axis": axis, "keepdims": keepdims})

    def one_hot(self, depth, **kwargs) -> "NDArray":
        return invoke("one_hot", [self], dict(depth=depth, **kwargs))

    def tile(self, reps) -> "NDArray":
        return invoke("tile", [self], {"reps": tuple(reps)})

    def repeat(self, repeats, axis=None) -> "NDArray":
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def broadcast_to(self, shape) -> "NDArray":
        return invoke("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other) -> "NDArray":
        return invoke("broadcast_like", [self, other])

    def clip(self, a_min=None, a_max=None) -> "NDArray":
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    # -- reductions as methods -----------------------------------------
    def sum(self, axis=None, keepdims=False, **kw) -> "NDArray":
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False, **kw) -> "NDArray":
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False) -> "NDArray":
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False) -> "NDArray":
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False) -> "NDArray":
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False) -> "NDArray":
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False) -> "NDArray":
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False) -> "NDArray":
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True) -> "NDArray":
        return invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True) -> "NDArray":
        return invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False) -> "NDArray":
        return invoke("topk", [self], {"axis": axis, "k": k, "ret_typ": ret_typ,
                                       "is_ascend": is_ascend})

    def dot(self, other, transpose_a=False, transpose_b=False) -> "NDArray":
        return invoke("dot", [self, other],
                      {"transpose_a": transpose_a, "transpose_b": transpose_b})

    # elementwise method forms
    def abs(self): return invoke("abs", [self])
    def sqrt(self): return invoke("sqrt", [self])
    def square(self): return invoke("square", [self])
    def exp(self): return invoke("exp", [self])
    def log(self): return invoke("log", [self])
    def sigmoid(self): return invoke("sigmoid", [self])
    def tanh(self): return invoke("tanh", [self])
    def relu(self): return invoke("relu", [self])
    def softmax(self, axis=-1): return invoke("softmax", [self], {"axis": axis})
    def log_softmax(self, axis=-1): return invoke("log_softmax", [self], {"axis": axis})
    def sign(self): return invoke("sign", [self])
    def round(self): return invoke("round", [self])
    def floor(self): return invoke("floor", [self])
    def ceil(self): return invoke("ceil", [self])

    # -- arithmetic dunders --------------------------------------------
    _REV_SCALAR = {
        "_minus_scalar": "_rminus_scalar",
        "_div_scalar": "_rdiv_scalar",
        "_mod_scalar": "_rmod_scalar",
        "_power_scalar": "_rpower_scalar",
    }

    def _binary(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            args = [other, self] if reverse else [self, other]
            return invoke(op, args)
        if isinstance(other, (int, float, _np.generic)):
            name = self._REV_SCALAR.get(scalar_op, scalar_op) if reverse else scalar_op
            return invoke(name, [self], {"scalar": float(other)})
        return NotImplemented

    def __add__(self, o): return self._binary(o, "broadcast_add", "_plus_scalar")
    def __radd__(self, o): return self._binary(o, "broadcast_add", "_plus_scalar", True)
    def __sub__(self, o): return self._binary(o, "broadcast_sub", "_minus_scalar")
    def __rsub__(self, o): return self._binary(o, "broadcast_sub", "_minus_scalar", True)
    def __mul__(self, o): return self._binary(o, "broadcast_mul", "_mul_scalar")
    def __rmul__(self, o): return self._binary(o, "broadcast_mul", "_mul_scalar", True)
    def __truediv__(self, o): return self._binary(o, "broadcast_div", "_div_scalar")
    def __rtruediv__(self, o): return self._binary(o, "broadcast_div", "_div_scalar", True)
    def __div__(self, o): return self.__truediv__(o)
    def __mod__(self, o): return self._binary(o, "broadcast_mod", "_mod_scalar")
    def __rmod__(self, o): return self._binary(o, "broadcast_mod", "_mod_scalar", True)
    def __pow__(self, o): return self._binary(o, "broadcast_power", "_power_scalar")
    def __rpow__(self, o): return self._binary(o, "broadcast_power", "_power_scalar", True)
    def __neg__(self): return invoke("negative", [self])
    def __matmul__(self, o): return invoke("dot", [self, o])

    def __eq__(self, o):
        if o is None:
            return False
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o): return self._binary(o, "broadcast_greater", "_greater_scalar")
    def __ge__(self, o): return self._binary(o, "broadcast_greater_equal", "_greater_equal_scalar")
    def __lt__(self, o): return self._binary(o, "broadcast_lesser", "_lesser_scalar")
    def __le__(self, o): return self._binary(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__

    # in-place forms: swap the buffer + adopt the result's value version
    # (the ThreadedVar write, minus threads — the old version stays live on
    # the tape, so gradients through pre-mutation reads remain correct)
    def _assign(self, result: "NDArray") -> "NDArray":
        self._data = result._data
        self._vt = result._vt
        self._fresh_grad_node = result._fresh_grad_node
        return self

    def __iadd__(self, o): return self._assign(self.__add__(o))
    def __isub__(self, o): return self._assign(self.__sub__(o))
    def __imul__(self, o): return self._assign(self.__mul__(o))
    def __itruediv__(self, o): return self._assign(self.__truediv__(o))
    def __imod__(self, o): return self._assign(self.__mod__(o))

    # -- indexing -------------------------------------------------------
    def __getitem__(self, key):
        """Basic/advanced indexing.  Divergence from the reference: the
        result is a *copy*, not an aliasing view — XLA buffers are
        immutable, so views cannot share mutation.  ``__setitem__`` on the
        source still works (functional scatter + buffer swap)."""
        if autograd.is_recording():
            template, arrays = _split_index(key)
            return invoke("_index", [self] + arrays, {"key": template})
        return NDArray.from_raw(self._data[_convert_index(key)], self._ctx)

    def __setitem__(self, key, value):
        # whole-array assignment (`arr[:] = v`, the initializer/copyto
        # hot path) replaces the buffer instead of lowering to a jax
        # scatter: a scatter compiles one program PER ARRAY SHAPE, which
        # turns a 161-param init into 161 compilations
        if (key is None or key == slice(None) or key is Ellipsis):
            # preserve commitment semantics: a COMMITTED destination
            # keeps its device (o[:] = src across devices must not
            # migrate o); an uncommitted one stays uncommitted so mesh
            # users (DataParallelRunner.place) remain free to shard it
            dev = next(iter(self._data.devices())) \
                if getattr(self._data, "committed", False) else None
            if isinstance(value, NDArray):
                raw = value._data.astype(self._data.dtype) \
                    if value._data.dtype != self._data.dtype else value._data
                raw = _jnp().broadcast_to(raw, self._data.shape) \
                    if raw.shape != tuple(self._data.shape) else raw
                if dev is not None:
                    raw = _jax().device_put(raw, dev)
            else:
                arr = _np.asarray(value, dtype=self.dtype)
                arr = _np.broadcast_to(arr, tuple(self._data.shape))
                raw = _jax().device_put(arr, dev) if dev is not None \
                    else _jnp().asarray(arr)
            self._data = raw
            self._bump_version()
            return
        key2 = _convert_index(key)
        if isinstance(value, NDArray):
            raw = value._data
        else:
            raw = _np.asarray(value, dtype=self.dtype)
        self._data = self._data.at[key2].set(raw)
        # full in-place-write bump (token + stale producer node), same
        # contract as copyto
        self._bump_version()

    # iteration over first axis
    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


def _convert_index(key):
    if isinstance(key, NDArray):
        return key._data.astype("int32")
    if isinstance(key, tuple):
        return tuple(_convert_index(k) for k in key)
    return key


def _split_index(key):
    """Split an index expression into a hashable template (static jit param)
    plus the list of array indices (real op inputs, so tracing/vjp see them)."""
    arrays: List[NDArray] = []

    def walk(k):
        if isinstance(k, NDArray):
            arrays.append(k)
            return ("__arr__", len(arrays) - 1)
        if isinstance(k, _np.ndarray):
            arrays.append(NDArray(k.astype(_np.int32)))
            return ("__arr__", len(arrays) - 1)
        if isinstance(k, tuple):
            return ("__tuple__",) + tuple(walk(x) for x in k)
        if isinstance(k, list):
            return walk(_np.asarray(k))
        if isinstance(k, slice):
            return ("__slice__", k.start, k.stop, k.step)
        return k

    return walk(key), arrays


def _rebuild_index(template, idx_arrays):
    if isinstance(template, tuple):
        if template and template[0] == "__arr__":
            return idx_arrays[template[1]].astype("int32")
        if template and template[0] == "__slice__":
            return slice(template[1], template[2], template[3])
        if template and template[0] == "__tuple__":
            return tuple(_rebuild_index(t, idx_arrays) for t in template[1:])
    return template


# registered so indexing is differentiable under autograd.record
@_op_registry.register("_index")
def _index_op(data, *idx_arrays, key=None, **_):
    return data[_rebuild_index(key, idx_arrays)]


# ---------------------------------------------------------------------------
# the universal op invocation path
# (ref: MXImperativeInvokeEx → Imperative::Invoke, SURVEY.md §3.1)
# ---------------------------------------------------------------------------
def invoke(
    op: Union[str, _op_registry.Op],
    inputs: Sequence[NDArray],
    params: Optional[dict] = None,
    out: Optional[Union[NDArray, Sequence[NDArray]]] = None,
    ctx: Optional[Context] = None,
):
    if isinstance(op, str):
        op = _op_registry.get(op)
    params = dict(params) if params else {}
    # drop Nones so jit static args stay canonical
    params = {k: (tuple(v) if isinstance(v, list) else v) for k, v in params.items()}

    raw = []
    n_skip = 0
    if op.rng:
        from .. import random as _random

        raw.append(_random._next_key())
        n_skip = 1
    for x in inputs:
        if isinstance(x, NDArray):
            raw.append(x._data)
        else:
            raw.append(_jnp().asarray(x))

    fn = op.bound(**params)

    from .. import profiler as _profiler

    # one consistent snapshot: the run/sync decisions must not straddle
    # a concurrent set_config/set_state
    _prof, _prof_sync = _profiler.profiling_state()
    if _prof:
        _prof_start = _profiler._now_us()

    recording = (
        autograd.is_recording()
        and not op.nondiff
        and any(
            isinstance(x, NDArray)
            and (x._fresh_grad_node is not None or x._grad is not None)
            for x in inputs
        )
    )
    if recording:
        if op.remat:
            # whole-block ops (CachedOp) honor MXNET_BACKWARD_DO_MIRROR:
            # cheap activations recompute in backward (remat.py)
            from ..remat import maybe_checkpoint

            fn = maybe_checkpoint(fn)
    # the operator's name on everything it lowers to (HLO metadata
    # only): what a device trace is read by, as the reference's
    # profiler named engine operators (src/engine/profiler.cc)
    with _jax().named_scope(op.name):
        if recording:
            outs, vjp_fn = _jax().vjp(fn, *raw)
        else:
            outs = fn(*raw)

    if _prof:
        if _prof_sync:  # block for true op duration (NaiveEngine-style)
            _jax().block_until_ready(outs)
        _profiler.record_span(op.name, _prof_start,
                              _profiler._now_us() - _prof_start)

    out_ctx = ctx or (inputs[0]._ctx if inputs and isinstance(inputs[0], NDArray)
                      else current_context())
    tupled = outs if isinstance(outs, tuple) else (outs,)
    n_visible = len(tupled) - len(op.mutate_aux)
    wrapped = [NDArray.from_raw(o, out_ctx) for o in tupled[:n_visible]]
    if ctx is not None and tupled and \
            not NDArray._is_traced(tupled[0]):
        # an EXPLICIT creation context commits the buffer to that device
        # (model parallelism allocates per-group arrays with
        # mx.nd.zeros(shape, ctx); reference arrays live on their
        # context's device, ndarray.h Chunk)
        dev = ctx.jax_device()
        for w in wrapped:
            if dev not in w._data.devices():
                w._data = _jax().device_put(w._data, dev)

    # write back mutated aux states (BatchNorm moving stats et al.;
    # ref: aux-state updates in src/operator/batch_norm.cc)
    for pos, new_val in zip(op.mutate_aux, tupled[n_visible:]):
        tgt = inputs[pos]
        if isinstance(tgt, NDArray):
            tgt._data = new_val
            tgt._vt = object()

    if recording:
        nd_inputs = [x for x in inputs if isinstance(x, NDArray)]
        aux_templates = tupled[n_visible:]
        autograd._record_op(
            op.name,
            _VjpAdapter(vjp_fn, len(raw), n_skip, inputs, aux_templates,
                        single_out=not isinstance(outs, tuple)),
            nd_inputs,
            wrapped,
        )

    if out is not None:
        outs_list = [out] if isinstance(out, NDArray) else list(out)
        for o, w in zip(outs_list, wrapped):
            o._data = w._data.astype(o._data.dtype)
            o._vt = w._vt
            o._fresh_grad_node = w._fresh_grad_node
        return out if isinstance(out, NDArray) else outs_list
    if len(wrapped) == 1:
        return wrapped[0]
    return wrapped


class _VjpAdapter:
    """Maps output cotangents through jax.vjp, re-aligning to NDArray inputs
    (skips rng key / non-NDArray constants, zero-pads aux-state outputs)."""

    __slots__ = ("vjp_fn", "n_raw", "n_skip", "nd_mask", "aux_templates", "single_out")

    def __init__(self, vjp_fn, n_raw, n_skip, inputs, aux_templates=(), single_out=True):
        self.vjp_fn = vjp_fn
        self.n_raw = n_raw
        self.n_skip = n_skip
        self.nd_mask = [isinstance(x, NDArray) for x in inputs]
        self.aux_templates = tuple(aux_templates)
        self.single_out = single_out

    def __call__(self, out_cots):
        jnp = _jnp()
        if self.aux_templates:
            vis = out_cots if isinstance(out_cots, tuple) else (out_cots,)
            out_cots = tuple(vis) + tuple(jnp.zeros_like(t) for t in self.aux_templates)
        elif self.single_out and isinstance(out_cots, tuple):
            out_cots = out_cots[0]
        cots = self.vjp_fn(out_cots)
        # drop rng-key cotangent, then keep only NDArray positions
        cots = cots[self.n_skip :]
        return tuple(c for c, is_nd in zip(cots, self.nd_mask) if is_nd)


# ---------------------------------------------------------------------------
# creation functions (ref: python/mxnet/ndarray/utils.py, init_op.cc)
# ---------------------------------------------------------------------------
def array(source_array, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    if isinstance(source_array, NDArray):
        arr = source_array.asnumpy()
    elif isinstance(source_array, _np.ndarray):
        arr = source_array
    else:
        # python-native sources default to float32 (ref:
        # python/mxnet/ndarray/ndarray.py array(): "float32 by default")
        arr = _np.asarray(source_array)
        if dtype is None and arr.dtype in (_np.float64, _np.int64, _np.int32):
            arr = arr.astype(_np.float32)
    if dtype is not None:
        arr = arr.astype(np_dtype(dtype))
    return NDArray(arr, ctx=ctx)


def empty(shape, ctx=None, dtype=None) -> NDArray:
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs) -> NDArray:
    stype = kwargs.pop("stype", None)
    if stype is not None and stype != "default":
        from . import sparse as _sp

        return _sp.zeros(stype, shape, ctx, dtype)
    return invoke("_zeros", [], {"shape": as_shape(shape),
                                 "dtype": dtype_name(dtype)}, ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kwargs) -> NDArray:
    return invoke("_ones", [], {"shape": as_shape(shape),
                                "dtype": dtype_name(dtype)}, ctx=ctx)


def full(shape, val, ctx=None, dtype=None, out=None) -> NDArray:
    return invoke("_full", [], {"shape": as_shape(shape), "value": float(val),
                                "dtype": dtype_name(dtype)}, out=out, ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None) -> NDArray:
    return invoke("_arange", [], {"start": start, "stop": stop, "step": step,
                                  "repeat": repeat, "dtype": dtype_name(dtype)},
                  ctx=ctx)


def eye(N, M=0, k=0, ctx=None, dtype=None) -> NDArray:
    return invoke("_eye", [], {"N": N, "M": M, "k": k,
                               "dtype": dtype_name(dtype)}, ctx=ctx)


def zeros_like(other: NDArray) -> NDArray:
    return invoke("zeros_like", [other])


def ones_like(other: NDArray) -> NDArray:
    return invoke("ones_like", [other])


def concatenate(arrays: Sequence[NDArray], axis: int = 0, always_copy: bool = True) -> NDArray:
    return invoke("Concat", list(arrays), {"dim": axis})


def _public_binary(array_op: str, scalar_op: str):
    """Scalar-aware public binary fn (ref: ndarray.py module-level
    maximum/minimum/power dispatching on operand types)."""

    def f(lhs, rhs):
        if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
            return invoke(array_op, [lhs, rhs])
        if isinstance(lhs, NDArray):
            return invoke(scalar_op, [lhs], {"scalar": float(rhs)})
        if isinstance(rhs, NDArray):
            return invoke(scalar_op, [rhs], {"scalar": float(lhs)})
        raise TypeError("at least one NDArray operand required")

    f.__name__ = array_op.lstrip("_")
    return f


maximum = _public_binary("_maximum", "_maximum_scalar")
minimum = _public_binary("_minimum", "_minimum_scalar")


def moveaxis(tensor: NDArray, source: int, destination: int) -> NDArray:
    axes = list(range(tensor.ndim))
    axes.insert(destination, axes.pop(source))
    return tensor.transpose(*axes)


def waitall() -> None:
    """ref: Engine::WaitForAll (include/mxnet/engine.h).

    Devices execute enqueued XLA programs in submission order, so
    running one trivial program per device and transferring its result
    to host is a barrier on all previously dispatched work — on the
    chips and on the host backend ``mx.cpu()`` arrays live on.  A
    failure of the barrier is a failure of that work and propagates."""
    import jax
    import jax.numpy as jnp

    global _waitall_fence
    jax.effects_barrier()
    if _waitall_fence is None:
        # module-level singleton: a fresh lambda per call would miss
        # the jit cache and recompile the fence on every waitall()
        _waitall_fence = jax.jit(lambda x: x + 1)
    devices = list(jax.local_devices())
    if devices[0].platform != "cpu":
        devices += jax.local_devices(backend="cpu")
    for d in devices:
        jax.device_get(_waitall_fence(jax.device_put(
            jnp.zeros((), jnp.int32), d)))


_waitall_fence = None
