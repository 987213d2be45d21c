"""Python support layer for the general C ABI.

ref: include/mxnet/c_api.h (165 ``MX*`` entry points) and
src/c_api/c_api.cc / c_api_symbolic.cc / c_api_executor.cc — the
reference backs the ABI with its C++ runtime; here the runtime is this
package, so ``native/c_api.cc`` embeds CPython and marshals flat C
arguments into the calls below.  Every handle the C side holds is a
``PyObject*`` owning one of: NDArray, CSymbol, Executor, KVStore.

Design note: the C shim stays a dumb marshalling layer; anything with
semantics (dtype codes, grad_req codes, compose rules, CSR shape
marshalling) lives here where it is testable from pytest without a
compiler.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .base import MXNetError
from .context import Context, cpu, num_tpus, tpu
from .executor import Executor
from .ndarray import NDArray
from .ndarray import ndarray as _nd
from .ndarray.utils import load as _nd_load
from .ndarray.utils import save as _nd_save
from .ops import registry as _op_registry
from .symbol import symbol as _sym

__all__ = ["CSymbol"]

# mshadow dtype codes (ref: 3rdparty/mshadow/mshadow/base.h kFloat32 …)
_DTYPE_FROM_CODE = {0: "float32", 1: "float64", 2: "float16", 3: "uint8",
                    4: "int32", 5: "int8", 6: "int64", -1: "float32"}
_CODE_FROM_DTYPE = {v: k for k, v in _DTYPE_FROM_CODE.items() if k != -1}

# OpReqType (ref: include/mxnet/op_attr_types.h:45)
_GRAD_REQ = {0: "null", 1: "write", 2: "write", 3: "add"}


def _device(dev_type: int, dev_id: int) -> Context:
    # reference dev_type codes (include/mxnet/base.h): 1=cpu, 2=gpu,
    # 3=cpu_pinned; the TPU build maps gpu → tpu
    if dev_type == 2 and num_tpus() > 0:
        return tpu(dev_id)
    return cpu(dev_id)


def _devcode(ctx: Context) -> Tuple[int, int]:
    table = {"cpu": 1, "gpu": 2, "tpu": 2, "cpu_pinned": 3, "cpu_shared": 5}
    return table.get(ctx.device_type, 1), ctx.device_id


# ---------------------------------------------------------------------------
# NDArray
# ---------------------------------------------------------------------------
def nd_create(shape: Sequence[int], dev_type: int, dev_id: int,
              dtype: int = 0) -> NDArray:
    """ref: MXNDArrayCreateEx (c_api.cc MXNDArrayCreateEx)."""
    return _nd.zeros(tuple(int(d) for d in shape),
                     ctx=_device(dev_type, dev_id),
                     dtype=_DTYPE_FROM_CODE[int(dtype)])


def nd_create_none() -> NDArray:
    """ref: MXNDArrayCreateNone — a placeholder with no data."""
    return _nd.zeros((0,))


def nd_shape(arr: NDArray) -> Tuple[int, ...]:
    return tuple(int(d) for d in arr.shape)


def nd_dtype(arr: NDArray) -> int:
    return _CODE_FROM_DTYPE.get(np.dtype(arr.dtype).name, 0)


def nd_context(arr: NDArray) -> Tuple[int, int]:
    return _devcode(arr.context)


def nd_sync_copy_from(arr: NDArray, flat: np.ndarray) -> None:
    """ref: MXNDArraySyncCopyFromCPU — the C side hands a flat buffer
    already viewed with the array's dtype.

    The view wraps the *caller's* memory (np.frombuffer over the C
    pointer) and jax.device_put on CPU may alias rather than copy, so an
    owned copy here is mandatory — the caller's buffer lifetime ends at
    return (reference contract)."""
    import jax

    shape = tuple(arr.shape)
    arr._data = jax.device_put(np.array(flat, copy=True).reshape(shape))
    arr._vt = object()


def nd_tobytes(arr: NDArray) -> bytes:
    """ref: MXNDArraySyncCopyToCPU."""
    return np.ascontiguousarray(arr.asnumpy()).tobytes()


def nd_slice(arr: NDArray, begin: int, end: int) -> NDArray:
    return arr[int(begin):int(end)]


def nd_at(arr: NDArray, idx: int) -> NDArray:
    return arr[int(idx)]


def nd_reshape(arr: NDArray, shape: Sequence[int]) -> NDArray:
    return arr.reshape(tuple(int(d) for d in shape))


def nd_save(fname: str, arrs: Sequence[NDArray],
            keys: Sequence[str]) -> None:
    if keys:
        _nd_save(fname, dict(zip(keys, arrs)))
    else:
        _nd_save(fname, list(arrs))


def nd_load(fname: str) -> Tuple[List[NDArray], List[str]]:
    data = _nd_load(fname)
    if isinstance(data, dict):
        names = list(data)
        return [data[k] for k in names], names
    return list(data), []


def nd_waitall() -> None:
    from . import nd as _ndns

    _ndns.waitall()


def nd_wait(arr: NDArray) -> None:
    arr.wait_to_read()


# ---------------------------------------------------------------------------
# operator registry + imperative invoke
# ---------------------------------------------------------------------------
def op_names() -> List[str]:
    """Every resolvable op name, ALIASES INCLUDED — the reference's
    creator list carries both canonical and aliased names (e.g.
    elemwise_add beside _binary_add), and cpp-package callers compose
    through whichever the example uses."""
    return _op_registry.list_ops(include_aliases=True)


def op_info(name: str) -> Tuple[str, str, List[str]]:
    """(name, doc, input_names) — ref: MXSymbolGetAtomicSymbolInfo."""
    op = _op_registry.get(name)
    return op.name, op.doc or "", list(op.input_names or ())


def imperative_invoke(op_name: str, inputs: Sequence[NDArray],
                      param_keys: Sequence[str],
                      param_vals: Sequence[str],
                      outputs: Optional[Sequence[NDArray]]) -> List[NDArray]:
    """ref: MXImperativeInvoke (src/c_api/c_api_ndarray.cc:117).
    Returns the output list; when ``outputs`` is given the results are
    written into those arrays (reference out-param semantics)."""
    params = dict(zip(param_keys, param_vals))
    out = list(outputs) if outputs else None
    res = _nd.invoke(op_name, list(inputs), params, out=out)
    if isinstance(res, NDArray):
        return [res]
    return list(res)


# ---------------------------------------------------------------------------
# Symbol — handles are CSymbol wrappers so MXSymbolCompose can mutate
# the object behind a stable PyObject* (reference symbols are mutated
# in place by Compose, c_api_symbolic.cc MXSymbolCompose)
# ---------------------------------------------------------------------------
class CSymbol:
    """C-ABI symbol handle: either a built Symbol or a pending atomic op
    awaiting Compose."""

    __slots__ = ("sym", "op", "params")

    def __init__(self, sym: Optional[_sym.Symbol] = None,
                 op: Optional[str] = None,
                 params: Optional[Dict[str, str]] = None):
        self.sym = sym
        self.op = op
        self.params = params or {}

    def built(self) -> _sym.Symbol:
        if self.sym is None:
            # an atomic symbol used without compose: all-variable inputs
            self.sym = _sym.create(self.op, **self.params)
        return self.sym


def sym_create_atomic(op_name: str, keys: Sequence[str],
                      vals: Sequence[str]) -> CSymbol:
    """ref: MXSymbolCreateAtomicSymbol."""
    _op_registry.get(op_name)  # validate early
    return CSymbol(op=op_name, params=dict(zip(keys, vals)))


def sym_compose(h: CSymbol, name: Optional[str], keys: Sequence[str],
                args: Sequence[CSymbol]) -> None:
    """ref: MXSymbolCompose — attach inputs, finalize the node."""
    if h.op is None:
        raise MXNetError("Compose on a non-atomic symbol")
    kwargs = dict(h.params)
    arg_syms = [a.built() for a in args]
    if keys:
        for k, s in zip(keys, arg_syms):
            kwargs[k] = s
        h.sym = _sym.create(h.op, name=name or None, **kwargs)
    else:
        h.sym = _sym.create(h.op, *arg_syms, name=name or None, **kwargs)


def sym_variable(name: str) -> CSymbol:
    return CSymbol(sym=_sym.Variable(name))


def sym_group(handles: Sequence[CSymbol]) -> CSymbol:
    return CSymbol(sym=_sym.Group([h.built() for h in handles]))


def sym_from_json(json_str: str) -> CSymbol:
    return CSymbol(sym=_sym.load_json(json_str))


def sym_from_file(fname: str) -> CSymbol:
    return CSymbol(sym=_sym.load(fname))


def sym_to_json(h: CSymbol) -> str:
    return h.built().tojson()


def sym_save(h: CSymbol, fname: str) -> None:
    h.built().save(fname)


def sym_copy(h: CSymbol) -> CSymbol:
    # deep copy through JSON so SetAttr on the copy cannot touch nodes
    # shared with the original (reference MXSymbolCopy contract)
    return CSymbol(sym=_sym.load_json(h.built().tojson()))


def sym_name(h: CSymbol) -> str:
    return h.built().name


def sym_list_arguments(h: CSymbol) -> List[str]:
    return h.built().list_arguments()


def sym_list_outputs(h: CSymbol) -> List[str]:
    return h.built().list_outputs()


def sym_list_aux(h: CSymbol) -> List[str]:
    return h.built().list_auxiliary_states()


def sym_get_internals(h: CSymbol) -> CSymbol:
    return CSymbol(sym=h.built().get_internals())


def sym_get_output(h: CSymbol, index: int) -> CSymbol:
    return CSymbol(sym=h.built()[int(index)])


def sym_num_outputs(h: CSymbol) -> int:
    return len(h.built().list_outputs())


def sym_get_attr(h: CSymbol, key: str) -> Optional[str]:
    return h.built().attr(key)


def sym_set_attr(h: CSymbol, key: str, value: str) -> None:
    node = h.built()._entries[0][0]
    node.attrs["__%s__" % key if not key.startswith("__") else key] = value


def sym_infer_shape(h: CSymbol, keys: Sequence[str],
                    shapes: Sequence[Sequence[int]], partial: bool):
    """ref: MXSymbolInferShape(Partial) — returns
    (arg_shapes, out_shapes, aux_shapes, complete)."""
    from .symbol.infer import infer_shape

    kwargs = {k: tuple(int(d) for d in s) for k, s in zip(keys, shapes)}
    arg, out, aux = infer_shape(h.built(), partial=partial, **kwargs)
    complete = all(s is not None for s in list(arg) + list(out) +
                   list(aux))
    fix = lambda lst: [tuple(s) if s is not None else () for s in lst]
    return fix(arg), fix(out), fix(aux), complete


def sym_infer_type(h: CSymbol, keys: Sequence[str],
                   dtypes: Sequence[int]):
    """ref: MXSymbolInferType."""
    from .symbol.infer import infer_type

    kwargs = {k: _DTYPE_FROM_CODE[int(d)] for k, d in zip(keys, dtypes)}
    arg, out, aux = infer_type(h.built(), **kwargs)
    code = lambda lst: [_CODE_FROM_DTYPE.get(np.dtype(t).name, 0)
                       if t is not None else -1 for t in lst]
    carg, cout, caux = code(arg), code(out), code(aux)
    complete = all(c != -1 for c in carg + cout + caux)
    return carg, cout, caux, complete


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------
def exec_bind(h: CSymbol, dev_type: int, dev_id: int,
              g2c_keys: Sequence[str], g2c_dev_types: Sequence[int],
              g2c_dev_ids: Sequence[int], in_args: Sequence[NDArray],
              arg_grads: Sequence[Optional[NDArray]],
              grad_reqs: Sequence[int],
              aux_states: Sequence[NDArray]) -> Executor:
    """ref: MXExecutorBindEX (c_api_executor.cc)."""
    sym = h.built()
    ctx = _device(dev_type, dev_id)
    group2ctx = {k: _device(t, i) for k, t, i in
                 zip(g2c_keys, g2c_dev_types, g2c_dev_ids)} or None
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    if len(in_args) != len(arg_names):
        raise MXNetError("Bind: %d args given, %d expected"
                         % (len(in_args), len(arg_names)))
    args = dict(zip(arg_names, in_args))
    req = {n: _GRAD_REQ[int(r)] for n, r in zip(arg_names, grad_reqs)}
    grads = {n: g for n, g in zip(arg_names, arg_grads) if g is not None}
    return Executor.bind(sym, ctx=ctx, args=args, args_grad=grads,
                         grad_req=req,
                         aux_states=dict(zip(aux_names, aux_states)),
                         group2ctx=group2ctx)


def exec_forward(ex: Executor, is_train: int) -> None:
    ex.forward(is_train=bool(is_train))


def exec_backward(ex: Executor, head_grads: Sequence[NDArray]) -> None:
    ex.backward(list(head_grads) if head_grads else None)


def exec_outputs(ex: Executor) -> List[NDArray]:
    if not ex.outputs or not getattr(ex, "_forward_done", True):
        ex.forward()
    return list(ex.outputs)


def exec_print(ex: Executor) -> str:
    lines = ["Symbol outputs: %s" % ", ".join(ex._output_names)]
    for name, arr in ex.arg_dict.items():
        lines.append("arg %s %s %s" % (name, arr.shape,
                                       np.dtype(arr.dtype).name))
    for name, arr in ex.aux_dict.items():
        lines.append("aux %s %s %s" % (name, arr.shape,
                                       np.dtype(arr.dtype).name))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# KVStore
# ---------------------------------------------------------------------------
def kv_create(kind: str):
    from . import kvstore as _kv

    return _kv.create(kind)


def kv_init(kv, keys: Sequence, vals: Sequence[NDArray]) -> None:
    kv.init(list(keys), list(vals))


def kv_push(kv, keys: Sequence, vals: Sequence[NDArray],
            priority: int) -> None:
    kv.push(list(keys), list(vals), priority=priority)


def kv_pull(kv, keys: Sequence, outs: Sequence[NDArray],
            priority: int) -> None:
    kv.pull(list(keys), out=list(outs), priority=priority)


def kv_type(kv) -> str:
    return kv.type


def kv_rank(kv) -> int:
    return kv.rank


def kv_num_workers(kv) -> int:
    return kv.num_workers


def kv_barrier(kv) -> None:
    barrier = getattr(kv, "barrier", None)
    if callable(barrier):
        barrier()


def kv_set_updater(kv, trampoline) -> None:
    """``trampoline(key:int, recv:NDArray, local:NDArray)`` calls back
    into the C function pointer (ref: MXKVStoreSetUpdater)."""
    kv.set_updater(lambda key, recv, local: trampoline(int(key), recv,
                                                       local))


# ---------------------------------------------------------------------------
# Autograd (ref: src/c_api/c_api_ndarray.cc MXAutograd*)
# ---------------------------------------------------------------------------
def ag_set_recording(flag: int) -> int:
    from . import autograd

    prev = autograd.set_recording(bool(flag))
    return int(prev)


def ag_set_training(flag: int) -> int:
    from . import autograd

    prev = autograd.set_training(bool(flag))
    return int(prev)


def ag_is_recording() -> int:
    from . import autograd

    return int(autograd.is_recording())


def ag_is_training() -> int:
    from . import autograd

    return int(autograd.is_training())


def ag_mark_variables(arrs: Sequence[NDArray], reqs: Sequence[int],
                      grads: Sequence[NDArray]) -> None:
    """ref: MXAutogradMarkVariables — attach gradient buffers."""
    from . import autograd

    autograd.mark_variables(list(arrs),
                            list(grads),
                            [_GRAD_REQ[int(r)] for r in reqs])


def ag_backward(outputs: Sequence[NDArray],
                out_grads: Sequence[Optional[NDArray]],
                retain_graph: int, train_mode: int) -> None:
    """ref: MXAutogradBackwardEx."""
    from . import autograd

    autograd.backward(list(outputs),
                      list(out_grads) if out_grads else None,
                      retain_graph=bool(retain_graph),
                      train_mode=bool(train_mode))


def ag_get_grad(arr: NDArray) -> NDArray:
    if arr.grad is None:
        raise MXNetError("array has no grad buffer attached")
    return arr.grad


# ---------------------------------------------------------------------------
# CachedOp (ref: src/c_api/c_api_ndarray.cc MXCreateCachedOp/MXInvokeCachedOp)
# ---------------------------------------------------------------------------
class CCachedOp:
    """C-ABI cached op: a bound symbol specialized + jitted per input
    shape set (the reference's CachedOp re-executor)."""

    def __init__(self, h: "CSymbol"):
        self.sym = h.built()
        self._arg_names = self.sym.list_arguments()
        # per-shape executors like the reference CachedOp's per-shape
        # cached graphs: alternating shapes (bucketing, partial last
        # batch) must hit the jit cache, not rebind every call
        self._execs: Dict[tuple, Executor] = {}

    def invoke(self, inputs: Sequence[NDArray]) -> List[NDArray]:
        if len(inputs) != len(self._arg_names):
            raise MXNetError("CachedOp: %d inputs given, %d expected"
                             % (len(inputs), len(self._arg_names)))
        shapes = tuple(tuple(a.shape) for a in inputs)
        ex = self._execs.get(shapes)
        if ex is None:
            kwargs = {n: tuple(a.shape) for n, a in
                      zip(self._arg_names, inputs)}
            ex = Executor.simple_bind(self.sym, grad_req="null",
                                      **kwargs)
            self._execs[shapes] = ex
        for n, a in zip(self._arg_names, inputs):
            ex.arg_dict[n]._data = a._data.astype(ex.arg_dict[n].dtype)
        return list(ex.forward(is_train=False))


def cachedop_create(h: "CSymbol") -> CCachedOp:
    return CCachedOp(h)


def cachedop_invoke(co: CCachedOp,
                    inputs: Sequence[NDArray]) -> List[NDArray]:
    return co.invoke(inputs)


# ---------------------------------------------------------------------------
# DataIter C surface (ref: src/c_api/c_api.cc MXDataIter*, registered
# iterators listed by MXListDataIters)
# ---------------------------------------------------------------------------
_DATAITERS = None


def _dataiter_registry():
    global _DATAITERS
    if _DATAITERS is None:
        from . import io as _io

        _DATAITERS = {
            "MNISTIter": _io.MNISTIter,
            "ImageRecordIter": _io.ImageRecordIter,
            "ImageDetRecordIter": _io.ImageDetRecordIter,
            "CSVIter": _io.CSVIter,
            "LibSVMIter": _io.LibSVMIter,
        }
    return _DATAITERS


def di_list() -> List[str]:
    return sorted(_dataiter_registry())


def di_info(name: str) -> Tuple[str, str]:
    cls = _dataiter_registry()[name]
    return name, (cls.__doc__ or "").strip()


class CDataIter:
    """Holds the iterator + the current batch (the C getters hand out
    NDArray handles from the last MXDataIterNext)."""

    def __init__(self, name: str, params: Dict[str, str]):
        cls = _dataiter_registry()[name]
        kwargs: Dict[str, object] = {}
        for k, v in params.items():
            kwargs[k] = _coerce_iter_param(k, v)
        self.it = cls(**kwargs)
        self.batch = None

    def next(self) -> int:
        try:
            self.batch = self.it.next()
            return 1
        except StopIteration:
            self.batch = None
            return 0

    def before_first(self) -> None:
        self.it.reset()
        self.batch = None


def _coerce_iter_param(key: str, val: str):
    s = str(val).strip()
    if s.startswith("(") and s.endswith(")"):
        # fractional tuples (crop scales, overlaps, mean/std) must
        # survive; only integral values collapse to int (shape dims)
        out = []
        for p in s[1:-1].split(","):
            if not p.strip():
                continue
            f = float(p)
            out.append(int(f) if f == int(f) else f)
        return tuple(out)
    for conv in (int, float):
        try:
            return conv(s)
        except ValueError:
            pass
    if s in ("True", "true"):
        return True
    if s in ("False", "false"):
        return False
    return s


def di_create(name: str, keys: Sequence[str],
              vals: Sequence[str]) -> CDataIter:
    return CDataIter(name, dict(zip(keys, vals)))


def di_next(h: CDataIter) -> int:
    return h.next()


def di_before_first(h: CDataIter) -> None:
    h.before_first()


def di_get_data(h: CDataIter) -> NDArray:
    return h.batch.data[0]


def di_get_label(h: CDataIter) -> NDArray:
    return h.batch.label[0]


def di_get_pad(h: CDataIter) -> int:
    return int(h.batch.pad or 0)


def di_get_index(h: CDataIter) -> List[int]:
    idx = h.batch.index
    return [int(i) for i in idx] if idx is not None else []


# ---------------------------------------------------------------------------
# SimpleBind (ref: src/c_api/c_api_executor.cc MXExecutorSimpleBind —
# what every reference binding actually calls)
# ---------------------------------------------------------------------------
def exec_simple_bind(h: "CSymbol", dev_type: int, dev_id: int,
                     g2c_keys: Sequence[str],
                     g2c_dev_types: Sequence[int],
                     g2c_dev_ids: Sequence[int],
                     shape_keys: Sequence[str],
                     shapes: Sequence[Sequence[int]],
                     dtype_keys: Sequence[str], dtype_vals: Sequence[int],
                     grad_req_keys: Sequence[str],
                     grad_req_vals: Sequence[str],
                     shared_exec: Optional[Executor]):
    """Returns (executor, in_args, arg_grads_or_None, aux_states) — the
    reference's out-parameter set."""
    sym = h.built()
    ctx = _device(dev_type, dev_id)
    group2ctx = {k: _device(t, i) for k, t, i in
                 zip(g2c_keys, g2c_dev_types, g2c_dev_ids)} or None
    grad_req: object = "write"
    if grad_req_keys:
        grad_req = {k: v for k, v in zip(grad_req_keys, grad_req_vals)}
    type_dict = {k: _DTYPE_FROM_CODE[int(v)]
                 for k, v in zip(dtype_keys, dtype_vals)} or None
    kwargs = {k: tuple(int(d) for d in s)
              for k, s in zip(shape_keys, shapes)}
    ex = Executor.simple_bind(sym, ctx=ctx, grad_req=grad_req,
                              type_dict=type_dict, group2ctx=group2ctx,
                              shared_exec=shared_exec, **kwargs)
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    in_args = [ex.arg_dict[n] for n in arg_names]
    arg_grads = [ex.grad_dict.get(n) for n in arg_names]
    aux_states = [ex.aux_dict[n] for n in aux_names]
    return ex, in_args, arg_grads, aux_states


def exec_set_monitor_callback(ex: Executor, trampoline,
                              monitor_all: int) -> None:
    """ref: MXExecutorSetMonitorCallback."""
    ex.set_monitor_callback(lambda name, arr: trampoline(str(name), arr),
                            monitor_all=bool(monitor_all))


# ---------------------------------------------------------------------------
# NDArray tail
# ---------------------------------------------------------------------------
_STYPE_CODE = {"default": 0, "row_sparse": 1, "csr": 2}


def nd_storage_type(arr) -> int:
    return _STYPE_CODE.get(getattr(arr, "stype", "default"), 0)


def nd_detach(arr: NDArray) -> NDArray:
    return arr.detach()


def nd_grad(arr: NDArray) -> Optional[NDArray]:
    return arr.grad


def nd_set_grad_state(arr: NDArray, state: int) -> None:
    arr._grad_req = "write" if state else "null"


def nd_get_grad_state(arr: NDArray) -> int:
    return int(arr._grad_req != "null")


def nd_save_raw(arr: NDArray) -> bytes:
    """ref: MXNDArraySaveRawBytes — the dmlc single-array blob."""
    import io as _pyio

    from .ndarray.utils import _write_dmlc

    buf = _pyio.BytesIO()
    _write_dmlc(buf, [arr], [])
    return buf.getvalue()


def nd_load_raw(data: bytes) -> NDArray:
    import io as _pyio

    from .context import current_context
    from .ndarray.utils import _read_dmlc

    arrs = _read_dmlc(_pyio.BytesIO(data), current_context())
    if isinstance(arrs, dict):
        arrs = list(arrs.values())
    if not arrs:
        raise MXNetError("empty raw NDArray blob")
    return arrs[0]


def nd_create_sparse(stype: int, shape: Sequence[int], dev_type: int,
                     dev_id: int, dtype: int,
                     aux_types: Sequence[int]):
    from .ndarray import sparse as _sp

    name = {1: "row_sparse", 2: "csr"}[int(stype)]
    return _sp.zeros(name, tuple(int(d) for d in shape),
                     ctx=_device(dev_type, dev_id),
                     dtype=_DTYPE_FROM_CODE[int(dtype)])


def nd_aux_type(arr, i: int) -> int:
    # row_sparse: indices; csr: indptr, indices — all int64 here
    return 6


def nd_num_aux(arr) -> int:
    st = getattr(arr, "stype", "default")
    return {"default": 0, "row_sparse": 1, "csr": 2}[st]


def nd_get_aux(arr, i: int) -> NDArray:
    st = getattr(arr, "stype", "default")
    if st == "row_sparse":
        return [arr.indices][int(i)]
    if st == "csr":
        return [arr.indptr, arr.indices][int(i)]
    raise MXNetError("dense NDArray has no aux arrays")


def nd_get_data_nd(arr) -> NDArray:
    if getattr(arr, "stype", "default") == "default":
        raise MXNetError("use the array itself for dense data")
    return arr.data


def nd_sync_copy_from_nd(dst: NDArray, src: NDArray, loc: int) -> None:
    """ref: MXNDArraySyncCopyFromNDArray."""
    if loc >= 0:
        dst[int(loc)] = src
    else:
        src.copyto(dst)


def nd_check_format(arr, full_check: int) -> None:
    """ref: MXNDArraySyncCheckFormat — sparse invariant check."""
    st = getattr(arr, "stype", "default")
    if st == "csr":
        import numpy as _np2

        indptr = arr.indptr.asnumpy()
        if indptr[0] != 0 or (_np2.diff(indptr) < 0).any():
            raise MXNetError("malformed CSR indptr")


# ---------------------------------------------------------------------------
# KVStore tail (dist surface)
# ---------------------------------------------------------------------------
def kv_pull_row_sparse(kv, keys: Sequence, outs: Sequence,
                       row_ids: Sequence, priority: int) -> None:
    kv.row_sparse_pull(list(keys), out=list(outs), priority=priority,
                       row_ids=list(row_ids))


def kv_run_server(kv, controller_trampoline) -> None:
    """ref: MXKVStoreRunServer — blocks in the server loop; the
    controller receives (head, body) commands sent by workers via
    MXKVStoreSendCommmandToServers."""
    from . import kvstore_server

    controller = None
    if controller_trampoline is not None and \
            callable(controller_trampoline):
        controller = lambda head, body: controller_trampoline(int(head),
                                                              str(body))
    kvstore_server.init(controller=controller)


def kv_send_command(kv, head: int, body: str) -> None:
    fn = getattr(kv, "send_command_to_servers", None)
    if fn is None:
        raise MXNetError("kvstore %r has no command channel" % kv.type)
    fn(int(head), body)


def kv_set_compression(kv, keys: Sequence[str],
                       vals: Sequence[str]) -> None:
    kv.set_gradient_compression(dict(zip(keys, vals)))


def kv_barrier_before_exit(kv, flag: int) -> None:
    setattr(kv, "_barrier_before_exit", bool(flag))


def kv_is_scheduler() -> int:
    import os

    return int(os.environ.get("DMLC_ROLE") == "scheduler")


def kv_is_server() -> int:
    import os

    return int(os.environ.get("DMLC_ROLE") == "server")


def kv_num_dead_node(kv, node_id: int, timeout: int) -> int:
    fn = getattr(kv, "get_dead_nodes", None)
    if fn is None:
        return 0
    return len(fn(timeout))


# ---------------------------------------------------------------------------
# Profiler / engine / misc (ref: c_api_profile.cc, MXEngineSetBulkSize)
# ---------------------------------------------------------------------------
def profiler_set_config(keys: Sequence[str], vals: Sequence[str]) -> None:
    from . import profiler

    params = dict(zip(keys, vals))
    fname = params.get("filename", params.get("file_name",
                                              "profile.json"))
    profiler.set_config(filename=fname)


def profiler_set_state(state: int) -> None:
    from . import profiler

    profiler.set_state("run" if state else "stop")


def profiler_dump(finished: int) -> None:
    from . import profiler

    profiler.dump(finished=bool(finished))


_bulk_prev = None


def engine_set_bulk_size(size: int) -> int:
    """MXEngineSetBulkSize.  A C int cannot carry what
    ``engine.set_bulk_size`` returns (the previous size AND whether it
    had been asked for), so the last one is kept here and handed back
    when C restores the value it was given."""
    global _bulk_prev
    from . import engine

    restoring = _bulk_prev is not None and int(size) == _bulk_prev
    _bulk_prev = engine.set_bulk_size(_bulk_prev if restoring
                                      else int(size))
    return int(_bulk_prev)


def get_version() -> int:
    # encode like the reference: major*10000 + minor*100 + patch (1.0.0)
    return 10000


def set_omp_threads(n: int) -> None:
    import os

    os.environ["OMP_NUM_THREADS"] = str(int(n))


def init_ps_env(keys: Sequence[str], vals: Sequence[str]) -> None:
    import os

    for k, v in zip(keys, vals):
        os.environ[str(k)] = str(v)


# ---------------------------------------------------------------------------
# Symbol tail
# ---------------------------------------------------------------------------
def sym_list_attr(h: "CSymbol", shallow: int) -> List[str]:
    """Flattened [key, value, key, value...] like the reference's
    MXSymbolListAttr."""
    out: List[str] = []
    sym = h.built()
    if shallow:
        node = sym._entries[0][0]
        for k, v in node.attrs.items():
            kk = k[2:-2] if k.startswith("__") and k.endswith("__") else k
            out.extend([kk, str(v)])
        return out
    for name, attrs in sym.attr_dict().items():
        for k, v in attrs.items():
            out.extend(["%s$%s" % (name, k), str(v)])
    return out


def sym_get_children(h: "CSymbol") -> "CSymbol":
    sym = h.built()
    node = sym._entries[0][0]
    from .symbol.symbol import Symbol as _S

    if not node.inputs:
        raise MXNetError("symbol has no children")
    return CSymbol(sym=_S(list(node.inputs)))


# ---------------------------------------------------------------------------
# Custom op registration from C (ref: src/c_api/c_api_function.cc)
# ---------------------------------------------------------------------------
def custom_op_register(op_type: str, creator_trampoline) -> None:
    """The C creator is invoked per instantiation; it returns forward/
    backward/infer callbacks.  The full reference protocol (struct of
    function pointers) is marshalled by the C side into python callables
    before reaching here."""
    from . import operator as _operator

    _operator.register_c_creator(op_type, creator_trampoline)
