"""Bulk fit execution: K train steps per XLA dispatch for Module.fit.

TPU translation of the reference engine's bulk segments
(ref: src/engine/threaded_engine.h:386-458 bulk-exec fusion,
src/executor/graph_executor.cc:1340-1375 InitOpSegs,
MXNET_EXEC_BULK_EXEC_TRAIN): where the reference amortizes per-op engine
push overhead by fusing op segments, the dispatch-latency-bound unit
here is the whole train step, so ``engine.set_bulk_size`` K means K
complete steps (forward + vjp backward + optimizer update) inside ONE
compiled program via ``lax.scan``.

The optimizer runs *inside* the scan through a trace adapter: the
registered ``Optimizer.update_multi_precision`` body is executed once at
trace time over tracer-backed NDArray cells, so every fused optimizer op
(sgd_mom_update, adam_update, ...) lowers into the same program as the
backward pass.  Time-dependent hyperparameters stay correct:

  * learning rate is a traced scalar input, re-evaluated host-side at
    every dispatch (lr_scheduler granularity = K batches);
  * the per-param update count ``t`` (Adam/FTML bias correction) is the
    scan counter, a per-step tracer.

Observable semantics vs the per-batch loop: metrics see every batch
(outputs are returned stacked), callbacks fire per batch; only the
gradient buffers (`grad_dict`) are not materialized between steps and
lr updates quantize to K.  Falls back (permanently, with one log line)
whenever the module configuration is outside the fast path's contract:
model-parallel placement, dist/compressed kvstore, sparse grads,
``grad_req='add'``, or an optimizer whose update body fails to trace.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional

from ..executor import build_graph_eval
from ..ndarray import NDArray

_log = logging.getLogger(__name__)

__all__ = ["BulkTrainLoop"]


def _flatten_state(st, out: List[Any]) -> None:
    if st is None:
        return
    if isinstance(st, (list, tuple)):
        for s in st:
            _flatten_state(s, out)
        return
    out.append(st)


def _rebuild_state(template, leaves_iter):
    """Same nesting as ``template`` with fresh tracer-backed cells."""
    if template is None:
        return None, []
    if isinstance(template, (list, tuple)):
        cells_all = []
        parts = []
        for t in template:
            part, cells = _rebuild_state(t, leaves_iter)
            parts.append(part)
            cells_all.extend(cells)
        return type(template)(parts), cells_all
    cell = NDArray.from_raw(next(leaves_iter))
    return cell, [cell]


class _TracedCounts(dict):
    """Stand-in for Optimizer._index_update_count during tracing: every
    index reads as the scan step counter (a tracer), so bias-correction
    terms (Adam's t) are computed per step inside the program."""

    def __init__(self, t):
        super().__init__()
        self._t = t

    def __getitem__(self, key):
        return self._t

    def get(self, key, default=None):
        return self._t

    def setdefault(self, key, default=None):
        return self._t


class BulkTrainLoop:
    """Compiled K-step fit path for a bound, optimized Module."""

    def __init__(self, module):
        self._mod = module
        self._runners: Dict[int, Any] = {}  # K -> jitted program
        self._reason: Optional[str] = None
        self._checked = False
        self._built = False
        self._bucketed = False
        self._bucket_plan = None
        self._mesh = None

    # -- eligibility ----------------------------------------------------
    def _check(self) -> Optional[str]:
        mod = self._mod
        ex = mod._exec
        if ex is None or not mod.optimizer_initialized:
            return "module not bound/optimized"
        if ex._placement is not None:
            return "model-parallel placement executes op-by-op"
        kv = mod._kvstore
        if kv is not None:
            from ..kvstore import KVStoreDist

            if isinstance(kv, KVStoreDist):
                return "dist kvstore: server-side aggregation is per-batch"
            if getattr(kv, "_compression_params", None):
                return "gradient compression changes push numerics"
        for name in ex._grad_names:
            if ex._grad_req.get(name) == "add":
                return "grad_req='add' accumulates across calls"
        updater = mod._active_updater()
        if updater is None:
            return "no local updater"
        dp = getattr(mod, "_dp", None)
        if dp is not None and int(dp.mesh.devices.size) > 1:
            # multi-context DP is only inside the bulk contract through
            # the bucketed shard_map reduce (explicit dp sharding; the
            # per-batch path re-places cells instead)
            from ..parallel import buckets as _buckets

            if tuple(dp.mesh.axis_names) != ("dp",):
                return "multi-context DP mesh is not pure dp"
            if _buckets.bucket_cap_bytes() == 0:
                return ("multi-context DP bulk needs the bucketed "
                        "reduce (MXNET_KVSTORE_BUCKET_BYTES=0 set)")
            n_dp = int(dp.mesh.devices.size)
            for d in list(mod._data_shapes) + list(mod._label_shapes or []):
                if d.shape[0] % n_dp:
                    return ("batch %d not divisible by dp=%d"
                            % (d.shape[0], n_dp))
        return None

    def available(self) -> bool:
        if not self._checked:
            self._reason = self._check()
            self._checked = True
            if self._reason is not None:
                _log.info("bulk fit disabled: %s (per-batch path)",
                          self._reason)
        return self._reason is None

    # -- build ----------------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        # persistent XLA compilation cache (compile_cache.py):
        # the bulk scan is the big program a restarted fit re-pays
        from ..compile_cache import enable as _cc_enable

        _cc_enable()

        mod = self._mod
        ex = mod._exec
        updater = mod._active_updater()
        opt = updater.optimizer

        # bucketed backward-overlapped gradient exchange: a pure-dp
        # multi-device module (Module(context=[...])) compiles the scan
        # body through shard_map with per-bucket reductions in reverse
        # layer order (parallel/buckets.py) instead of the partitioner's
        # combined all-reduce — Module.fit gets the same overlapped
        # schedule as the FusedTrainStep bench path.
        from ..parallel import buckets as _buckets

        dp = getattr(mod, "_dp", None)
        mesh = getattr(dp, "mesh", None)
        n_dp = int(mesh.devices.size) if mesh is not None else 1
        bucketed = (mesh is not None
                    and tuple(mesh.axis_names) == ("dp",) and n_dp > 1
                    and _buckets.bucket_cap_bytes() != 0)

        symbol = mod._symbol
        eval_fn = build_graph_eval(symbol)
        io_names = list(mod._data_names) + list(mod._label_names)
        grad_names = [n for n in ex._grad_names if n not in io_names]
        self._io_names = io_names
        self._trainable = [(i, n) for i, n in enumerate(mod._param_names)
                           if n in set(grad_names)]
        # materialize optimizer state for every trainable param now, so
        # its structure is a static template for the scan carry
        for i, name in self._trainable:
            if i not in updater.states:
                updater.states[i] = opt.create_state_multi_precision(
                    i, ex.arg_dict[name])
                updater.states_synced[i] = True
        self._state_templates = [updater.states[i]
                                 for i, _ in self._trainable]
        arg_dtypes = {n: ex.arg_dict[n].dtype for n in io_names}
        aux_dtypes = {n: c.dtype for n, c in ex.aux_dict.items()}
        trainable = self._trainable
        templates = self._state_templates
        n_outs = len(symbol.list_outputs())

        if bucketed:
            # every data/label batch dim must split evenly over dp
            for nm in io_names:
                if ex.arg_dict[nm].shape[0] % n_dp:
                    bucketed = False
        plan, tuning = _buckets.plan_with_tuning(
            [(name, tuple(ex.arg_dict[name].shape),
              ex.arg_dict[name].dtype) for _i, name in trainable]) \
            if bucketed else (None, None)
        # hierarchical impl: per-host grouping along the dp axis
        hier_local_n = _buckets.host_local_count(mesh) \
            if bucketed and _buckets.impl_name() == "hierarchical" \
            else None
        self._bucketed = bucketed
        self._bucket_plan = plan
        self._bucket_tuning = tuning

        def one_step(params, aux_vals, state_leaves, data_parts, key_root,
                     ctr, lr):
            args = dict(params)
            for n, v in zip(io_names, data_parts):
                args[n] = v.astype(arg_dtypes[n]) \
                    if v.dtype != arg_dtypes[n] else v
            key = jax.random.fold_in(key_root, ctr)
            if bucketed:
                # decorrelate per-device random ops (dropout masks)
                key = jax.random.fold_in(key, lax.axis_index("dp"))
            diff = {k: args[k] for k in grad_names}
            rest = {k: v for k, v in args.items() if k not in diff}

            def pure(d):
                return eval_fn({**rest, **d}, aux_vals, key, True)

            # MXNET_BACKWARD_DO_MIRROR honored inside the scan body too
            from ..remat import maybe_checkpoint

            res, vjp_fn = jax.vjp(maybe_checkpoint(pure), diff)
            outs = res[0]
            cots = [jnp.ones_like(o) for o in outs]
            zero_rest = jax.tree.map(jnp.zeros_like, res[1:])
            (grads,) = vjp_fn((cots,) + tuple(zero_rest))

            if bucketed:
                # per-device partial grads -> global grads, one psum per
                # reverse-layer-order bucket (cotangents are ones, so
                # the global gradient is the plain cross-device sum;
                # batch-normalized ops already divided by the GLOBAL
                # count under the cross-device context)
                grads = {**dict(grads),
                         **_buckets.bucketed_reduce(
                             dict(grads), plan, "dp", n=n_dp,
                             mean=False, local_n=hier_local_n)}

            # ---- optimizer via trace adapter ----
            saved = (opt.lr_scheduler, opt.__dict__.get("lr"),
                     opt._index_update_count, opt.num_update)
            new_params = dict(params)
            new_leaves: List[Any] = []
            try:
                opt.lr_scheduler = None
                opt.lr = lr
                # t = the scan counter (1-based), per-step, traced
                opt._index_update_count = _TracedCounts(ctr)
                opt._update_count = lambda idx: None  # instance shadow
                leaves_iter = iter(state_leaves)
                for pos, (i, name) in enumerate(trainable):
                    w = NDArray.from_raw(args[name])
                    g = NDArray.from_raw(grads[name])
                    st, cells = _rebuild_state(templates[pos], leaves_iter)
                    opt.update_multi_precision(i, w, g, st)
                    new_params[name] = w._data
                    for c in cells:
                        new_leaves.append(c._data)
            finally:
                (opt.lr_scheduler, lr_restore, opt._index_update_count,
                 opt.num_update) = saved
                opt.__dict__.pop("_update_count", None)
                if lr_restore is not None:
                    opt.lr = lr_restore
                else:  # never leak a tracer into the live optimizer
                    opt.__dict__.pop("lr", None)

            new_aux = dict(aux_vals)
            for k, v in res[1].items():
                new_aux[k] = v.astype(aux_dtypes[k]) \
                    if v.dtype != aux_dtypes[k] else v
            return new_params, new_aux, new_leaves, outs

        if bucketed:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            from ..ops import nn as _nn_ops

            def _local_step(params, aux_vals, state_leaves, data_parts,
                            key_root, ctr, lr):
                # batch-statistics ops (BatchNorm moments, SoftmaxOutput
                # batch/valid normalization) reduce over dp during this
                # trace: per-device program, GLOBAL-batch semantics
                with _nn_ops.cross_device_batch_stats("dp"):
                    return one_step(params, aux_vals, state_leaves,
                                    data_parts, key_root, ctr, lr)

            step_fn = shard_map(
                _local_step, mesh=mesh,
                in_specs=(P(), P(), P(), P("dp"), P(), P(), P()),
                out_specs=(P(), P(), P(), P("dp")),
                check_vma=False)
        else:
            step_fn = one_step

        self._mesh = mesh

        def bulk(params, aux_vals, state_leaves, datas, key_root, ctr0,
                 lr):
            def body(carry, xs):
                params, aux_vals, leaves, ctr = carry
                new_p, new_a, new_l, outs = step_fn(
                    params, aux_vals, leaves, xs, key_root, ctr, lr)
                return (new_p, new_a, new_l, ctr + 1), tuple(outs)

            (fp, fa, fl, _), stacked = lax.scan(
                body, (params, aux_vals, state_leaves, ctr0), datas)
            return fp, fa, fl, stacked

        # recompile tracking + flight-recorder plan header
        # (diagnostics.py): the bulk scan is THE compiled path of
        # Module.fit, so churn here is the recompilation storm that
        # silently doubles epoch time
        from .. import diagnostics as _diag

        plan_meta_v = _buckets.plan_meta(
            plan, tuning["cap_bytes"] if tuning else None,
            tuning=tuning) if bucketed else None
        if bucketed:
            _diag.set_bucket_plan(plan_meta_v, owner=id(self))
        else:
            # owned clear: drop only a stale plan THIS loop stamped,
            # not one a different live bucketed step runs under
            _diag.set_bucket_plan(None, owner=id(self))
        # donate params/aux/optimizer-state (in-place update) AND the
        # K-batch stack (argnum 3): run() builds it fresh every
        # dispatch (jnp.stack), nothing else holds it, so the program
        # reuses K batches of HBM as scratch instead of holding them
        # alongside its intermediates
        from ..remat import remat_policy as _remat_policy

        self._bulk_fn = _diag.instrument_jit(
            "Module.bulk_fit",
            jax.jit(bulk, donate_argnums=(0, 1, 2, 3)),
            meta={"bucket_plan": plan_meta_v,
                  # auditor parity with FusedTrainStep: the declared
                  # policy is cross-checked against the traced program
                  "remat_policy": _remat_policy()})
        self._n_outs = n_outs
        self._built = True

    # -- dispatch -------------------------------------------------------
    def run(self, batches) -> Optional[List[List[NDArray]]]:
        """Run one train step per batch in a single compiled dispatch.
        Returns per-batch output lists, or None when the configuration
        is outside the bulk contract (caller falls back per-batch)."""
        if not self.available():
            return None
        import numpy as _np

        import jax.numpy as jnp

        mod = self._mod
        ex = mod._exec
        try:
            if not self._built:
                self._build()
            io_names = self._io_names
            k = len(batches)
            stacked = []
            for pos, name in enumerate(io_names):
                n_data = len(mod._data_names)
                arrs = []
                for b in batches:
                    src = (b.data[pos] if pos < n_data
                           else b.label[pos - n_data])
                    # async-prefetched batches (io_pipeline) arrive as
                    # device-committed jax arrays: jnp.stack runs on
                    # device, so the K-batch stack never round-trips
                    # through the host — the zero-copy handoff into the
                    # bulk scan
                    arrs.append(src._data if isinstance(src, NDArray)
                                else jnp.asarray(src))
                stacked.append(jnp.stack(arrs))
            import jax as _jax

            dev = ex._ctx.jax_device()
            if self._bucketed:
                # batches arrive committed to one device; the shard_map
                # scan wants them batch-sharded over dp (leading dim is
                # the scan's K).  Skip the put when the stack already
                # landed with that sharding (prefetched dp batches).
                from jax.sharding import NamedSharding, PartitionSpec as _P

                ksh = NamedSharding(self._mesh, _P(None, "dp"))
                stacked = [s if getattr(s, "sharding", None) == ksh
                           else _jax.device_put(s, ksh) for s in stacked]
            else:
                # host-built batches join parameters bound on the
                # executor's context (a no-op when they are there)
                stacked = [_jax.device_put(s, dev) for s in stacked]
            # COMMIT every carried buffer to the device before the first
            # dispatch: jit keys include placement, so uncommitted
            # first-call inputs vs committed (donated-output) later ones
            # would trace the huge program twice
            target = None
            if self._bucketed:
                # shard_map needs every carried buffer replicated over
                # the mesh, not pinned to one device
                from jax.sharding import NamedSharding, PartitionSpec as _P

                target = NamedSharding(self._mesh, _P())

            def _commit(cell):
                if target is not None:
                    cell._data = _jax.device_put(cell._data, target)
                elif getattr(cell._data, "committed", True) is not True:
                    cell._data = _jax.device_put(cell._data, dev)
                return cell._data

            params = {n: _commit(c) for n, c in ex.arg_dict.items()
                      if n not in io_names}
            aux_vals = {n: _commit(c) for n, c in ex.aux_dict.items()}
            updater = mod._active_updater()
            leaves: List[Any] = []
            for i, _ in self._trainable:
                flat: List[Any] = []
                _flatten_state(updater.states[i], flat)
                leaves.extend(_commit(c) for c in flat)
            from .. import random as _random

            key_root = _random._next_key()
            opt = updater.optimizer
            # effective base lr at this dispatch (per-param lr_mult is
            # applied inside the traced update); scheduler granularity
            # quantizes to K batches
            lr = _np.float32(opt.lr_scheduler(opt.num_update)
                             if opt.lr_scheduler else opt.lr)
            ctr0 = jnp.asarray(opt.num_update + 1, dtype=jnp.int32)
            from .. import traceview as _traceview

            with _traceview.step_window("Module.bulk_fit", k=k) as _tvw:
                (new_params, new_aux, new_leaves,
                 stacked_outs) = self._bulk_fn(
                    params, aux_vals, leaves, tuple(stacked), key_root,
                    ctr0, jnp.asarray(lr))
                if _tvw is not None:
                    _tvw.block(stacked_outs)
        except Exception as exc:
            # The program donates param/aux/state buffers: a TRACE/
            # compile failure never consumed them (safe fallback), but a
            # failure during EXECUTION may have — falling back onto
            # deleted buffers would corrupt training, so that case must
            # surface, not degrade.
            donated_gone = any(
                getattr(c._data, "is_deleted", lambda: False)()
                for c in list(ex.arg_dict.values()) +
                list(ex.aux_dict.values()))
            if donated_gone:
                raise RuntimeError(
                    "bulk fit dispatch failed AFTER its donated input "
                    "buffers were consumed; parameter state is "
                    "unrecoverable — rerun with per-batch fit (no "
                    "set_bulk_size)") from exc
            self._reason = "bulk trace/dispatch failed: %r" % (exc,)
            self._checked = True
            _log.warning("bulk fit disabled: %s (per-batch path)",
                         self._reason)
            return None

        if self._bucketed:
            from ..parallel import buckets as _buckets

            _buckets.stamp_profiler(self._bucket_plan)
        for name, val in new_params.items():
            cell = ex.arg_dict[name]
            cell._data = val
            cell._vt = object()
        for name, val in new_aux.items():
            cell = ex.aux_dict[name]
            cell._data = val
            cell._vt = object()
        it = iter(new_leaves)
        for i, _ in self._trainable:
            flat: List[Any] = []
            _flatten_state(updater.states[i], flat)
            for c in flat:
                c._data = next(it)
                c._vt = object()
        # host-side schedule bookkeeping: K real updates happened
        for i, _ in self._trainable:
            opt._index_update_count.setdefault(i, opt.begin_num_update)
            opt._index_update_count[i] += k
            opt.num_update = max(opt._index_update_count[i],
                                 opt.num_update)
        out = []
        for step in range(k):
            out.append([NDArray.from_raw(stacked_outs[j][step], ex._ctx)
                        for j in range(self._n_outs)])
        return out
