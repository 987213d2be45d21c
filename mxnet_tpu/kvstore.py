"""KVStore — the data-parallel communication layer.

ref: include/mxnet/kvstore.h:47-382, src/kvstore/kvstore.cc:38-77,
kvstore_local.h, comm.h.

Backends:
  * ``local`` / ``device``  — in-process reduce over the values pushed for a
    key (the reference's CommCPU tree-reduce / CommDevice GPU reduce,
    src/kvstore/comm.h:102,484, collapse into one jnp sum: XLA fuses it).
  * ``tpu``                 — same API; multi-key dense pushes merge through
    ONE compiled bucketed-reduction program (KVStoreTPU: reverse-key-order
    size-capped buckets, parallel/buckets.py — the same partitioner the
    in-graph FusedTrainStep exchange uses), with per-bucket comms spans +
    byte counters.  Inside jitted train steps the exchange rides ICI as
    per-bucket ``lax.psum`` (SURVEY.md §2.3: "XLA AllReduce over ICI …
    replacing CommDevice+NCCL").
  * ``dist_sync`` / ``dist_async`` / ``dist_device_sync`` — multi-process
    parameter-server semantics over ``jax.distributed`` land with the
    multi-host milestone; single-process creation works now (maps to local
    reduce, rank 0 of 1) so launcher scripts run unmodified.

Semantics preserved from the reference:
  * push accumulates (sums) all values pushed for a key; pull broadcasts
  * ``set_updater`` moves the optimizer into the store
    (update_on_kvstore path, ref: kvstore_local.h updater_)
  * row_sparse_pull gathers only the requested rows on device and returns
    a RowSparseNDArray (ref: kvstore_dist.h:258 PullRowSparseImpl)
"""
from __future__ import annotations

import contextlib
import pickle
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as _np

from .base import MXNetError
from .ndarray import NDArray
from . import optimizer as _opt

__all__ = ["KVStore", "create"]


def _as_list(x):
    return x if isinstance(x, (list, tuple)) else [x]


def _payload_dtype(value) -> Optional[str]:
    """dtype of the first array in a (possibly nested) payload —
    flight-recorder metadata only, never raises."""
    try:
        v = value
        while isinstance(v, (list, tuple)):
            if not v:
                return None
            v = v[0]
        dt = getattr(v, "dtype", None)
        return None if dt is None else str(dt)
    except Exception:
        return None


def _comms_span(prof: bool, name: str, args: dict):
    """The profiler span for one instrumented verb, or a no-op context
    when no profiling session is running — keeps each verb's _do_* call
    at exactly one site."""
    if not prof:
        return contextlib.nullcontext()
    from . import profiler as _profiler

    return _profiler.span(name, cat="comms", args=args)


def _feed_bytes_metric(op: str, nbytes: int) -> None:
    """Cumulative kvstore byte counter (metric name/help/guard live in
    diagnostics.feed_kvstore_bytes); the import guard keeps telemetry
    from ever failing the collective it measures."""
    try:
        from . import diagnostics as _diag

        _diag.feed_kvstore_bytes(op, nbytes)
    except Exception:
        pass


def _payload_nbytes(value) -> int:
    """Approximate wire bytes of a push/pull payload: NDArrays (dense:
    whole buffer; row-sparse: touched rows + indices — only those
    travel, ref: kvstore_dist.h:444 EncodeRowSparseKey) or nested lists
    of them.  Telemetry only — never raises."""
    try:
        from . import profiler as _profiler
        from .ndarray import sparse as _sp

        if value is None:
            return 0
        if isinstance(value, (list, tuple)):
            return sum(_payload_nbytes(v) for v in value)
        if isinstance(value, _sp.RowSparseNDArray):
            return (_profiler.nd_nbytes(value.data) +
                    _profiler.nd_nbytes(value.indices))
        if isinstance(value, NDArray):
            return _profiler.nd_nbytes(value)
    except Exception:
        pass
    return 0


def _all_row_sparse(value) -> bool:
    """True when every leaf of a push payload is row-sparse — those
    pushes account under op=row_sparse_push so wire-pressure dashboards
    can separate hot-row traffic from dense traffic.  Telemetry only."""
    try:
        from .ndarray import sparse as _sp

        if isinstance(value, _sp.RowSparseNDArray):
            return True
        if isinstance(value, (list, tuple)) and value:
            return all(_all_row_sparse(v) for v in value)
    except Exception:
        pass
    return False


def _rsp_pull_wire_nbytes(key, out, row_ids) -> int:
    """Deterministic wire bytes of one row_sparse_pull: per key, only
    the DEDUPED requested rows travel — unique_rows * (row payload +
    8-byte int64 row id) — independent of vocab.  This is the number
    ``mxnet_kvstore_bytes_total{op=row_sparse_pull}`` accumulates, the
    counter the hot-row claim is audited against.  Telemetry only —
    never raises."""
    try:
        keys, outs = _key_value(key, out)
        rids = _as_list(row_ids)
        if len(rids) == 1 and len(keys) > 1:
            rids = rids * len(keys)
        total = 0
        for olist, rid in zip(outs, rids):
            o = _as_list(olist)[0]
            rows = _np.unique(
                (rid.asnumpy() if isinstance(rid, NDArray)
                 else _np.asarray(rid)).astype(_np.int64).ravel())
            row_elems = 1
            for d in o.shape[1:]:
                row_elems *= int(d)
            row_bytes = row_elems * _np.dtype(o.dtype).itemsize
            total += int(rows.size) * (row_bytes + 8)
        return total
    except Exception:
        return 0


class KVStore:
    """ref: python/mxnet/kvstore.py KVStore."""

    def __init__(self, kind: str):
        self._kind = kind
        self._store: Dict[Any, NDArray] = {}
        self._updater: Optional[Callable] = None
        self._opt_updater: Optional[_opt.Updater] = None
        self._pending: Dict[Any, NDArray] = {}
        self._compression_params = None

    # -- identity ------------------------------------------------------
    @property
    def type(self) -> str:
        return self._kind

    @property
    def rank(self) -> int:
        import jax

        return getattr(jax, "process_index", lambda: 0)()

    @property
    def num_workers(self) -> int:
        import jax

        return getattr(jax, "process_count", lambda: 1)()

    # -- core API (ref: include/mxnet/kvstore.h Init/Push/Pull) --------
    def init(self, key, value) -> None:
        keys, values = _key_value(key, value)
        for k, v in zip(keys, values):
            self._store[k] = v.copy()

    # -- instrumented verbs: every backend's push/pull stamps a comms
    #    span + cumulative byte counters (ref: the reference profiler's
    #    KVStoreDistDefault events around ZPush/ZPull), and records one
    #    collective flight-recorder entry (diagnostics.py — seq/keys/
    #    bytes/state, the post-mortem ``--health`` reads) --------------
    def push(self, key, value, priority: int = 0) -> None:
        """Sum all pushed values per key (ref: kvstore_local.h Push →
        Comm::Reduce).  Engine-priority overlap is not needed: XLA's async
        dispatch already overlaps these reductions with other work."""
        from . import diagnostics as _diag
        from . import profiler as _profiler

        prof = _profiler.is_running()
        # all-row-sparse pushes account separately: their wire payload
        # is rows-touched-sized, and the hot-row claim needs the counter
        # to witness that independent of dense traffic
        op = "row_sparse_push" if _all_row_sparse(value) else "push"
        if not prof and not _diag.flight_enabled():
            # the byte counter is independent of profiler/flight state:
            # a scraped MXNET_METRICS_FILE must still see comms traffic
            self._do_push(key, value, priority)
            _feed_bytes_metric(op, self._push_wire_nbytes(key, value))
            return
        nbytes = self._push_wire_nbytes(key, value)
        with _diag.record_collective(op, keys=key, nbytes=nbytes,
                                     dtype=_payload_dtype(value),
                                     args={"type": self._kind}), \
                _comms_span(prof, "KVStore::Push",
                            {"bytes": nbytes, "type": self._kind}):
            self._do_push(key, value, priority)
        if prof:
            _profiler.record_bytes("kvstore:push_bytes", nbytes)
        _feed_bytes_metric(op, nbytes)

    def _push_wire_nbytes(self, key, value) -> int:
        """Bytes one push puts on the wire — the figure
        ``mxnet_kvstore_bytes_total{op=push}`` accumulates.  In-process
        stores move device buffers, so the payload size IS the wire
        size; the dist store overrides this to account the 2-bit codes
        when compression is on (deterministic, so the counter and the
        flight entry can record it before the encode happens)."""
        return _payload_nbytes(value)

    def pull(self, key, out=None, priority: int = 0,
             ignore_sparse: bool = True) -> None:
        from . import diagnostics as _diag
        from . import profiler as _profiler

        prof = _profiler.is_running()
        if not prof and not _diag.flight_enabled():
            self._do_pull(key, out, priority, ignore_sparse)
            _feed_bytes_metric("pull", _payload_nbytes(out))
            return
        nbytes = _payload_nbytes(out)
        with _diag.record_collective("pull", keys=key, nbytes=nbytes,
                                     dtype=_payload_dtype(out),
                                     args={"type": self._kind}), \
                _comms_span(prof, "KVStore::Pull",
                            {"bytes": nbytes, "type": self._kind}):
            self._do_pull(key, out, priority, ignore_sparse)
        if prof:
            _profiler.record_bytes("kvstore:pull_bytes", nbytes)
        _feed_bytes_metric("pull", nbytes)

    def pushpull(self, key, value, out=None, priority: int = 0) -> None:
        """The allreduce verb: push + pull in one call (the in-graph
        ``tpu`` store does the same exchange as a fused psum)."""
        from . import diagnostics as _diag
        from . import profiler as _profiler

        prof = _profiler.is_running()
        if not prof and not _diag.flight_enabled():
            self._do_push(key, value, priority)
            self._do_pull(key, out if out is not None else value,
                          priority, True)
            _feed_bytes_metric("allreduce", _payload_nbytes(value))
            return
        nbytes = _payload_nbytes(value)
        with _diag.record_collective("allreduce", keys=key, nbytes=nbytes,
                                     dtype=_payload_dtype(value),
                                     args={"type": self._kind}), \
                _comms_span(prof, "KVStore::AllReduce",
                            {"bytes": nbytes, "type": self._kind}):
            self._do_push(key, value, priority)
            self._do_pull(key, out if out is not None else value,
                          priority, True)
        if prof:
            _profiler.record_bytes("kvstore:allreduce_bytes", nbytes)
        _feed_bytes_metric("allreduce", nbytes)

    def row_sparse_pull(self, key, out=None, priority=0,
                        row_ids=None) -> None:
        from . import diagnostics as _diag
        from . import profiler as _profiler

        prof = _profiler.is_running()
        nbytes = _rsp_pull_wire_nbytes(key, out, row_ids)
        if not prof and not _diag.flight_enabled():
            self._do_row_sparse_pull(key, out, priority, row_ids)
            _feed_bytes_metric("row_sparse_pull", nbytes)
            return
        with _diag.record_collective("row_sparse_pull", keys=key,
                                     nbytes=nbytes,
                                     dtype=_payload_dtype(out),
                                     args={"type": self._kind}), \
                _comms_span(prof, "KVStore::PullRowSparse",
                            {"bytes": nbytes, "type": self._kind}):
            self._do_row_sparse_pull(key, out, priority, row_ids)
        if prof:
            _profiler.record_bytes("kvstore:row_sparse_pull_bytes",
                                   nbytes)
        _feed_bytes_metric("row_sparse_pull", nbytes)

    def _do_push(self, key, value, priority: int = 0) -> None:
        from .ndarray import sparse as _sp

        keys, values = _key_value(key, value)
        for k, vlist in zip(keys, values):
            vs = _as_list(vlist)
            merged = vs[0]
            if len(vs) > 1:
                if all(isinstance(v, _sp.RowSparseNDArray) for v in vs):
                    # row-sparse reduce keeps the merged gradient sparse
                    # (ref: comm.h ReduceRowSparse)
                    for v in vs[1:]:
                        merged = _sp.add(merged, v)
                else:
                    acc = vs[0]._data
                    for v in vs[1:]:
                        acc = acc + v._data
                    merged = NDArray.from_raw(acc, vs[0].context)
            if self._updater is not None:
                if k not in self._store:
                    raise MXNetError("push before init on key %r" % k)
                self._updater(_int_key(k), merged, self._store[k])
            else:
                self._pending[k] = merged

    def _do_pull(self, key, out=None, priority: int = 0,
                 ignore_sparse: bool = True) -> None:
        keys, outs = _key_value(key, out)
        for k, olist in zip(keys, outs):
            if self._updater is not None or k not in self._pending:
                src = self._store.get(k)
                if src is None:
                    src = self._pending.get(k)
            else:
                src = self._pending[k]
            if src is None:
                raise MXNetError("pull on uninitialised key %r" % k)
            for o in _as_list(olist):
                src.copyto(o)

    def _do_row_sparse_pull(self, key, out=None, priority=0,
                            row_ids=None) -> None:
        """Pull only the rows named in ``row_ids`` as a RowSparseNDArray
        (ref: kvstore_dist.h:258 PullRowSparseImpl; kvstore_local.h
        PullRowSparseImpl gathers the requested rows)."""
        import jax.numpy as jnp

        from .ndarray import sparse as _sp

        if row_ids is None:
            raise MXNetError("row_sparse_pull requires row_ids (matches reference)")
        if out is None:
            raise MXNetError("row_sparse_pull requires out (matches reference)")
        keys, outs = _key_value(key, out)
        rids = _as_list(row_ids)
        if len(rids) == 1 and len(keys) > 1:
            rids = rids * len(keys)
        for k, olist, rid in zip(keys, outs, rids):
            # same source precedence as pull(): pending push wins when no
            # updater is installed
            if self._updater is not None or k not in self._pending:
                src = self._store.get(k, self._pending.get(k))
            else:
                src = self._pending[k]
            if src is None:
                raise MXNetError("pull on uninitialised key %r" % k)
            rows = _np.unique(
                (rid.asnumpy() if isinstance(rid, NDArray) else _np.asarray(rid))
                .astype(_np.int64).ravel())
            # device-side gather of only the requested rows — the full table
            # never leaves HBM (ref: kvstore_local.h PullRowSparseImpl)
            taken = jnp.take(src._data, jnp.asarray(rows), axis=0)
            pulled = _sp.RowSparseNDArray._make(
                src.shape, src.dtype,
                {"data": taken, "indices": jnp.asarray(rows)}, src.context)
            for o in _as_list(olist):
                if isinstance(o, _sp.RowSparseNDArray):
                    pulled.copyto(o)
                else:
                    # dense out: caller gets the retained rows densified
                    pulled.todense().copyto(o)

    def set_gradient_compression(self, compression_params) -> None:
        """Validate the params, then refuse for in-process stores —
        silently storing them (the pre-round-13 behavior) made callers
        believe their gradients were compressed when NOTHING was: only
        dist stores put bytes on a wire to compress (the reference's
        own type check, python/mxnet/kvstore.py set_gradient_compression
        raises for local stores).  The launcher-less ``dist_*``
        fallback (single process, no wire) validates and warns instead:
        the degrade-to-local contract keeps launcher scripts runnable,
        and compression there is semantically a no-op, not a lie."""
        from .gradient_compression import GradientCompression

        params = dict(compression_params or {})
        # invalid type/threshold raise HERE, for every store kind
        GradientCompression(type=params.get("type", "2bit"),
                            threshold=float(params.get("threshold", 0.5)))
        if "dist" not in self._kind:
            raise MXNetError(
                "gradient compression is not supported for %r kvstore: "
                "only dist stores compress pushes on the wire (in-"
                "process reduces never serialize a payload).  Create a "
                "dist_sync/dist_async store under a PS launcher to "
                "compress for real." % self._kind)
        import logging

        logging.getLogger(__name__).warning(
            "set_gradient_compression on a launcher-less %r store: "
            "single process, no wire — params validated and ignored",
            self._kind)
        self._compression_params = params

    # -- updater / optimizer (ref: kvstore.h set_updater) --------------
    def set_updater(self, updater: Callable) -> None:
        self._updater = updater

    def set_optimizer(self, optimizer: _opt.Optimizer) -> None:
        """ref: python/mxnet/kvstore.py set_optimizer — on dist stores the
        pickled optimizer travels to servers via SendCommandToServers; in
        process it just installs an Updater."""
        self._opt_updater = _opt.get_updater(optimizer)
        self._updater = self._opt_updater

    # -- cluster control (ref: kvstore.h Barrier/SendCommandToServers) --
    def barrier(self) -> None:
        pass  # single-process: no-op; multi-host lands with jax.distributed

    def send_command_to_servers(self, head: int, body: str) -> None:
        pass

    def get_optimizer_states_bytes(self, dump_optimizer: bool = False
                                   ) -> bytes:
        """Optimizer/momenta state as ONE opaque blob — what the
        checkpoint layer (mxnet_tpu/checkpoint.py) shards per rank.
        The dist store overrides this to gather every server shard."""
        if self._opt_updater is None:
            raise MXNetError("no optimizer state to save")
        return self._opt_updater.get_states(dump_optimizer)

    def set_optimizer_states_bytes(self, states: bytes) -> None:
        if self._opt_updater is None:
            raise MXNetError("set_optimizer before loading states")
        self._opt_updater.set_states(states)

    def save_optimizer_states(self, fname: str, dump_optimizer: bool = False) -> None:
        with open(fname, "wb") as f:
            f.write(self.get_optimizer_states_bytes(dump_optimizer))

    def load_optimizer_states(self, fname: str) -> None:
        with open(fname, "rb") as f:
            self.set_optimizer_states_bytes(f.read())


class KVStoreTPU(KVStore):
    """The ``kvstore('tpu')`` fast path: multi-key dense pushes merge
    through ONE compiled bucketed-reduction program.

    The reference reduced each key separately (comm.h tree-reduce /
    KVStoreNCCL per-key ring); here the whole gradient set pushed in one
    call is partitioned into reverse-key-order, size-capped buckets
    (parallel/buckets.py — the same partitioner the in-graph
    FusedTrainStep path uses), each bucket reduced as one fused op, with
    per-bucket comms spans + byte counters stamped through the telemetry
    layer.  Single-key, single-value and sparse pushes keep the base
    store's semantics unchanged.
    """

    def __init__(self):
        super().__init__("tpu")
        self._fused_cache: Dict = {}
        self._plan_cache: Dict = {}

    def _do_push(self, key, value, priority: int = 0) -> None:
        from .ndarray import sparse as _sp

        keys, values = _key_value(key, value)
        dense = []
        for k, vlist in zip(keys, values):
            vs = _as_list(vlist)
            if len(vs) > 1 and all(
                    isinstance(v, NDArray)
                    and not isinstance(v, _sp.RowSparseNDArray)
                    for v in vs):
                dense.append((k, vs))
        from .parallel import buckets as _buckets

        if (len(dense) < 2 or len(dense) != len(keys)
                or _buckets.bucket_cap_bytes() == 0
                or len({len(vs) for _k, vs in dense}) != 1):
            # nothing to bucket across (or MXNET_KVSTORE_BUCKET_BYTES=0
            # disabled bucketing, or ragged device-copy counts the flat
            # concat cannot stack): base per-key reduce
            return super()._do_push(key, value, priority)
        merged = self._fused_reduce(dense)
        for (k, _vs), m in zip(dense, merged):
            if self._updater is not None:
                if k not in self._store:
                    raise MXNetError("push before init on key %r" % k)
                self._updater(_int_key(k), m, self._store[k])
            else:
                self._pending[k] = m

    def _fused_reduce(self, items) -> List[NDArray]:
        """Reduce every key's device copies in one compiled program,
        bucket by bucket (reverse key order), and stamp per-bucket
        telemetry."""
        import jax
        import jax.numpy as jnp

        from .parallel import buckets as _buckets

        from . import env as _envmod

        entries = [(pos, tuple(vs[0].shape), vs[0].dtype)
                   for pos, (_k, vs) in enumerate(items)]
        # cache the resolved plan per (entries, tuning-env) state: a
        # tuned-plan file must not be re-read on EVERY push, but env
        # changes between pushes still take effect (same reactivity the
        # bucket_cap_bytes() read always had)
        plan_key = (tuple((p, s, str(d)) for p, s, d in entries),
                    _envmod.get_str("MXNET_AUTOTUNE_PLAN"),
                    _envmod.get_str("MXNET_AUTOTUNE_DIR"),
                    _buckets.bucket_cap_bytes())
        cached = self._plan_cache.get(plan_key)
        if cached is None:
            cached = _buckets.plan_with_tuning(entries, None)
            self._plan_cache[plan_key] = cached
        plan, _tuning = cached
        sig = (tuple((len(vs), tuple(vs[0].shape), str(vs[0].dtype))
                     for _k, vs in items),
               tuple((b.keys, b.dtype) for b in plan))
        fn = self._fused_cache.get(sig)
        if fn is None:
            shapes = [tuple(vs[0].shape) for _k, vs in items]

            def reduce_all(stacks):
                out = [None] * len(stacks)
                for b in plan:
                    flat = jnp.concatenate(
                        [stacks[pos].reshape(stacks[pos].shape[0], -1)
                         for pos in b.keys], axis=1) \
                        if len(b.keys) > 1 else \
                        stacks[b.keys[0]].reshape(
                            stacks[b.keys[0]].shape[0], -1)
                    red = flat.sum(axis=0)
                    off = 0
                    for pos in b.keys:
                        sz = int(_np.prod(shapes[pos])) if shapes[pos] else 1
                        out[pos] = red[off:off + sz].reshape(shapes[pos])
                        off += sz
                return out

            fn = jax.jit(reduce_all)
            self._fused_cache[sig] = fn
        stacks = [jnp.stack([v._data for v in vs]) for _k, vs in items]
        reduced = fn(stacks)
        _buckets.stamp_profiler(plan, store_type="tpu")
        from . import profiler as _profiler

        if _profiler.is_running():
            # the push's place in a merged chrome trace: one span a
            # bucket, as the reference stamped its per-key reductions
            impl = _buckets.impl_name()
            for i, b in enumerate(plan):
                with _profiler.span(
                        "KVStore::AllReduceBucket", cat="comms",
                        args={"bucket": i, "bytes": int(b.nbytes),
                              "n_grads": len(b.keys), "impl": impl,
                              "type": "tpu"}):
                    pass
        return [NDArray.from_raw(r, items[i][1][0].context)
                for i, r in enumerate(reduced)]


def _int_key(k):
    try:
        return int(k)
    except (TypeError, ValueError):
        return k


def _key_value(key, value):
    """Align keys and values: returns parallel lists; each value entry is an
    NDArray or a per-device list of NDArrays (ref: kvstore_local.h
    GroupKVPairs)."""
    if isinstance(key, (list, tuple)):
        return list(key), list(value)
    return [key], [value]


class PSConnectionLost(MXNetError, ConnectionError):
    """A PS peer vanished mid-exchange.  Subclasses both MXNetError
    (the API's error surface, existing handlers keep working) and
    ConnectionError (the retry layer's transport-failure signal)."""


class KVStoreDist(KVStore):
    """Multi-process parameter-server worker
    (ref: src/kvstore/kvstore_dist.h:49 KVStoreDist).

    Keys shard across servers by crc32 (the EncodeDefaultKey analogue,
    kvstore_dist.h:229). ``dist_sync``: servers aggregate each key until
    all workers contributed, then apply the (server-side) optimizer —
    a worker's pull after its push blocks until that round is applied.
    ``dist_async``: every push applies immediately
    (kvstore_dist_server.h:266)."""

    def __init__(self, kind: str):
        super().__init__(kind)
        import os
        import threading as _threading

        from . import _ps

        self._ps = _ps
        self._sync = "async" not in kind
        self._recovery = bool(os.environ.get("DMLC_PS_IS_RECOVERY"))
        sched = _ps.connect_scheduler()
        reg = {"op": "register_worker"}
        if self._recovery:
            # is_recovery rejoin (ref: kvstore_dist.h:56): reclaim the
            # previous rank; startup barriers are skipped so the healthy
            # cohort is never blocked on the rejoining node
            reg["recovery"] = int(os.environ.get("DMLC_WORKER_ID", "0"))
        resp = sched.request(reg)
        self._rank = resp["rank"]
        # per-rank trace dumps (profile_rank{K}.json, pid=rank) key off
        # the scheduler-assigned rank, not the launcher env
        from . import profiler as _profiler

        _profiler.set_rank(self._rank, _ps.env_cluster()[3])
        # barrier catch-up for recovery: skip exactly as many barriers
        # as the cohort has already completed, then participate normally
        # (a blanket skip would deadlock healthy workers at the next
        # barrier; ref: is_recovery skips only the *startup* barrier)
        self._barrier_skip = resp.get("barrier_gen", 0) \
            if self._recovery else 0
        self._server_addrs = [tuple(a) for a in resp["servers"]]
        self._server_clients = [_ps.Client(a) for a in self._server_addrs]
        self._reconnect_lock = _threading.Lock()
        # per-key monotonic push sequence: rides every push frame so a
        # retried (resent) push is deduped server-side instead of
        # double-counted into the sync aggregation round
        self._pseq: Dict[Any, int] = {}
        self._pseq_lock = _threading.Lock()
        self._sched = sched
        _, _, _, nw = _ps.env_cluster()
        self._nw = nw
        self._gc = None
        self._closed = False
        if self._recovery:
            # re-seed the per-key push counters from every server's
            # pushed_by high water: a rejoined worker restarting at
            # pseq=1 would otherwise have its every push deduped as a
            # stale resend (and the fleet's sync rounds would starve)
            for c in self._server_clients:
                resp = self._req(c, {"op": "worker_hello",
                                     "worker": self._rank,
                                     "recovery": True})
                for key, count in (resp.get("pseq") or {}).items():
                    self._pseq[key] = max(self._pseq.get(key, 0),
                                          int(count))
        self._heartbeat = _ps.Heartbeat("worker", self._rank)
        from concurrent.futures import ThreadPoolExecutor

        self._pool = ThreadPoolExecutor(max_workers=16)
        if not self._sync:
            if self._rank == 0:
                for c in self._server_clients:
                    self._req(c, {"op": "set_sync", "sync": False})
            # every rank reaches this barrier => servers switched mode
            # before any worker's first push can race the set_sync
            self.barrier()
        # env-toggled wire compression: every worker takes the same
        # path (rank 0 configures the servers, the barrier inside
        # set_gradient_compression syncs the fleet before any push)
        from . import env as _envmod

        gc_type = _envmod.get_str("MXNET_GRADIENT_COMPRESSION")
        if gc_type:
            self.set_gradient_compression({
                "type": gc_type,
                "threshold": _envmod.get_float(
                    "MXNET_GRADIENT_COMPRESSION_THRESHOLD")})
        import atexit

        atexit.register(self.close)

    # -- identity ------------------------------------------------------
    @property
    def rank(self) -> int:
        return self._rank

    @property
    def num_workers(self) -> int:
        return self._nw

    def _server_for(self, key):
        return self._server_clients[self._server_idx(key)]

    def _server_idx(self, key) -> int:
        import zlib

        return zlib.crc32(str(key).encode()) % len(self._server_clients)

    @staticmethod
    def _req(client, msg):
        """Request + error check (failed server commands must not be
        silently swallowed)."""
        resp = client.request(msg)
        if resp is None:
            # EOF mid-exchange: the peer died.  Poison the connection
            # (nothing can be paired on this stream anymore) and raise
            # the dual-typed error — MXNetError for API compat,
            # ConnectionError so _req_server's retry treats it as the
            # transport failure it is.
            client.broken = True
            try:
                client.sock.close()
            except OSError:
                pass
            raise PSConnectionLost("server connection lost during %r"
                                   % msg.get("op"))
        if resp.get("error") or resp.get("ok") is False:
            raise MXNetError("server rejected %r: %s"
                             % (msg.get("op"),
                                resp.get("error", "unknown error")))
        return resp

    # ops safe to resend on a transport failure: init is idempotent
    # (set-if-absent), pulls are reads, pushes dedupe server-side via
    # pseq.  Control ops (set_optimizer, stop, ...) keep fail-fast
    # semantics — a lost 'stop' ack retried could double-count a
    # worker's shutdown and end the server under its peers.
    # the sdc ops are idempotent reads/overwrites (a report resent for
    # the same (step, worker) just rewrites the same vector)
    _RETRY_OPS = frozenset(("init", "push", "pull", "pull_rows",
                            "sdc_report", "sdc_gather", "sdc_digest"))

    def _req_server(self, idx: int, msg):
        """Server request with bounded retry: on a transport failure
        (timeout / dead connection / dropped response) back off with
        jitter (MXNET_PS_RETRY_BACKOFF_S), reconnect, and resend up to
        MXNET_PS_RETRY_MAX times — the failure-absorption ps-lite gives
        the reference through its resend timers.  Server-side errors
        (error frames) are NOT retried: the server is alive and said
        no."""
        import time as _time

        op = msg.get("op")
        retries = self._ps.retry_max() if op in self._RETRY_OPS else 0
        delays = [0.0] + self._ps.backoff_delays(retries)
        last_exc = None
        for attempt, delay in enumerate(delays):
            if delay:
                _time.sleep(delay)
            try:
                client = self._server_clients[idx]
                if client.broken:
                    client = self._reconnect(idx)
                return self._req(client, msg)
            except (ConnectionError, OSError) as e:
                last_exc = e
                if attempt >= len(delays) - 1:
                    break
                try:
                    from . import diagnostics as _diag

                    _diag.metrics.counter(
                        "mxnet_ps_retries_total",
                        help="PS requests resent after transport "
                             "failures", labels={"op": str(op)}).inc()
                except Exception:
                    pass
                import logging as _logging

                _logging.getLogger(__name__).warning(
                    "PS %r to server %d failed (%s) — retry %d/%d after "
                    "%.2fs backoff", op, idx, e, attempt + 1, retries,
                    delays[attempt + 1])
        raise MXNetError(
            "PS %r to server %d failed after %d attempt(s): %s"
            % (op, idx, len(delays), last_exc)) from last_exc

    def _reconnect(self, idx: int):
        """Replace a broken server connection (thread-safe: concurrent
        fanout threads that both saw the break reconnect once)."""
        with self._reconnect_lock:
            client = self._server_clients[idx]
            if not client.broken:
                return client  # another thread already reconnected
            try:
                client.close()
            except OSError:
                pass
            fresh = self._ps.Client(self._server_addrs[idx])
            self._server_clients[idx] = fresh
            return fresh

    def _next_pseq(self, key) -> int:
        with self._pseq_lock:
            n = self._pseq.get(key, 0) + 1
            self._pseq[key] = n
            return n

    def _fanout(self, work):
        """Run per-key request thunks concurrently on the persistent
        pool — keys shard across servers, so independent requests
        overlap instead of paying one RTT each (the reference pipelines
        via async ZPush/ZPull)."""
        if len(work) <= 1:
            return [w() for w in work]
        return list(self._pool.map(lambda w: w(), work))

    # -- core API ------------------------------------------------------
    def init(self, key, value) -> None:
        keys, values = _key_value(key, value)
        self._fanout([
            (lambda k=k, v=v: self._req_server(
                self._server_idx(k),
                {"op": "init", "key": k, "data": _as_list(v)[0].asnumpy()}))
            for k, v in zip(keys, values)])
        self.barrier()

    def _merge(self, vlist):
        """Local multi-device reduce before the wire, keeping row-sparse
        gradients sparse (same reduce the base store uses,
        ref: comm.h ReduceRowSparse)."""
        from .ndarray import sparse as _sp

        vs = _as_list(vlist)
        if all(isinstance(v, _sp.RowSparseNDArray) for v in vs):
            merged = vs[0]
            for v in vs[1:]:
                merged = _sp.add(merged, v)
            return merged
        acc = vs[0]._data
        for v in vs[1:]:
            acc = acc + v._data
        return NDArray.from_raw(acc, vs[0].context)

    def _do_push(self, key, value, priority: int = 0) -> None:
        from .ndarray import sparse as _sp

        keys, values = _key_value(key, value)

        def one(k, vlist):
            merged = self._merge(vlist)
            # pseq makes the push exactly-once under retry: the server
            # acks-without-applying any pseq it already counted
            msg = {"op": "push", "key": k, "worker": self._rank,
                   "pseq": self._next_pseq(k)}
            if isinstance(merged, _sp.RowSparseNDArray):
                # only touched rows travel (ref: kvstore_dist.h:444
                # EncodeRowSparseKey push)
                rows = _np.asarray(merged.indices.asnumpy(),
                                   dtype=_np.int64)
                msg.update(sparse=True, rows=rows,
                           shape=tuple(merged.shape))
                if self._gc is not None and rows.size:
                    # sparse-aware 2-bit encode: the values compress,
                    # the row ids travel exact, and the error feedback
                    # is PER ROW so a hot row's residual follows it
                    # across batches (gradient_compression.compress_rows)
                    codes, _vshape = self._gc.compress_rows(
                        k, rows, merged.data.asnumpy())
                    msg.update(compressed=True, data=codes)
                else:
                    msg["data"] = merged.data.asnumpy()
            elif self._gc is not None:
                codes, shape = self._gc.compress(k, merged.asnumpy())
                msg.update(compressed=True, data=codes, shape=shape)
            else:
                msg["data"] = merged.asnumpy()
            self._req_server(self._server_idx(k), msg)

        self._fanout([
            (lambda k=k, v=v: one(k, v)) for k, v in zip(keys, values)])

    def _do_pull(self, key, out=None, priority: int = 0,
                 ignore_sparse: bool = True) -> None:
        keys, outs = _key_value(key, out)

        def one(k, olist):
            resp = self._req_server(self._server_idx(k),
                                    {"op": "pull", "key": k,
                                     "worker": self._rank})
            src = _np.asarray(resp["data"])
            for o in _as_list(olist):
                o[:] = src.astype(o.dtype, copy=False)

        self._fanout([
            (lambda k=k, o=o: one(k, o)) for k, o in zip(keys, outs)])

    def _do_row_sparse_pull(self, key, out=None, priority=0,
                            row_ids=None) -> None:
        from .ndarray import sparse as _sp

        if row_ids is None or out is None:
            raise MXNetError("row_sparse_pull requires out and row_ids")
        keys, outs = _key_value(key, out)
        rids = _as_list(row_ids)
        if len(rids) == 1 and len(keys) > 1:
            rids = rids * len(keys)
        for k, olist, rid in zip(keys, outs, rids):
            rows = _np.unique(
                (rid.asnumpy() if isinstance(rid, NDArray)
                 else _np.asarray(rid)).astype(_np.int64).ravel())
            resp = self._req_server(self._server_idx(k),
                                    {"op": "pull_rows", "key": k,
                                     "rows": rows, "worker": self._rank})
            import jax.numpy as jnp

            for o in _as_list(olist):
                if isinstance(o, _sp.RowSparseNDArray):
                    data = _np.asarray(resp["data"]).astype(o.dtype,
                                                            copy=False)
                    pulled = _sp.RowSparseNDArray._make(
                        o.shape, o.dtype,
                        {"data": jnp.asarray(data),
                         "indices": jnp.asarray(resp["rows"])}, o.context)
                    pulled.copyto(o)
                else:
                    dense = _np.zeros(o.shape, o.dtype)
                    dense[resp["rows"]] = resp["data"]
                    o[:] = dense

    # -- optimizer travels to the servers ------------------------------
    def set_optimizer(self, optimizer: _opt.Optimizer) -> None:
        """ref: kvstore.py set_optimizer — pickle the optimizer and ship
        it via the server command channel (SendCommandToServers)."""
        if self._rank == 0:
            payload = pickle.dumps(optimizer)
            for c in self._server_clients:
                self._req(c, {"op": "set_optimizer", "payload": payload})
        self.barrier()

    def send_command_to_servers(self, head: int, body: str) -> None:
        """Generic command broadcast to every server — received by the
        server's controller callback (ref: KVStore::SendCommandToServers
        include/mxnet/kvstore.h + MXKVStoreRunServer server_controller;
        server side: kvstore_server.py op == 'command')."""
        for c in self._server_clients:
            self._req(c, {"op": "command", "head": int(head),
                          "body": str(body)})

    def set_gradient_compression(self, compression_params) -> None:
        """Install worker-side encode (error feedback stays per-key on
        THIS worker — the residual is local state, never pushed) and
        ship the config to every server so their decompress matches
        (ref: kvstore_dist.h SetGradientCompression broadcasting the
        params via the command channel)."""
        from .gradient_compression import GradientCompression

        params = dict(compression_params or {})
        self._gc = GradientCompression(
            type=params.get("type", "2bit"),
            threshold=float(params.get("threshold", 0.5)))
        if self._rank == 0:
            for c in self._server_clients:
                self._req(c, {"op": "set_compression",
                              "type": self._gc.type,
                              "threshold": self._gc.threshold})
        self.barrier()

    def _push_wire_nbytes(self, key, value) -> int:
        """With compression on, what travels is the packed 2-bit codes
        of ONE merged array per key — ceil(n/4) bytes — not the dense
        float payload.  Row-sparse pushes account deterministically as
        rows-on-wire: n_rows * (8-byte int64 id + row payload), or the
        exact row ids + 2-bit value codes when compression is on
        (GradientCompression.rows_wire_nbytes) — matching _do_push byte
        for byte.  These are the numbers
        mxnet_kvstore_bytes_total{op=push|row_sparse_push} must report
        for the wire-pressure claim to be auditable."""
        try:
            from .gradient_compression import GradientCompression
            from .ndarray import sparse as _sp

            total = 0
            _keys, values = _key_value(key, value)
            for vlist in values:
                vs = _as_list(vlist)
                if not vs:
                    continue
                merged = vs[0]
                if isinstance(merged, _sp.RowSparseNDArray):
                    n_rows = int(merged.indices.shape[0])
                    row_elems = 1
                    for d in merged.shape[1:]:
                        row_elems *= int(d)
                    if self._gc is not None and n_rows:
                        total += GradientCompression.rows_wire_nbytes(
                            n_rows, row_elems)
                    else:
                        row_bytes = (row_elems *
                                     _np.dtype(merged.dtype).itemsize)
                        total += n_rows * (row_bytes + 8)
                    continue
                if self._gc is None:
                    total += _payload_nbytes(vlist)
                    continue
                n = 1
                for d in merged.shape:
                    n *= int(d)
                total += GradientCompression.wire_nbytes(n)
            return total
        except Exception:
            return _payload_nbytes(value)

    def get_optimizer_states_bytes(self, dump_optimizer: bool = False,
                                   timeout: Optional[float] = None
                                   ) -> bytes:
        """Gather every server shard's optimizer state — keys shard by
        crc32, so each server holds state only for its own keys
        (ref: Trainer.save_states round-tripping the server updater).
        This is the blob the checkpoint layer stores (rank 0 gathers;
        on resume rank 0 restores it into the fresh servers).

        The gather rides FRESH short-lived connections, never the
        shared fanout clients: the watchdog-abort/SIGTERM checkpoint
        hook must not block on a client whose lock is held by the very
        request that is hung (that wait would be the full
        MXNET_PS_REQUEST_TIMEOUT — minutes — against the documented
        exit-within-seconds contract).  ``timeout`` bounds each server
        exchange; the preemption path passes a small one."""
        blobs = {}
        for i, addr in enumerate(self._server_addrs):
            c = self._ps.Client(addr, timeout=timeout)
            try:
                resp = self._req(c, {"op": "save_optimizer_states",
                                     "dump_optimizer": dump_optimizer})
                blobs[i] = resp["data"]
            finally:
                c.close()
        return pickle.dumps({"num_servers": len(blobs), "shards": blobs})

    def set_optimizer_states_bytes(self, states: bytes) -> None:
        payload = pickle.loads(states)
        if not (isinstance(payload, dict) and "shards" in payload
                and "num_servers" in payload):
            # a LOCAL updater blob (flat {key: state} dict, optionally
            # (states, optimizer)): an elastic resume restoring a
            # 1-rank checkpoint onto a dist fleet — re-shard the keys
            # by the same crc32 rule the servers partition with
            payload = self._reshard_local_states(payload)
        if payload["num_servers"] != len(self._server_clients):
            payload = self._reshard_merged_states(payload)
        for i, c in enumerate(self._server_clients):
            self._req(c, {"op": "load_optimizer_states",
                          "data": payload["shards"][i]})

    def _reshard_local_states(self, data) -> dict:
        """Flat updater states -> the per-server-shard wrapper, keys
        partitioned exactly as pushes are (crc32 % num_servers)."""
        optimizer = None
        if isinstance(data, tuple):
            data, optimizer = data
        n = len(self._server_clients)
        per: Dict[int, dict] = {i: {} for i in range(n)}
        for k, v in (data or {}).items():
            per[self._server_idx(k)][k] = v
        return {"num_servers": n, "shards": {
            i: pickle.dumps((per[i], optimizer) if optimizer is not None
                            else per[i]) for i in range(n)}}

    def _reshard_merged_states(self, payload) -> dict:
        """A wrapper saved with a DIFFERENT server count: merge every
        shard's keys and re-partition for this cluster (deterministic —
        crc32 keys land where pushes will look for them)."""
        merged: dict = {}
        optimizer = None
        for blob in payload["shards"].values():
            if not blob:
                continue
            sub = pickle.loads(blob)
            if isinstance(sub, tuple):
                sub, optimizer = sub
            merged.update(sub)
        return self._reshard_local_states(
            (merged, optimizer) if optimizer is not None else merged)

    def save_optimizer_states(self, fname: str,
                              dump_optimizer: bool = False) -> None:
        with open(fname, "wb") as f:
            f.write(self.get_optimizer_states_bytes(dump_optimizer))

    def load_optimizer_states(self, fname: str) -> None:
        with open(fname, "rb") as f:
            self.set_optimizer_states_bytes(f.read())

    # -- sdc fingerprint exchange (mxnet_tpu/sdc.py) -------------------
    def sdc_exchange(self, step: int, fps,
                     timeout: float = 60.0) -> Dict[int, list]:
        """Report this rank's per-key fingerprint vector for ``step``
        and gather every rank's (rendezvous on server 0 — the vectors
        are a few bytes; no key sharding needed).  Returns
        ``{rank: fps}`` with however many ranks reported before the
        timeout — the caller treats a short roster as inconclusive, so
        a straggling or dead peer can never wedge the vote."""
        import time as _time

        self._req_server(0, {"op": "sdc_report", "step": int(step),
                             "worker": self._rank,
                             "fps": [int(v) for v in fps]})
        deadline = _time.monotonic() + max(float(timeout), 0.0)
        got: Dict[int, list] = {}
        while True:
            resp = self._req_server(0, {"op": "sdc_gather",
                                        "step": int(step)})
            got = {int(k): [int(x) for x in v]
                   for k, v in (resp.get("data") or {}).items()}
            if len(got) >= self._nw or _time.monotonic() > deadline:
                return got
            _time.sleep(0.02)

    def sdc_reference(self, keys) -> List[int]:
        """The AUTHORITATIVE fingerprint vector: each key's owning
        server digests its OWN stored copy — the bytes every rank's
        pull delivered — so the vote has a tie-breaking voter that a
        worker-side bit flip cannot touch (server-side-update mode
        makes the store the ground truth).  Raises when any key is
        missing server-side (caller votes without the reference)."""
        by_server: Dict[int, list] = {}
        for k in keys:
            by_server.setdefault(self._server_idx(k), []).append(k)
        digests: Dict[Any, int] = {}
        for idx, ks in sorted(by_server.items()):
            resp = self._req_server(idx, {"op": "sdc_digest",
                                          "keys": list(ks)})
            for k, v in (resp.get("data") or {}).items():
                digests[k] = v
        out = []
        for k in keys:
            v = digests.get(k)
            if v is None:
                raise MXNetError(
                    "sdc_reference: server holds no value for key %r"
                    % (k,))
            out.append(int(v))
        return out

    # -- cluster control -----------------------------------------------
    def barrier(self) -> None:
        """ref: Postoffice::Barrier via the scheduler."""
        if self._barrier_skip > 0:
            # is_recovery catch-up: this barrier was already completed
            # by the cohort before the rejoin
            self._barrier_skip -= 1
            return
        self._sched.request({"op": "barrier", "rank": self._rank},
                            timeout=86400.0)

    def get_dead_nodes(self, timeout: float = 60.0) -> List[str]:
        """Nodes whose heartbeat is older than ``timeout`` seconds, as
        ``role:rank`` strings (ref: ps::Postoffice::GetDeadNodes via
        kvstore_dist.h:113-121 — the reference surfaces liveness through
        the scheduler exactly like this)."""
        resp = self._sched.request({"op": "dead_nodes",
                                    "timeout": timeout})
        return list(resp["dead"]) if resp else []

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._heartbeat.stop()
        self._pool.shutdown(wait=False)
        for c in self._server_clients:
            try:
                c.request({"op": "stop"})
                c.close()
            except OSError:
                pass
        try:
            self._sched.request({"op": "finalize", "role": "worker",
                                 "rank": self._rank})
            self._sched.close()
        except (OSError, ConnectionError):
            pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


_VALID = {"local", "device", "tpu", "nccl", "dist_sync", "dist_async",
          "dist_device_sync", "dist"}


def create(name: str = "local") -> KVStore:
    """ref: src/kvstore/kvstore.cc:38 KVStore::Create. ``dist_*`` with
    DMLC_* cluster env present returns the parameter-server worker; with
    no cluster env it degrades to the single-process store (rank 0 of 1)
    so launcher-less scripts still run."""
    if not isinstance(name, str) or name not in _VALID:
        raise MXNetError("unknown kvstore type %r" % (name,))
    from . import dist as _dist

    # multi-host pod: join the jax.distributed coordination service when
    # the MXNET_COORDINATOR_ADDRESS contract is present (no-op otherwise)
    # so rank/num_workers and pod-wide meshes are real
    _dist.initialize()
    if name.startswith("dist"):
        import os

        from . import kvstore_server

        kvstore_server.init()  # blocks forever in scheduler/server roles
        if os.environ.get("DMLC_PS_ROOT_URI"):
            return KVStoreDist(name)
    if name == "tpu":
        return KVStoreTPU()
    return KVStore(name)
