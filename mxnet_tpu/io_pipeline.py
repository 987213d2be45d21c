"""mx.io_pipeline — sharded multi-process decode pool + double-buffered
async device prefetch: the input pipeline that keeps up with the chip.

One decoding core does not keep up with a chip (how far it falls
short has not been measured on one: ROADMAP W4) — the
fetch path was ``PrefetchingIter`` (io.py), a literal Python port of
dmlc ``ThreadedIter`` double buffering: ONE thread decoding JPEGs while
the GIL serializes everything else.  The reference never ran that way:
``iter_image_recordio_2.cc`` decoded on an OMP pool over a dmlc
InputSplit record shard per worker.  This module is that architecture,
process-based (the GIL is the reason threads don't scale Python
decode):

  ┌────────────┐  shared-memory slots   ┌─────────────┐   bounded q
  │ worker 0   │ ─────────────────────▶ │             │  ┌─────────┐
  │ (records   │   (decoded uint8       │  round-robin│─▶│ device  │─▶ fit /
  │  0,N,2N..) │    batches — never     │  reassembly │  │ prefetch│   run_steps
  ├────────────┤    pickled through     │  (parent)   │  │ thread  │
  │ worker w   │    a pipe)             │             │  └─────────┘
  │ (w,w+N,..) │ ─────────────────────▶ └─────────────┘   device_put k+1
  └────────────┘                                          while k computes

Three pieces:

* :class:`ShardedDecodePool` — N worker *processes* (``MXNET_IO_WORKERS``,
  default cpu_count-1), each owning a disjoint record slice via the
  existing ``num_parts``/``part_index`` idiom (worker w of N under outer
  rank sharding (R, r) reads the strided slice ``r + R*w :: R*N``).
  Decoded batches travel through preallocated shared-memory slots
  (mmap'd files under /dev/shm) — only tiny ``(slot, seq, pad)`` tuples
  cross the queue, never batch bytes.  The parent reassembles a
  DETERMINISTIC round-robin stream (batch k comes from worker k%N), so
  exact-resume and bitwise-reproducibility hold regardless of worker
  timing.  A dead worker's shard is adopted inline by the parent at its
  exact stream position: throughput degrades, the stream stays
  identical, nothing hangs.
* :class:`InputPipeline` — the :class:`~mxnet_tpu.io.DataIter` facade:
  an async device stage (``MXNET_IO_PREFETCH_DEPTH``, default 2 =
  classic double buffering) issues ``jax.device_put`` for batch k+1
  (and k+2) on its own thread while batch k's fused step runs, then
  hands device-committed batches to ``Module.fit`` / ``FusedTrainStep``.
  Placed arrays are marked *disposable* so ``_donate_safe_put``
  (parallel/dp.py) can donate them to the compiled step without a
  defensive copy — and the placement itself is alias-checked against
  the pool's shared-memory slot, so a donated dispatch can never
  consume a pool-owned buffer.
* worker hygiene — workers are HOST-ONLY by contract (no jax, no
  ``device_put``; mxlint MXL007 enforces it statically), fetch through
  the iterators' jax-free ``next_raw`` path, exit when orphaned, and
  every shared-memory segment is unlinked on close/atexit/SIGTERM
  (``python -m mxnet_tpu.io_pipeline --self-test`` proves no /dev/shm
  litter survives a SIGTERM).

Telemetry: per-batch decode wall time feeds ``mxnet_io_decode_seconds``
and per-worker ``io:*`` trace lanes (merge_traces.py shows them
overlapping the compiled step); the consumer-side queue depth feeds
``mxnet_io_queue_depth``; worker deaths feed
``mxnet_io_worker_deaths_total``.  Chaos kind ``slow_decode`` seeds a
straggling worker to prove the pipeline degrades instead of
deadlocking.
"""
from __future__ import annotations

import atexit
import functools
import json
import logging
import mmap
import os
import queue as _queue
import signal
import sys
import tempfile
import threading
import time
import uuid
import weakref
from collections import deque, namedtuple
from typing import Any, Dict, List, Optional

import multiprocessing as _mp

import numpy as _np

from .base import MXNetError
from .context import host_only_children as _host_only_children
from .io import DataBatch, DataIter, _instrumented_fetch

__all__ = [
    "ShardedDecodePool", "InputPipeline",
    "make_ndarray_iter_fn", "make_record_iter_fn",
    "mark_disposable", "take_disposable",
    "IO_WORKER_TID_BASE",
]

_log = logging.getLogger(__name__)

#: /dev/shm filename prefix for pool slots (the hygiene tests scan it)
_SHM_PREFIX = "mxio-"
#: trace-lane base: decode worker w stamps spans on tid BASE+w
IO_WORKER_TID_BASE = 100

_EPOCH_END = object()


def _shm_dir() -> str:
    d = "/dev/shm"
    return d if os.path.isdir(d) else tempfile.gettempdir()


# ---------------------------------------------------------------------------
# slot layout: one shared-memory file holds every array of one batch
# ---------------------------------------------------------------------------
class _SlotSpec:
    """Byte layout of one batch slot, derived from provide_data/
    provide_label (fixed shapes — the pool contract).  Picklable (dtype
    kept as str) so workers rebuild identical views."""

    def __init__(self, data_descs, label_descs):
        self.fields = []  # (is_label, name, shape, dtype_str, off, nbytes)
        off = 0
        for is_label, descs in ((False, data_descs), (True, label_descs)):
            for d in descs:
                dt = _np.dtype(d.dtype)
                nb = int(_np.prod(d.shape)) * dt.itemsize if d.shape \
                    else dt.itemsize
                self.fields.append((is_label, d.name, tuple(d.shape),
                                    dt.str, off, nb))
                off = (off + nb + 63) & ~63  # 64B-align each array
        self.nbytes = max(off, 64)

    def views(self, buf):
        """(data_views, label_views) numpy views over one slot buffer."""
        data: List[_np.ndarray] = []
        label: List[_np.ndarray] = []
        for is_label, _name, shape, dtype, off, _nb in self.fields:
            n = int(_np.prod(shape)) if shape else 1
            a = _np.frombuffer(buf, dtype=_np.dtype(dtype), count=n,
                               offset=off).reshape(shape)
            (label if is_label else data).append(a)
        return data, label


def _map_slot(path: str, nbytes: int):
    """mmap one slot file read-write (creator already sized it)."""
    fd = os.open(path, os.O_RDWR)
    try:
        return mmap.mmap(fd, nbytes)
    finally:
        os.close(fd)


def _host_batch(it):
    """One host batch ``(data_np_list, label_np_list, pad)`` — through
    the iterator's jax-free ``next_raw`` contract when it has one (the
    decode-worker path), otherwise via ``next()`` + numpy conversion
    (parent-side adoption fallback only)."""
    nr = getattr(it, "next_raw", None)
    if nr is not None:
        return nr()
    b = it.next()

    def to_np(a):
        asn = getattr(a, "asnumpy", None)
        return _np.asarray(asn()) if asn is not None else _np.asarray(a)

    return ([to_np(a) for a in b.data], [to_np(a) for a in b.label],
            int(getattr(b, "pad", 0) or 0))


# ---------------------------------------------------------------------------
# worker process body — HOST-ONLY: no jax / device_put / block_until_ready
# in here or below it (mxlint MXL007 lints decode-worker functions)
# ---------------------------------------------------------------------------
def _decode_worker_main(worker_id, iter_fn, num_parts, part_index,
                        slot_files, spec, free_q, result_q, ctrl_q,
                        parent_pid):
    """Decode worker: iterate a disjoint record slice, write each
    decoded batch into a free shared-memory slot, report ``(slot, pad,
    decode_s)``.  Polls everything with timeouts and exits when
    orphaned, so a vanished parent never strands it."""
    try:
        from . import chaos as _chaos
    except Exception:  # chaos must never be load-bearing
        _chaos = None
    it = iter_fn(num_parts=num_parts, part_index=part_index)
    maps = [_map_slot(p, spec.nbytes) for p in slot_files]
    views = [spec.views(m) for m in maps]
    epoch = 0
    exhausted = False
    while True:
        cmd = None
        try:
            cmd = ctrl_q.get_nowait()
        except _queue.Empty:
            if exhausted:
                try:
                    cmd = ctrl_q.get(timeout=0.5)
                except _queue.Empty:
                    if os.getppid() != parent_pid:
                        return
                    continue
        if cmd == "stop":
            return
        if cmd == "reset":
            it.reset()
            epoch += 1
            exhausted = False
            continue
        if exhausted:
            continue
        t0_mono = time.monotonic()  # CLOCK_MONOTONIC: comparable with
        # the parent's clock, so the trace span lands at the TRUE
        # decode time, not at queue-drain time
        try:
            data, label, pad = _host_batch(it)
        except StopIteration:
            result_q.put(("end", epoch))
            exhausted = True
            continue
        decode_s = time.monotonic() - t0_mono
        injected = None
        if _chaos is not None:
            injected = _chaos.maybe_slow_decode(worker=worker_id)
            if injected:
                # fold the seeded stall into the span so the straggler
                # is visible in the timeline — but TAGGED, so --health
                # reports "INJECTED STALL (chaos)", not an organic one
                decode_s = time.monotonic() - t0_mono
        slot = None
        while slot is None:
            try:
                slot = free_q.get(timeout=0.5)
            except _queue.Empty:
                if os.getppid() != parent_pid:
                    return
                try:
                    cmd = ctrl_q.get_nowait()
                except _queue.Empty:
                    continue
                if cmd == "stop":
                    return
                if cmd == "reset":
                    # drop the decoded batch: the epoch it belongs to is
                    # gone (parent discards stale messages the same way)
                    it.reset()
                    epoch += 1
                    exhausted = False
                    data = None
                    break
        if slot is None or data is None:
            continue
        if slot == -1:  # stop sentinel through the slot channel
            return
        d_views, l_views = views[slot]
        for v, a in zip(d_views, data):
            v[...] = _np.asarray(a).reshape(v.shape)
        for v, a in zip(l_views, label):
            v[...] = _np.asarray(a).reshape(v.shape)
        result_q.put(("b", epoch, slot, int(pad), decode_s, t0_mono,
                      (injected or {}).get("kind")))


# ---------------------------------------------------------------------------
# disposable-array registry: the donate handoff into parallel/dp.py
# ---------------------------------------------------------------------------
_DISPOSABLE: Dict[int, Any] = {}
_disposable_lock = threading.Lock()


def mark_disposable(arr) -> None:
    """Mark a device array as input-pipeline-owned and consumable: the
    pipeline guarantees nothing reads it after the training step takes
    it, so ``_donate_safe_put`` may donate it WITHOUT the defensive
    copy it makes for caller-owned buffers."""
    try:
        ref = weakref.ref(arr)
    except TypeError:
        return  # not weakref-able: stays copy-on-donate (safe)
    with _disposable_lock:
        if len(_DISPOSABLE) > 4096:
            for k in [k for k, r in _DISPOSABLE.items() if r() is None]:
                _DISPOSABLE.pop(k, None)
        _DISPOSABLE[id(arr)] = ref


def take_disposable(arr) -> bool:
    """Consume a disposable mark (one-shot).  True iff ``arr`` was
    marked by :func:`mark_disposable` and is still the same object."""
    with _disposable_lock:
        ref = _DISPOSABLE.pop(id(arr), None)
    return ref is not None and ref() is arr


# ---------------------------------------------------------------------------
# pool-wide cleanup: atexit + SIGTERM chain (shared-memory hygiene)
# ---------------------------------------------------------------------------
_LIVE_POOLS: "weakref.WeakSet[ShardedDecodePool]" = weakref.WeakSet()
_cleanup_installed = False


def _cleanup_all_pools() -> None:
    for p in list(_LIVE_POOLS):
        try:
            p.close()
        except Exception:
            pass


def _install_cleanup_once() -> None:
    global _cleanup_installed
    if _cleanup_installed:
        return
    _cleanup_installed = True
    atexit.register(_cleanup_all_pools)
    if threading.current_thread() is not threading.main_thread():
        return
    try:
        prev = signal.getsignal(signal.SIGTERM)
        if prev == signal.SIG_IGN:
            return  # the app deliberately ignores SIGTERM: respect it

        def _term(signum, frame):
            _cleanup_all_pools()
            if callable(prev):
                prev(signum, frame)
                return
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, _term)
    except (ValueError, OSError):
        pass


# ---------------------------------------------------------------------------
# telemetry feeds (all guarded: telemetry never fails the pipeline)
# ---------------------------------------------------------------------------
def _stamp_decode(worker: int, decode_s: float,
                  t0_mono: Optional[float] = None,
                  injected_kind: Optional[str] = None) -> None:
    """Per-batch decode evidence: the mxnet_io_decode_seconds histogram
    + a span on the worker's dedicated trace lane (tid BASE+worker) so
    the merged timeline shows every worker's decode activity.  The
    span is anchored at the worker's ``time.monotonic()`` decode start
    (CLOCK_MONOTONIC is process-invariant on one host), translated
    into the profiler's clock — NOT at parent consumption time, which
    would shift every lane by the batch's queue residency and corrupt
    the io-vs-step overlap evidence."""
    try:
        from . import diagnostics as _diag

        _diag.feed_io_decode_seconds(decode_s)
    except Exception:
        pass
    try:
        from . import profiler as _profiler

        if _profiler.is_running():
            tid = IO_WORKER_TID_BASE + int(worker)
            _profiler.register_tid_name(
                tid, "io:decode-worker %d" % worker)
            dur = max(float(decode_s) * 1e6, 1.0)
            now = _profiler._now_us()
            start = now - dur
            if t0_mono is not None:
                age_us = (time.monotonic() - float(t0_mono)) * 1e6
                if 0.0 <= age_us < 3600e6:  # sane clock: true anchor
                    start = now - age_us
            span_args = {"worker": int(worker)}
            if injected_kind:
                span_args["injected"] = True
                span_args["injected_kind"] = str(injected_kind)
            _profiler.record_span("io:decode", start, dur, cat="io",
                                  tid=tid, args=span_args)
    except Exception:
        pass


def _feed_worker_death() -> None:
    try:
        from . import diagnostics as _diag

        _diag.feed_io_worker_death()
    except Exception:
        pass


_HostBatch = namedtuple("_HostBatch",
                        "worker slot data label pad decode_s")


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------
class ShardedDecodePool(DataIter):
    """N decode worker processes over disjoint ``num_parts``/
    ``part_index`` record slices, reassembled into one deterministic
    round-robin batch stream.

    Parameters
    ----------
    iter_fn : callable(num_parts=..., part_index=...) -> DataIter
        Picklable factory (see :func:`make_ndarray_iter_fn` /
        :func:`make_record_iter_fn`).  The pool composes its worker
        sharding with the caller's outer (rank) sharding.
    num_workers : worker processes (default ``MXNET_IO_WORKERS``,
        0 → cpu_count-1, min 1).
    num_parts / part_index : OUTER sharding (this rank's slice); each
        worker then owns a disjoint sub-slice of it.
    """

    def __init__(self, iter_fn, num_workers: Optional[int] = None,
                 num_parts: int = 1, part_index: int = 0,
                 slots_per_worker: Optional[int] = None,
                 start_method: Optional[str] = None):
        from . import env as _env

        nw = num_workers if num_workers is not None \
            else _env.get_int("MXNET_IO_WORKERS")
        if not nw or int(nw) <= 0:
            nw = max(1, (os.cpu_count() or 2) - 1)
        self._nw = int(nw)
        self._outer = (int(num_parts), int(part_index))
        self._slots = max(1, int(
            slots_per_worker if slots_per_worker is not None
            else _env.get_int("MXNET_IO_POOL_SLOTS")))
        self._iter_fn = iter_fn
        # probe the UNsharded iterator for shapes/batch size/raw
        # capability: per-desc shapes are slice-invariant, and probing
        # worker 0's real slice would make ImageRecordIter copy that
        # whole record slice into a temp shard just to be thrown away
        probe = iter_fn(num_parts=1, part_index=0)
        self._provide_data = list(probe.provide_data)
        self._provide_label = list(probe.provide_label)
        super().__init__(int(getattr(probe, "batch_size", 0)
                             or self._provide_data[0].shape[0]))
        raw_ok = hasattr(probe, "next_raw")
        del probe
        method = start_method or _env.get_str("MXNET_IO_START_METHOD")
        if not method:
            # fork is safe exactly when workers never touch jax: the
            # next_raw contract guarantees that for library iterators;
            # anything else decodes through NDArray (jax) -> spawn
            method = "fork" if raw_ok \
                and "fork" in _mp.get_all_start_methods() else "spawn"
        if method not in _mp.get_all_start_methods():
            raise MXNetError("unknown start method %r" % method)
        self._method = method
        self._spec = _SlotSpec(self._provide_data, self._provide_label)
        self._started = False
        self._closed = False
        self._lock = threading.RLock()

    # -- sharding arithmetic: arr[r::R][w::N] == arr[r + R*w :: R*N] --
    def _inner_parts(self) -> int:
        return self._outer[0] * self._nw

    def _inner_index(self, w: int) -> int:
        return self._outer[1] + self._outer[0] * w

    @property
    def num_workers(self) -> int:
        return self._nw

    @property
    def provide_data(self):
        return self._provide_data

    @property
    def provide_label(self):
        return self._provide_label

    # -- lifecycle ------------------------------------------------------
    def _ensure_started(self) -> None:
        with self._lock:
            if self._started:
                return
            if self._closed:
                raise MXNetError("decode pool is closed")
            ctx = _mp.get_context(self._method)
            self._uid = "%s%d-%s" % (_SHM_PREFIX, os.getpid(),
                                     uuid.uuid4().hex[:8])
            base = _shm_dir()
            self._files = [[os.path.join(base, "%s-w%ds%d"
                                         % (self._uid, w, s))
                            for s in range(self._slots)]
                           for w in range(self._nw)]
            for row in self._files:
                for path in row:
                    fd = os.open(path, os.O_CREAT | os.O_RDWR, 0o600)
                    try:
                        os.ftruncate(fd, self._spec.nbytes)
                    finally:
                        os.close(fd)
            self._maps = [[_map_slot(p, self._spec.nbytes) for p in row]
                          for row in self._files]
            self._views = [[self._spec.views(m) for m in row]
                           for row in self._maps]
            self._free_qs = [ctx.Queue() for _ in range(self._nw)]
            self._result_qs = [ctx.Queue() for _ in range(self._nw)]
            self._ctrl_qs = [ctx.Queue() for _ in range(self._nw)]
            for w in range(self._nw):
                for s in range(self._slots):
                    self._free_qs[w].put(s)
            self._procs = []
            # spawn workers import jax with the package; they are
            # host-only by contract and must not open the parent's chip
            with _host_only_children():
                for w in range(self._nw):
                    p = ctx.Process(
                        target=_decode_worker_main,
                        args=(w, self._iter_fn, self._inner_parts(),
                              self._inner_index(w), self._files[w],
                              self._spec, self._free_qs[w],
                              self._result_qs[w], self._ctrl_qs[w],
                              os.getpid()),
                        daemon=True, name="mxio-decode-%d" % w)
                    p.start()
                    self._procs.append(p)
            self._epoch = 0
            self._rr = 0
            self._finished = [False] * self._nw
            self._consumed = [0] * self._nw
            self._dead = [False] * self._nw
            self._adopted: List[Optional[dict]] = [None] * self._nw
            self._started = True
            _LIVE_POOLS.add(self)
            _install_cleanup_once()
            _log.info("decode pool up: %d worker(s), %d slot(s) each, "
                      "%d B/slot, start_method=%s", self._nw,
                      self._slots, self._spec.nbytes, self._method)

    def close(self) -> None:
        """Stop workers, unlink every shared-memory segment.  Safe to
        call twice; runs from atexit and the SIGTERM chain."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            started = self._started
        if started:
            for w in range(self._nw):
                try:
                    self._ctrl_qs[w].put("stop")
                    self._free_qs[w].put(-1)
                except Exception:
                    pass
            for p in self._procs:
                p.join(timeout=3.0)
            for p in self._procs:
                if p.is_alive():
                    p.terminate()
                    p.join(timeout=1.0)
            for p in self._procs:
                if p.is_alive():
                    try:
                        p.kill()
                    except Exception:
                        pass
                    p.join(timeout=1.0)
            for q in (self._free_qs + self._result_qs + self._ctrl_qs):
                try:
                    q.close()
                    q.cancel_join_thread()
                except Exception:
                    pass
            for row in self._maps:
                for m in row:
                    try:
                        m.close()
                    except Exception:
                        pass
            for row in self._files:
                for path in row:
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
        _LIVE_POOLS.discard(self)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- the deterministic stream --------------------------------------
    def next_host(self) -> _HostBatch:
        """Next batch of the round-robin stream as HOST views into a
        shared-memory slot.  The caller must :meth:`recycle` the batch
        once its bytes are consumed (the device stage does this after
        the transfer completes)."""
        self._ensure_started()
        n = self._nw
        while True:
            if all(self._finished):
                raise StopIteration
            w = self._rr % n
            if self._finished[w]:
                self._rr += 1
                continue
            hb = self._fetch_from(w)
            if hb is None:  # w just finished this epoch
                self._rr += 1
                continue
            self._rr += 1
            self._consumed[w] += 1
            return hb

    def recycle(self, hb: _HostBatch) -> None:
        """Return a consumed batch's slot to its worker."""
        if hb.slot is not None and not self._dead[hb.worker]:
            self._free_qs[hb.worker].put(hb.slot)

    def _fetch_from(self, w: int) -> Optional[_HostBatch]:
        if self._dead[w]:
            return self._adopt_next(w)
        q = self._result_qs[w]
        while True:
            try:
                msg = q.get(timeout=0.2)
            except _queue.Empty:
                # io-bound wait: the parent is alive, just starved —
                # beacon so a supervised run stuck behind slow decode
                # workers is not SIGKILLed as "hung" by
                # MXNET_ELASTIC_HEARTBEAT_TIMEOUT_S (rate-limited,
                # no-op unsupervised)
                from . import diagnostics as _diag

                _diag.touch_heartbeat()
                if not self._procs[w].is_alive():
                    self._declare_dead(w)
                    return self._adopt_next(w)
                continue
            out = self._msg_to_batch(w, msg)
            if out is _EPOCH_END:
                return None
            if out is not None:
                return out

    def _msg_to_batch(self, w: int, msg):
        """One queue message -> _HostBatch | _EPOCH_END | None (stale,
        discarded — its slot recycled)."""
        if msg[0] == "end":
            if msg[1] == self._epoch:
                self._finished[w] = True
                return _EPOCH_END
            return None
        _kind, ep, slot, pad, decode_s, t0_mono = msg[:6]
        injected_kind = msg[6] if len(msg) > 6 else None
        if ep != self._epoch:
            if not self._dead[w]:
                self._free_qs[w].put(slot)
            return None
        _stamp_decode(w, decode_s, t0_mono, injected_kind=injected_kind)
        d, l = self._views[w][slot]
        return _HostBatch(w, slot, d, l, int(pad), float(decode_s))

    # -- dead-worker adoption ------------------------------------------
    def _declare_dead(self, w: int) -> None:
        self._dead[w] = True
        _feed_worker_death()
        _log.warning(
            "io_pipeline: decode worker %d died — adopting its shard "
            "inline at batch %d (degraded throughput, stream "
            "unchanged)", w, self._consumed[w])
        # batches it fully delivered before dying are still readable
        buffered: deque = deque()
        deadline = time.time() + 0.5
        while time.time() < deadline:
            try:
                buffered.append(self._result_qs[w].get(timeout=0.05))
            except _queue.Empty:
                break
        self._adopted[w] = {"buffer": buffered, "it": None}

    def _adopt_next(self, w: int) -> Optional[_HostBatch]:
        st = self._adopted[w]
        while st["buffer"]:
            out = self._msg_to_batch(w, st["buffer"].popleft())
            if out is _EPOCH_END:
                return None
            if out is not None:
                return out
        if self._finished[w]:
            return None
        if st["it"] is None:
            it = self._iter_fn(num_parts=self._inner_parts(),
                               part_index=self._inner_index(w))
            # fast-forward to the dead worker's exact stream position.
            # "Exact" holds for deterministic iterators (the same
            # contract exact-resume already requires); an iterator that
            # reshuffles per epoch replays a fresh-epoch order here.
            for _ in range(self._consumed[w]):
                try:
                    _host_batch(it)
                except StopIteration:
                    break
            st["it"] = it
        t0_mono = time.monotonic()
        try:
            data, label, pad = _host_batch(st["it"])
        except StopIteration:
            self._finished[w] = True
            return None
        decode_s = time.monotonic() - t0_mono
        _stamp_decode(w, decode_s, t0_mono)
        return _HostBatch(w, None, data, label, int(pad), decode_s)

    # -- DataIter surface (host mode: safe copies) ----------------------
    def reset(self):
        with self._lock:
            if not self._started:
                return
            self._epoch += 1
            self._rr = 0
            self._finished = [False] * self._nw
            self._consumed = [0] * self._nw
            for w in range(self._nw):
                if self._dead[w]:
                    st = self._adopted[w]
                    st["buffer"].clear()
                    if st["it"] is not None:
                        st["it"].reset()
                else:
                    self._ctrl_qs[w].put("reset")

    def next(self) -> DataBatch:
        return _instrumented_fetch(self, self._next_copy)

    def _next_copy(self) -> DataBatch:
        from .ndarray import array as _nd_array

        hb = self.next_host()
        batch = DataBatch([_nd_array(v.copy()) for v in hb.data],
                          [_nd_array(v.copy()) for v in hb.label],
                          pad=hb.pad)
        self.recycle(hb)
        return batch


# ---------------------------------------------------------------------------
# the facade: pool + async device prefetch
# ---------------------------------------------------------------------------
class InputPipeline(DataIter):
    """Sharded decode pool behind a double-buffered async device stage.

    ``device=True`` (default): a background thread issues
    ``jax.device_put`` for upcoming batches (``depth`` ahead, default
    ``MXNET_IO_PREFETCH_DEPTH``) so H2D overlaps the compiled step;
    ``next()`` returns device-committed, donation-safe batches.
    ``device=False``: host-side copies (decode scaling benchmarks).
    ``sharding`` optionally names the target placement (a jax Sharding
    or Device) — e.g. ``NamedSharding(mesh, P("dp"))`` for the dp mesh.
    """

    def __init__(self, iter_fn, num_workers: Optional[int] = None,
                 num_parts: int = 1, part_index: int = 0,
                 depth: Optional[int] = None,
                 slots_per_worker: Optional[int] = None,
                 device: bool = True, sharding=None,
                 start_method: Optional[str] = None):
        from . import env as _env

        self._pool = ShardedDecodePool(
            iter_fn, num_workers=num_workers, num_parts=num_parts,
            part_index=part_index, slots_per_worker=slots_per_worker,
            start_method=start_method)
        super().__init__(self._pool.batch_size)
        self._depth = max(1, int(
            depth if depth is not None
            else _env.get_int("MXNET_IO_PREFETCH_DEPTH")))
        self._device_mode = bool(device)
        self._sharding = sharding
        self._q: _queue.Queue = _queue.Queue(maxsize=max(1, self._depth))
        self._gen = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._pending: Optional[DataBatch] = None
        self._consumed_batches = 0

    # -- DataIter surface ----------------------------------------------
    @property
    def provide_data(self):
        return self._pool.provide_data

    @property
    def provide_label(self):
        return self._pool.provide_label

    @property
    def num_workers(self) -> int:
        return self._pool.num_workers

    @property
    def cursor(self) -> int:
        """Stream position in SAMPLES (the iterator_state the periodic
        checkpoint records)."""
        return self._consumed_batches * self.batch_size

    def next(self) -> DataBatch:
        if self._pending is not None:
            b, self._pending = self._pending, None
            return b
        return _instrumented_fetch(self, self._next_impl)

    def iter_next(self) -> bool:
        if self._pending is None:
            try:
                self._pending = self.next()
            except StopIteration:
                return False
        return True

    def getdata(self):
        return self._pending.data

    def getlabel(self):
        return self._pending.label

    def getpad(self):
        return self._pending.pad

    def reset(self):
        self._pending = None
        self._stop_thread()
        self._gen += 1
        self._pool.reset()
        self._consumed_batches = 0

    def close(self) -> None:
        self._pending = None
        self._stop_thread()
        self._pool.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def skip_batches(self, n: int) -> None:
        """Fast-forward the stream ``n`` batches WITHOUT device
        placement — the exact-resume fast path (base_module.fit): the
        skipped batches are decoded (stream position is what matters)
        but never cross the H2D link."""
        if self._thread is not None and self._thread.is_alive():
            for _ in range(int(n)):  # device stage already running
                try:
                    self.next()
                except StopIteration:
                    break
            return
        for _ in range(int(n)):
            try:
                hb = self._pool.next_host()
            except StopIteration:
                break
            self._pool.recycle(hb)
            self._consumed_batches += 1

    # -- internals ------------------------------------------------------
    def _next_impl(self) -> DataBatch:
        if not self._device_mode:
            batch = self._pool._next_copy()
            self._consumed_batches += 1
            return batch
        self._ensure_thread()
        from . import profiler as _profiler

        t0 = _profiler._now_us() if _profiler.is_running() else None
        while True:
            gen, item = self._q.get()
            if gen == self._gen:
                break
        if t0 is not None:
            # consumer-side stall: the input-pipeline-bound signal
            _profiler.record_span("io:wait", t0,
                                  _profiler._now_us() - t0, cat="io")
        try:
            from . import diagnostics as _diag

            _diag.feed_io_queue_depth(self._q.qsize())
        except Exception:
            pass
        if item is None:
            t = self._thread
            if t is not None:
                t.join(timeout=2.0)
            self._thread = None
            raise StopIteration
        self._consumed_batches += 1
        return item

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._device_loop, args=(self._gen,),
                daemon=True, name="mxio-device-prefetch")
            self._thread.start()

    def _stop_thread(self) -> None:
        t = self._thread
        if t is None:
            return
        self._stop.set()
        # unblock a producer waiting on a full queue
        try:
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass
        t.join(timeout=10.0)
        self._thread = None
        self._stop.clear()
        try:
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass

    def _place(self, jax, view: _np.ndarray):
        """``device_put`` one slot view; the result must NEVER alias
        the pool-owned shared-memory buffer (the compiled step donates
        these arrays — jax CPU may zero-copy an aligned numpy array, in
        which case recycling the slot would corrupt the in-flight
        batch).  Blocks until the transfer lands so the caller may
        recycle the slot immediately after."""
        if self._sharding is not None:
            placed = jax.device_put(view, self._sharding)
        else:
            placed = jax.device_put(view)
        placed.block_until_ready()
        try:
            if placed.unsafe_buffer_pointer() == \
                    view.__array_interface__["data"][0]:
                src = view.copy()
                placed = jax.device_put(src, self._sharding) \
                    if self._sharding is not None else jax.device_put(src)
                placed.block_until_ready()
        except Exception:
            pass  # multi-shard placement: fresh per-shard buffers
        return placed

    def _device_loop(self, gen: int) -> None:
        """The async device stage: place batch k+1 (and k+2, up to
        ``depth``) while the consumer's batch k computes."""
        import jax

        from . import profiler as _profiler
        from .ndarray import NDArray

        pool = self._pool
        while not self._stop.is_set():
            # ANY failure in this body must still enqueue the None
            # sentinel: the consumer blocks on an untimed q.get(), so a
            # thread that died silently (device_put OOM, bad sharding)
            # would hang Module.fit forever instead of raising
            try:
                try:
                    hb = pool.next_host()
                except StopIteration:
                    self._q.put((gen, None))
                    return
                try:
                    t0 = _profiler._now_us()
                    data = [self._place(jax, v) for v in hb.data]
                    label = [self._place(jax, v) for v in hb.label]
                finally:
                    pool.recycle(hb)  # never leak the slot
                if _profiler.is_running():
                    _profiler.record_span(
                        "io:device_put", t0, _profiler._now_us() - t0,
                        cat="io", args={"worker": hb.worker})
                for a in data:
                    mark_disposable(a)
                for a in label:
                    mark_disposable(a)
                batch = DataBatch([NDArray.from_raw(a) for a in data],
                                  [NDArray.from_raw(a) for a in label],
                                  pad=hb.pad)
            except Exception:
                _log.exception("io_pipeline device stage failed")
                self._q.put((gen, None))
                return
            while not self._stop.is_set():
                try:
                    self._q.put((gen, batch), timeout=0.1)
                    break
                except _queue.Full:
                    continue
            if self._stop.is_set():
                return


# ---------------------------------------------------------------------------
# picklable iterator factories (the worker-side constructors)
# ---------------------------------------------------------------------------
def _ndarray_iter_fn(data, label, batch_size, kwargs,
                     num_parts=1, part_index=0):
    from .io import NDArrayIter

    return NDArrayIter(data, label, batch_size, num_parts=num_parts,
                       part_index=part_index, **kwargs)


def make_ndarray_iter_fn(data, label=None, batch_size=1, **kwargs):
    """Picklable ``iter_fn`` over in-memory numpy arrays (arrays travel
    by value to spawn workers; fork workers share pages)."""
    if "num_parts" in kwargs or "part_index" in kwargs:
        raise ValueError("pass rank sharding to the pool "
                         "(num_parts/part_index), not the factory")
    return functools.partial(_ndarray_iter_fn, data, label,
                             int(batch_size), kwargs)


def _record_iter_fn(kwargs, num_parts=1, part_index=0):
    from .io import ImageRecordIter

    return ImageRecordIter(num_parts=num_parts, part_index=part_index,
                           **kwargs)


def make_record_iter_fn(**kwargs):
    """Picklable ``iter_fn`` over a .rec file (ImageRecordIter kwargs:
    path_imgrec, data_shape, batch_size, ...).  Each worker copies its
    record slice into a private temp shard and decodes only that."""
    if "num_parts" in kwargs or "part_index" in kwargs:
        raise ValueError("pass rank sharding to the pool "
                         "(num_parts/part_index), not the factory")
    return functools.partial(_record_iter_fn, kwargs)


# ---------------------------------------------------------------------------
# CLI: python -m mxnet_tpu.io_pipeline --self-test
# ---------------------------------------------------------------------------
def _leaked_segments(token: str) -> List[str]:
    base = _shm_dir()
    try:
        return [n for n in os.listdir(base)
                if n.startswith(_SHM_PREFIX) and token in n]
    except OSError:
        return []


def _drain_ids(pipe) -> List[int]:
    """Consume one epoch; return the label ids seen (stream order)."""
    out: List[int] = []
    while True:
        try:
            b = pipe.next()
        except StopIteration:
            return out
        lab = b.label[0]
        lab = lab.asnumpy() if hasattr(lab, "asnumpy") else _np.asarray(lab)
        keep = len(lab) - b.pad
        out.extend(int(v) for v in _np.asarray(lab).reshape(-1)[:keep])


_SIGTERM_CHILD_SRC = r"""
import os, signal, sys, time
import numpy as np
from mxnet_tpu import io_pipeline as iop

x = np.arange(64, dtype=np.float32).reshape(32, 2)
y = np.arange(32, dtype=np.float32)
pipe = iop.InputPipeline(iop.make_ndarray_iter_fn(x, y, batch_size=4),
                         num_workers=2, device=False)
pipe.next()  # pool is up, slots exist
print("READY", pipe._pool._uid, flush=True)
time.sleep(60)  # killed by the parent's SIGTERM long before this
"""


def _self_test() -> tuple:
    import subprocess

    checks: Dict[str, bool] = {}
    x = _np.arange(96, dtype=_np.float32).reshape(48, 2)
    y = _np.arange(48, dtype=_np.float32)
    fn = make_ndarray_iter_fn(x, y, batch_size=4,
                              last_batch_handle="discard")

    # 1) start/stream/drain: deterministic round-robin reassembly,
    # disjoint-and-exhaustive coverage, identical across epochs
    pipe = InputPipeline(fn, num_workers=2, device=False)
    token = None
    try:
        e1 = _drain_ids(pipe)
        token = pipe._pool._uid
        checks["covers_every_record"] = sorted(e1) == list(range(48))
        expect = []
        parts = [list(range(w, 48, 2)) for w in range(2)]
        k = 0
        while any(parts[i] for i in range(2)):
            w = k % 2
            if parts[w]:
                expect.extend(parts[w][:4])
                parts[w] = parts[w][4:]
            k += 1
        checks["round_robin_deterministic"] = e1 == expect
        pipe.reset()
        checks["epoch2_identical"] = _drain_ids(pipe) == e1
        # mid-epoch reset
        pipe.reset()
        for _ in range(3):
            pipe.next()
        pipe.reset()
        checks["mid_epoch_reset_restarts"] = _drain_ids(pipe) == e1
        checks["segments_live_while_open"] = \
            len(_leaked_segments(token)) > 0
    finally:
        pipe.close()
    checks["close_unlinks_segments"] = _leaked_segments(token) == []

    # 2) worker death: kill one worker mid-stream; the stream finishes
    # bitwise-identically (inline adoption), nothing hangs
    pipe = InputPipeline(fn, num_workers=2, device=False)
    try:
        got = [pipe.next() for _ in range(2)]
        ids = [int(v) for b in got
               for v in b.label[0].asnumpy().reshape(-1)]
        victim = pipe._pool._procs[1]
        victim.kill()
        victim.join(5.0)
        rest = _drain_ids(pipe)
        checks["worker_death_stream_exact"] = ids + rest == e1
        checks["worker_death_flagged"] = pipe._pool._dead[1]
    finally:
        pipe.close()

    # 3) slow_decode chaos: a seeded straggler degrades throughput but
    # the epoch still completes (no deadlock)
    os.environ["MXNET_CHAOS"] = "slow_decode:worker=0,ms=30,count=3"  # mxlint: disable=MXL002
    try:
        pipe = InputPipeline(fn, num_workers=2, device=False)
        try:
            checks["slow_decode_completes"] = \
                sorted(_drain_ids(pipe)) == list(range(48))
        finally:
            pipe.close()
    finally:
        del os.environ["MXNET_CHAOS"]  # mxlint: disable=MXL002

    # 4) async device stage: batches come back device-committed,
    # values identical to the host stream, arrays donation-marked
    pipe = InputPipeline(fn, num_workers=2, device=True)
    try:
        b = pipe.next()
        arr = b.data[0]._data
        checks["device_committed"] = getattr(arr, "committed", True) \
            in (True,) or hasattr(arr, "devices")
        first = _np.asarray(arr)
        checks["device_values_match"] = \
            first.shape == (4, 2) and float(first[0, 0]) == 0.0
        checks["device_disposable"] = take_disposable(arr)
        rest = _drain_ids(pipe)
        checks["device_stream_complete"] = len(rest) == 44
    finally:
        pipe.close()

    # 5) SIGTERM hygiene: a SIGTERM'd pipeline process leaves zero
    # shared-memory litter behind
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen([sys.executable, "-c", _SIGTERM_CHILD_SRC],
                            stdout=subprocess.PIPE, text=True, env=env)
    line = proc.stdout.readline().strip()
    child_token = line.split()[-1] if line.startswith("READY") else ""
    checks["sigterm_child_started"] = bool(child_token)
    checks["sigterm_child_segments_exist"] = \
        len(_leaked_segments(child_token)) > 0 if child_token else False
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=20)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
    time.sleep(0.2)
    checks["sigterm_no_shm_litter"] = \
        _leaked_segments(child_token) == [] if child_token else False

    return all(checks.values()), checks


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.io_pipeline",
        description="sharded decode pool + async device prefetch "
                    "self-test")
    ap.add_argument("--self-test", action="store_true",
                    help="pool start/stop/drain, determinism, worker "
                         "death, slow_decode chaos, device stage, "
                         "SIGTERM shared-memory hygiene")
    args = ap.parse_args(argv)
    if args.self_test:
        ok, checks = _self_test()
        print(json.dumps({"self_test_ok": ok, "checks": checks}))
        return 0 if ok else 1
    ap.print_help()
    return 0


if __name__ == "__main__":
    sys.exit(main())
