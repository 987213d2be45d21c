"""Paged KV-cache allocator: fixed-size token blocks with a free list.

The generation tier budgets cache memory the way ``parallel/buckets.py``
budgets gradient bytes: a fixed pool carved into fixed-size units, a
deterministic plan of who holds what, and accounting that feeds
``diagnostics.metrics``.  Each layer owns the pools its mixer states
(``rows``: a pool's name and the shape of one token's row in it; the
dense block's K and V, ``(num_blocks, block_tokens, n_heads,
head_dim)`` twice a layer, or a latent mixer's ``(num_blocks,
block_tokens, kv_lora_rank + rope)`` once) and a
sequence holds a LIST of block ids, not a contiguous span, so slot
churn from continuous batching cannot fragment the pool into unusable
holes: any free block serves any sequence.

Block 0 is the GARBAGE block, never allocated: the compiled steps route
every write from a padded position or an inactive slot there (see
``transformer.model._scatter_tokens``), so the device code never
branches on liveness and a freed slot costs nothing to keep riding.

The allocator is HOST state (block tables, free list, cursors); the
pools themselves are device arrays DONATED to the compiled
prefill/decode steps: a step writes its new rows into the buffers it
was handed and returns them, and the engine replaces ``kv.pages`` with
what came back.  The dict that went into a step is dead once the call
has been dispatched (its arrays read ``is_deleted()``), so nobody may
keep a reference to ``kv.pages`` or to one of its arrays across a
step.  A step that raises after it consumed the pools has taken every
live sequence's history with it: :meth:`PagedKVCache.pools_lost` says
so and :meth:`PagedKVCache.rebuild_pools` makes them again.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

from . import reqtrace as _reqtrace

__all__ = ["CacheExhausted", "PagedKVCache"]


class CacheExhausted(RuntimeError):
    """No free blocks left for an allocation — the engine's cue to
    evict (retire a sequence early, counted) or defer admission."""


class PagedKVCache:
    """Free-list block allocator over per-layer pools of cache rows.

    ``rows`` is ``{pool: shape of one token's row}`` as the model
    states it (``transformer.model.cache_rows``); ``counters`` is
    ``{name: shape}`` of int32 arrays that ride in ``pages`` beside the
    pools, donated and returned with them (what an expert layer's steps
    routed), and are no pool."""

    def __init__(self, *, rows: Dict[str, tuple], num_blocks: int,
                 block_tokens: int, dtype: str = "float32",
                 name: str = "gen",
                 counters: Optional[Dict[str, tuple]] = None):
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "garbage block)")
        self.name = str(name)
        self.num_blocks = int(num_blocks)
        self.block_tokens = int(block_tokens)
        self._lock = threading.Lock()
        #: blocks available for allocation — 0 reserved as garbage
        self._free: List[int] = list(range(1, self.num_blocks))
        #: seq_id -> ordered block ids (index i covers tokens
        #: [i*bt, (i+1)*bt))
        self._blocks: Dict[str, List[int]] = {}
        #: seq_id -> tokens actually written (fragmentation accounting)
        self._lengths: Dict[str, int] = {}
        self.evictions = 0
        self.pool_rebuilds = 0
        self._held = None      # the registry's objects feed_metrics sets
        self._pool_shapes = {
            pool: (self.num_blocks, self.block_tokens)
            + tuple(int(n) for n in row) for pool, row in rows.items()}
        self._counter_shapes = {k: tuple(v) for k, v in
                                (counters or {}).items() if v}
        self._pool_dtype = dtype
        #: device pools (and counters), donated to the compiled steps:
        #: the engine replaces this dict with what each step returns,
        #: and the dict that went in is dead — keep no reference to it
        #: or to its arrays across a step
        self.pages = self._zeroed_pools()
        #: held by the engine's thread from a compiled step's dispatch
        #: until ``pages`` names what the step returned; any other
        #: thread that reads an array of ``pages`` takes it
        self.in_step = threading.Lock()
        (self._device,) = next(iter(self.pages.values())).devices()

    @property
    def pools(self) -> tuple:
        """The names of ``pages`` that are pools of cache rows."""
        return tuple(self._pool_shapes)

    def block_bytes(self) -> int:
        """What one block holds over all pools."""
        import math

        import jax.numpy as jnp

        return sum(math.prod(shape[1:]) for shape in
                   self._pool_shapes.values()) \
            * jnp.dtype(self._pool_dtype).itemsize

    def _zeroed_pools(self) -> Dict:
        import jax.numpy as jnp

        pages = {pool: jnp.zeros(shape, dtype=self._pool_dtype)
                 for pool, shape in self._pool_shapes.items()}
        pages.update((k, jnp.zeros(shape, dtype=jnp.int32))
                     for k, shape in self._counter_shapes.items())
        return pages

    def pools_lost(self) -> bool:
        """Whether a step consumed the donated pools and gave none
        back: some array of ``pages`` is deleted."""
        # the host stub's pools are numpy arrays: never donated
        return any(a.is_deleted() for a in self.pages.values()
                   if hasattr(a, "is_deleted"))

    def rebuild_pools(self) -> None:
        """Zeroed pools again, with the shape, dtype and placement of
        ``__init__``.  The block tables still name blocks whose rows
        are gone: the caller fails and frees every live sequence."""
        import jax

        # placed by the default device as __init__'s were, and so not
        # committed: a committed pool would compile every plan cell anew
        with jax.default_device(self._device):
            self.pages = self._zeroed_pools()
        self.pool_rebuilds += 1

    # -- allocation ----------------------------------------------------
    def _blocks_for(self, n_tokens: int) -> int:
        return max(1, -(-int(n_tokens) // self.block_tokens))

    def alloc(self, seq_id: str, n_tokens: int) -> List[int]:
        """Claim blocks covering ``n_tokens`` for a NEW sequence.
        Raises :class:`CacheExhausted` (allocating nothing) if the free
        list cannot cover it."""
        need = self._blocks_for(n_tokens)
        with self._lock:
            if seq_id in self._blocks:
                raise ValueError("sequence %r already holds blocks"
                                 % seq_id)
            if need > len(self._free):
                raise CacheExhausted(
                    "need %d blocks for %r, %d free (of %d)"
                    % (need, seq_id, len(self._free),
                       self.num_blocks - 1))
            got = [self._free.pop() for _ in range(need)]
            self._blocks[seq_id] = got
            self._lengths[seq_id] = int(n_tokens)
        # seq_id IS the request id: KV allocations land in the
        # request's lifecycle trace
        _reqtrace.event(seq_id, "kv_alloc", blocks=len(got))
        return list(got)

    def extend(self, seq_id: str, new_len: int) -> List[int]:
        """Grow a sequence's coverage to ``new_len`` tokens, claiming
        blocks as its cursor crosses block boundaries.  Raises
        :class:`CacheExhausted` without partial allocation."""
        with self._lock:
            held = self._blocks[seq_id]
            need = self._blocks_for(new_len) - len(held)
            if need > len(self._free):
                raise CacheExhausted(
                    "need %d more blocks for %r, %d free"
                    % (need, seq_id, len(self._free)))
            for _ in range(max(need, 0)):
                held.append(self._free.pop())
            self._lengths[seq_id] = max(self._lengths[seq_id],
                                        int(new_len))
            out = list(held)
        if need > 0:
            _reqtrace.event(seq_id, "kv_extend", blocks=need)
        return out

    def free(self, seq_id: str, evicted: bool = False) -> int:
        """Return a sequence's blocks to the free list (idempotent);
        ``evicted`` marks an under-pressure early retirement for the
        stats feed.  Returns the number of blocks released."""
        with self._lock:
            held = self._blocks.pop(seq_id, None)
            self._lengths.pop(seq_id, None)
            if held is None:
                return 0
            self._free.extend(held)
            if evicted:
                self.evictions += 1
        _reqtrace.event(seq_id, "evicted" if evicted else "kv_free",
                        blocks=len(held))
        return len(held)

    def block_table(self, seq_id: str, width: int):
        """This sequence's block table padded to ``width`` entries with
        the garbage block — the row the compiled step consumes."""
        import numpy as np

        with self._lock:
            held = self._blocks.get(seq_id, [])
            if len(held) > int(width):
                raise ValueError(
                    "sequence %r holds %d blocks > table width %d"
                    % (seq_id, len(held), width))
            row = np.zeros(int(width), dtype=np.int32)
            row[:len(held)] = held
            return row

    def note_length(self, seq_id: str, n_tokens: int) -> None:
        with self._lock:
            if seq_id in self._lengths:
                self._lengths[seq_id] = max(self._lengths[seq_id],
                                            int(n_tokens))

    # -- accounting ----------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Allocator accounting: blocks live/free, sequence count, and
        internal fragmentation (allocated token slots not yet holding a
        token, over all allocated slots)."""
        with self._lock:
            live = sum(len(b) for b in self._blocks.values())
            slots = live * self.block_tokens
            used = sum(self._lengths.values())
            frag = (slots - used) / slots if slots else 0.0
            return {
                "blocks_total": self.num_blocks - 1,
                "blocks_live": live,
                "blocks_free": len(self._free),
                "seqs": len(self._blocks),
                "fragmentation": round(frag, 4),
                "evictions": self.evictions,
            }

    def _metrics(self, reg) -> tuple:
        lab = {"model": self.name}
        return (
            reg.gauge("mxnet_serve_kv_blocks_live",
                      help="paged KV-cache blocks allocated", labels=lab),
            reg.gauge("mxnet_serve_kv_blocks_free",
                      help="paged KV-cache blocks free", labels=lab),
            reg.gauge("mxnet_serve_kv_fragmentation",
                      help="unused fraction of allocated KV token slots",
                      labels=lab),
            reg.counter("mxnet_serve_kv_evictions_total",
                        help="sequences evicted under cache pressure",
                        labels=lab),
            reg.counter("mxnet_serve_kv_pool_rebuilds_total",
                        help="times the KV pools were made again after a "
                             "failed step consumed them",
                        labels=lab))

    def feed_metrics(self) -> None:
        """Push allocator gauges/counters into diagnostics.metrics —
        best-effort, the serving convention (a metrics hiccup must not
        fail a decode tick).  The registry's objects are looked up once
        and held (``diagnostics.Held``): this runs every engine step."""
        try:
            if self._held is None:
                from .. import diagnostics as _diag

                self._held = _diag.Held(self._metrics)
            live, free, frag, evicted, rebuilt = self._held.get()
            st = self.stats()
            live.set(st["blocks_live"])
            free.set(st["blocks_free"])
            frag.set(st["fragmentation"])
            if st["evictions"] > evicted.value:
                evicted.inc(st["evictions"] - evicted.value)
            if self.pool_rebuilds > rebuilt.value:
                rebuilt.inc(self.pool_rebuilds - rebuilt.value)
        except Exception:
            pass
