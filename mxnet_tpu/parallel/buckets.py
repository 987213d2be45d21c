"""Gradient bucketing for backward-overlapped all-reduce (NCCL-DDP style).

Round 5 measured the data-parallel gradient exchange compiling to ONE
combined synchronous all-reduce (its HLO held no async start/done pair)
— a reduction that depends on EVERY gradient cannot start until backward
finishes, so nothing can hide it.
The fix is the same one NCCL DDP and the reference's engine-priority
path (python/mxnet/gluon/trainer.py:190, src/kvstore/kvstore_nccl.h:281)
converged on: partition the gradient pytree into REVERSE-LAYER-ORDER,
size-capped buckets and reduce each bucket separately.  Bucket 0 holds
the deepest (last-executed-forward) layers, whose gradients materialize
FIRST during backward — its all-reduce's operands are ready while most
of backward is still running, so the dataflow graph itself gives XLA's
latency-hiding scheduler the freedom to emit ``all-reduce-start``/
``all-reduce-done`` pairs that ride ICI under the remaining compute.

Mechanics (per bucket):
  * the bucket's gradient leaves are flattened and concatenated into one
    contiguous buffer, so every backend emits exactly ONE reduction op
    per bucket (a variadic ``lax.psum`` lowers to one all-reduce PER
    OPERAND on this toolchain — measured, not assumed);
  * the buffer is reduced with ``lax.psum`` over the mesh's dp axis
    (default), or with a manual ``lax.ppermute`` reduce-scatter/
    all-gather ring (``MXNET_KVSTORE_BUCKET_IMPL=ring`` — the pattern
    already proven to schedule async pairs in ring_attention.py);
  * consecutive buckets are chained through
    ``lax.optimization_barrier`` (issue order = reverse layer order,
    the NCCL in-order-stream analogue) so XLA's all-reduce combiner
    cannot re-merge them into the round-5 monolith.  Compute stays OFF
    the chain — only reductions serialize against each other.

Buckets never mix dtypes (the concat must be homogeneous) and every
gradient lands in exactly one bucket.  ``MXNET_KVSTORE_BUCKET_BYTES``
tunes the cap (default 4 MiB; ``0`` disables bucketing entirely and
callers fall back to the monolithic path).
"""
from __future__ import annotations

from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, Tuple

from .. import env as _env

__all__ = [
    "DEFAULT_BUCKET_BYTES", "Bucket", "bucket_cap_bytes", "chain_enabled",
    "impl_name", "partition", "plan_for_arrays", "plan_with_tuning",
    "bucketed_reduce", "ring_allreduce_flat", "hierarchical_reduce_flat",
    "host_local_count", "accounting", "plan_meta", "stamp_profiler",
]

DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024


class Bucket(NamedTuple):
    """One reduction unit: ``keys`` in issue order, homogeneous dtype."""
    keys: Tuple
    nbytes: int
    dtype: str


def bucket_cap_bytes(default: int = DEFAULT_BUCKET_BYTES) -> int:
    """The size cap, env-tunable via MXNET_KVSTORE_BUCKET_BYTES.
    0 disables bucketing (callers use the monolithic reduction)."""
    return _env.get_int("MXNET_KVSTORE_BUCKET_BYTES", default)


def chain_enabled() -> bool:
    """MXNET_KVSTORE_BUCKET_CHAIN=0 drops the optimization_barrier chain
    between consecutive bucket reductions (lets the combiner re-merge)."""
    return _env.get_bool("MXNET_KVSTORE_BUCKET_CHAIN")


def impl_name() -> str:
    """'psum' (default), 'ring' (manual ppermute reduce-scatter/
    all-gather — collective-permutes can never be combined into one
    all-reduce, and are the pattern ring_attention.py already overlaps)
    or 'hierarchical' (intra-host psum then inter-host ring — the
    two-tier schedule multi-host meshes want when intra-host ICI is an
    order of magnitude faster than the host-to-host links)."""
    return _env.get_str("MXNET_KVSTORE_BUCKET_IMPL")


def _nbytes(shape, dtype) -> int:
    import numpy as np

    n = 1
    for d in shape:
        n *= int(d)
    try:
        item = np.dtype(dtype).itemsize
    except TypeError:
        # extension dtypes numpy has not registered (bare 'bfloat16'
        # strings when ml_dtypes is absent)
        item = {"bfloat16": 2, "float16": 2}.get(str(dtype), 4)
    return n * item


def partition(entries: Sequence[Tuple], cap_bytes: Optional[int] = None,
              *, first_cap_bytes: Optional[int] = None,
              last_cap_bytes: Optional[int] = None) -> List[Bucket]:
    """Partition ``entries`` — ``(key, shape, dtype)`` in LAYER ORDER
    (forward execution order) — into reverse-layer-order buckets.

    Deterministic greedy fill over ``reversed(entries)``: a bucket
    closes when adding the next gradient would exceed its cap or
    change dtype; a single gradient larger than the cap gets a bucket
    of its own.  Every key lands in exactly one bucket.

    First/last asymmetry (the autotuner's knobs, mxnet_tpu/autotune):
    ``first_cap_bytes`` caps bucket 0 separately — a SMALL first bucket
    puts the first reduction on the wire while backward has barely
    started; ``last_cap_bytes`` (> cap) folds trailing buckets together
    — the tail reductions issue after backward ends, so fewer, larger
    launches cost nothing in overlap.  Tail folding never touches
    bucket 0 (that would undo the first-bucket asymmetry) and never
    mixes dtypes.
    """
    if cap_bytes is None:
        cap_bytes = bucket_cap_bytes()
    cap = max(int(cap_bytes), 1)
    first_cap = cap if first_cap_bytes is None \
        else max(int(first_cap_bytes), 1)
    buckets: List[Bucket] = []
    cur_keys: List = []
    cur_bytes = 0
    cur_dtype: Optional[str] = None

    def flush():
        nonlocal cur_keys, cur_bytes, cur_dtype
        if cur_keys:
            buckets.append(Bucket(tuple(cur_keys), cur_bytes, cur_dtype))
        cur_keys, cur_bytes, cur_dtype = [], 0, None

    for key, shape, dtype in reversed(list(entries)):
        nb = _nbytes(shape, dtype)
        dt = str(dtype)
        active = first_cap if not buckets else cap
        if cur_keys and (cur_dtype != dt or cur_bytes + nb > active):
            flush()
        cur_keys.append(key)
        cur_bytes += nb
        cur_dtype = dt
    flush()
    if last_cap_bytes is not None and int(last_cap_bytes) > cap:
        lcap = int(last_cap_bytes)
        while len(buckets) > 2 and \
                buckets[-2].dtype == buckets[-1].dtype and \
                buckets[-2].nbytes + buckets[-1].nbytes <= lcap:
            tail = buckets.pop()
            prev = buckets.pop()
            buckets.append(Bucket(prev.keys + tail.keys,
                                  prev.nbytes + tail.nbytes, prev.dtype))
    return buckets


def plan_with_tuning(entries: Sequence[Tuple],
                     cap_bytes: Optional[int] = None
                     ) -> Tuple[List[Bucket], Optional[Dict]]:
    """Partition under the autotuned caps when a tuned plan applies
    (MXNET_AUTOTUNE_PLAN / MXNET_AUTOTUNE_DIR — autotune/plan.py),
    falling back to the MXNET_KVSTORE_BUCKET_BYTES default otherwise.

    Returns ``(plan, tuning_meta)``; ``tuning_meta`` is None on the
    untuned path and the applied caps + plan provenance otherwise (the
    meta rides plan_meta into the flight-recorder header's stamp).
    An EXPLICIT ``cap_bytes`` bypasses tuning entirely — a caller
    pinning a cap means it."""
    if cap_bytes is not None:
        return partition(entries, cap_bytes), None
    entry_list = list(entries)
    total = sum(_nbytes(shape, dtype) for _k, shape, dtype in entry_list)
    from ..autotune import plan as _aplan  # lazy: no import cycle

    caps, _path = _aplan.resolve_caps(total_bytes=total,
                                      n_grads=len(entry_list))
    if caps is None:
        return partition(entry_list, None), None
    plan = partition(entry_list, caps["cap_bytes"],
                     first_cap_bytes=caps.get("first_cap_bytes"),
                     last_cap_bytes=caps.get("last_cap_bytes"))
    return plan, dict(caps)


def plan_for_arrays(named: Mapping, cap_bytes: Optional[int] = None
                    ) -> List[Bucket]:
    """Partition a ``{key: array}`` mapping (insertion order = layer
    order)."""
    return partition([(k, v.shape, v.dtype) for k, v in named.items()],
                     cap_bytes)


def ring_allreduce_flat(flat, axis_name: str, n: int):
    """Manual ring all-reduce of a flat buffer: unidirectional
    reduce-scatter then all-gather over ``lax.ppermute`` neighbour hops
    (2(n-1) steps, the bandwidth-optimal schedule KVStoreNCCL used).
    Must run inside shard_map over ``axis_name`` with ``n`` devices."""
    import jax.numpy as jnp
    from jax import lax

    if n == 1:
        return flat
    size = flat.shape[0]
    pad = (-size) % n
    buf = jnp.pad(flat, (0, pad)).reshape(n, -1)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: chunk j's partial starts on device j+1 and rides
    # the ring accumulating one resident contribution per hop; after
    # n-1 hops device d holds the FULL sum of chunk d
    acc = jnp.take(buf, (idx - 1) % n, axis=0)
    for s in range(1, n):
        acc = lax.ppermute(acc, axis_name, perm)
        acc = acc + jnp.take(buf, (idx - 1 - s) % n, axis=0)

    # all-gather: rotate the finished chunks; after hop t device d
    # holds chunk (d - t) mod n in slot t
    parts = [acc]
    cur = acc
    for _ in range(n - 1):
        cur = lax.ppermute(cur, axis_name, perm)
        parts.append(cur)
    stacked = jnp.stack(parts)  # slot t = chunk (idx - t) % n
    order = (idx - jnp.arange(n)) % n  # chunk j lives in slot (idx-j)%n
    full = jnp.take(stacked, order, axis=0).reshape(-1)
    return full[:size]


def hierarchical_reduce_flat(flat, axis_name: str, n: int, local_n: int):
    """Two-tier all-reduce of a flat buffer for multi-host meshes:
    intra-host ``lax.psum`` over groups of ``local_n`` consecutive
    devices on the axis, then an inter-host ppermute ring (reduce-
    scatter + all-gather over H = n/local_n hops) run in ``local_n``
    parallel rings — one per local index, so every device participates
    and the host-to-host traffic is the ring-optimal 2(H-1)/H of the
    payload per link instead of an n-wide flat ring's mixed-tier hops.
    This is the NCCL hierarchical/tree schedule the reference's
    KVStoreNCCL+PS split approximated: fast links absorb the dense
    intra-host sum, only one tier's worth of aggregate crosses hosts.
    Must run inside shard_map over ``axis_name``; requires
    ``n % local_n == 0`` with hosts contiguous on the axis
    (host_local_count checks that)."""
    import jax.numpy as jnp
    from jax import lax

    L = int(local_n)
    H = n // L
    intra = [[h * L + i for i in range(L)] for h in range(H)]
    part = lax.psum(flat, axis_name, axis_index_groups=intra)
    if H == 1:
        return part
    size = flat.shape[0]
    pad = (-size) % H
    buf = jnp.pad(part, (0, pad)).reshape(H, -1)
    idx = lax.axis_index(axis_name)
    h_idx = idx // L
    # one ring per local index: device (h, i) -> ((h+1) % H, i)
    perm = [(h * L + i, ((h + 1) % H) * L + i)
            for h in range(H) for i in range(L)]

    # reduce-scatter over hosts (same schedule as ring_allreduce_flat,
    # ring position = host index)
    acc = jnp.take(buf, (h_idx - 1) % H, axis=0)
    for s in range(1, H):
        acc = lax.ppermute(acc, axis_name, perm)
        acc = acc + jnp.take(buf, (h_idx - 1 - s) % H, axis=0)

    # all-gather: rotate the finished chunks around the host ring
    parts = [acc]
    cur = acc
    for _ in range(H - 1):
        cur = lax.ppermute(cur, axis_name, perm)
        parts.append(cur)
    stacked = jnp.stack(parts)
    order = (h_idx - jnp.arange(H)) % H
    full = jnp.take(stacked, order, axis=0).reshape(-1)
    return full[:size]


def host_local_count(mesh) -> Optional[int]:
    """Per-host device count along a mesh's flattened device order,
    when every host's devices are CONTIGUOUS on the axis and equally
    sized — the layout hierarchical_reduce_flat's index arithmetic
    assumes.  None when the topology doesn't qualify (single device,
    ragged hosts, interleaved placement): callers fall back to the flat
    psum.  On a single-host mesh this returns n (H=1 — the hierarchical
    schedule degenerates to one intra-host psum, numerically identical
    to the flat reduction)."""
    try:
        devs = list(mesh.devices.flat)
        n = len(devs)
        if n < 2:
            return None
        procs = [int(getattr(d, "process_index", 0)) for d in devs]
        L = 1
        while L < n and procs[L] == procs[0]:
            L += 1
        if n % L:
            return None
        block_procs = []
        for h in range(n // L):
            block = procs[h * L:(h + 1) * L]
            if len(set(block)) != 1:
                return None  # ragged host
            block_procs.append(block[0])
        if len(set(block_procs)) != len(block_procs):
            return None  # a host's devices are split across blocks
        return L
    except Exception:
        return None


def pack_flats(grads: Mapping, plan: Sequence[Bucket]) -> List:
    """Pack ``grads`` (``{key: array}``) into one flat buffer per
    bucket, in plan order — the exact concat layout
    :func:`bucketed_reduce` reduces and the ZeRO-1 schedule scatters.
    The accumulation scan carries these buffers instead of the per-key
    tree so microbatch sums land directly in reduce layout."""
    from .. import optimizer as _opt

    return [_opt.pack_flat([grads[k] for k in b.keys]) for b in plan]


def bucketed_reduce(grads: Mapping, plan: Sequence[Bucket],
                    axis_name: str, *, n: int, mean: bool = False,
                    chain: Optional[bool] = None,
                    impl: Optional[str] = None,
                    local_n: Optional[int] = None,
                    flats: Optional[Sequence] = None) -> Dict:
    """Reduce ``grads`` (``{key: local array}``) bucket by bucket over
    ``axis_name`` inside shard_map; returns ``{key: reduced array}``.

    ``mean`` divides by ``n`` (psum-mean — the data-parallel gradient of
    a global-mean loss); each bucket is one flat concat → one reduction
    op; consecutive buckets chain via optimization_barrier.  ``impl``
    'hierarchical' needs ``local_n`` (host_local_count(mesh)); an
    unqualified topology falls back to the flat psum.  ``flats``
    (pre-packed per-bucket buffers from :func:`pack_flats` — the
    accumulation scan's carry) skips the concat; ``grads`` then only
    supplies the per-key shapes for the unpack.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    if chain is None:
        chain = chain_enabled()
    if impl is None:
        impl = impl_name()
    hier = (impl == "hierarchical" and n > 1 and local_n
            and 0 < int(local_n) <= n and n % int(local_n) == 0)
    out: Dict = {}
    anchor = None
    inv_n = 1.0 / float(n)
    for i, bucket in enumerate(plan):
        # mxbkt<i> names the bucket in every op's HLO metadata: the
        # device-trace walker (traceview) maps measured collective
        # time back to bucket i by this scope — the only channel that
        # survives into an XLA profile (BatchNorm stat psums and the
        # loss pmean are name-identical otherwise) — and charges the
        # pack/unpack (concat/slice) fusions to exchange overhead
        # instead of forward compute
        with jax.named_scope("mxbkt%03d" % i):
            leaves = [grads[k] for k in bucket.keys]
            if flats is not None:
                flat = flats[i]
            else:
                flat = leaves[0].ravel() if len(leaves) == 1 else \
                    jnp.concatenate([g.ravel() for g in leaves])
            if chain and anchor is not None:
                # reductions issue in reverse-layer order, NCCL-stream
                # style; the data dependency stops the all-reduce
                # combiner from re-fusing the buckets into one op
                flat, _ = lax.optimization_barrier((flat, anchor))
            if impl == "ring" and n > 1:
                red = ring_allreduce_flat(flat, axis_name, n)
            elif hier:
                red = hierarchical_reduce_flat(flat, axis_name, n,
                                               int(local_n))
            else:
                red = lax.psum(flat, axis_name)
            if mean and n > 1:
                red = red * jnp.asarray(inv_n, dtype=red.dtype)
            anchor = lax.slice(red, (0,), (1,))
            off = 0
            for key, g in zip(bucket.keys, leaves):
                sz = g.size
                out[key] = lax.slice(red, (off,),
                                     (off + sz,)).reshape(g.shape)
                off += sz
    return out


def accounting(plan: Sequence[Bucket]) -> List[Dict]:
    """Per-bucket collective accounting rows (count/bytes per
    reduction) — the MULTICHIP/SCALING artifact block."""
    return [{"bucket": i, "n_grads": len(b.keys), "bytes": int(b.nbytes),
             "dtype": b.dtype} for i, b in enumerate(plan)]


def plan_meta(plan: Optional[Sequence[Bucket]],
              cap_bytes: Optional[int] = None,
              tuning: Optional[Dict] = None) -> Dict:
    """Self-describing summary of one reduction schedule — stamped into
    the flight-recorder header (diagnostics.py), which a traceview
    summary carries on, so every dump records which bucket plan
    produced it.  ``tuning`` (plan_with_tuning's meta) records that —
    and from which plan file — the caps were autotuned rather than the
    env default."""
    plan = list(plan or ())
    out = {
        "n_buckets": len(plan),
        "total_bytes": sum(int(b.nbytes) for b in plan),
        "cap_bytes": bucket_cap_bytes() if cap_bytes is None
        else int(cap_bytes),
        "impl": impl_name(),
        "chained": chain_enabled(),
        "buckets": accounting(plan),
    }
    if tuning is not None:
        out["autotune"] = {
            "plan_path": tuning.get("plan_path"),
            "cap_bytes": tuning.get("cap_bytes"),
            "first_cap_bytes": tuning.get("first_cap_bytes"),
            "last_cap_bytes": tuning.get("last_cap_bytes"),
            "score": tuning.get("score"),
        }
    return out


def stamp_profiler(plan: Sequence[Bucket], *, impl: Optional[str] = None,
                   store_type: str = "tpu") -> None:
    """Record one issued bucket schedule: cumulative byte counters
    through the telemetry layer (profiler.py, /metrics) and one
    flight-recorder entry per bucket reduction (diagnostics.py), so the
    collective seq stream covers every reduction a rank issued.  The
    reductions themselves execute inside XLA, where the ``mxbkt%03d``
    scopes name them in a device trace: no host span is stamped for
    them here.  The chrome counters need a running profiler; the flight
    entries don't.  Never raises."""
    try:
        from .. import diagnostics as _diag
        from .. import profiler as _profiler

        if impl is None:
            impl = impl_name()
        # the byte counter is independent of profiler/flight state
        # (same contract as the kvstore verb fast paths): scrapers see
        # bucket_reduce traffic whenever the registry is live
        total = sum(int(b.nbytes) for b in plan)
        _diag.feed_kvstore_bytes("bucket_reduce", total)
        if _diag.flight_enabled():
            for i, b in enumerate(plan):
                with _diag.record_collective(
                        "bucket_reduce", keys=b.keys, bucket=i,
                        nbytes=int(b.nbytes), dtype=b.dtype,
                        args={"impl": impl, "type": store_type,
                              "in_graph": True}):
                    pass
        if _profiler.is_running():
            _profiler.record_bytes("kvstore:bucket_allreduce_bytes", total)
            _profiler.record_bytes("kvstore:bucket_allreduce_count",
                                   len(plan))
    except Exception:
        pass
