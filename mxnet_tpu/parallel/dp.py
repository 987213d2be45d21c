"""Data-parallel execution over a device mesh.

TPU rebuild of the reference's data-parallel machinery (SURVEY.md §2.3):
DataParallelExecutorGroup batch slicing (python/mxnet/module/
executor_group.py:128,266-288), kvstore 'device' tree-reduce
(src/kvstore/comm.h:484) and KVStoreNCCL ring allreduce
(src/kvstore/kvstore_nccl.h:281).

Design ("computation follows data"): the batch is sharded over the mesh's
``dp`` axis, parameters are replicated; XLA's SPMD partitioner then emits
the gradient AllReduce over ICI automatically inside the compiled step —
gradient exchange is fused INTO the backward pass, overlapping with it,
which is what the reference approximated with engine priorities
(python/mxnet/gluon/trainer.py:190).

Two entry points:
  * ``DataParallelRunner`` — shards an Executor's data inputs so
    ``Module(context=[...])`` trains SPMD with unchanged code.
  * ``FusedTrainStep``    — whole-step compilation for a gluon block:
    forward + loss + backward + fused optimizer in ONE XLA program (the
    kvstore('tpu') fast path; also the bench harness).

Gradient exchange: on a pure-dp multi-device mesh the step compiles
through ``shard_map`` with the gradients reduced in REVERSE-LAYER-ORDER
size-capped buckets (parallel/buckets.py, NCCL-DDP style) instead of
letting the SPMD partitioner fold everything into the single combined
synchronous all-reduce that round 5 found in the compiled HLO (zero
async start/done pairs).  Per-bucket reductions become operand-
ready while backward is still running, so XLA's latency-hiding
scheduler can emit async start/done pairs that overlap backward compute
— the TPU equivalent of the reference's engine-priority overlap
(python/mxnet/gluon/trainer.py:190, src/kvstore/kvstore_nccl.h:281).
``MXNET_KVSTORE_BUCKET_BYTES=0`` restores the monolithic SPMD path;
BatchNorm keeps GLOBAL-batch statistics through the sync-BN context
(ops/nn.py cross_device_batch_stats), so numerics match the monolithic
program.
"""
from __future__ import annotations

from .. import autograd
from .. import env as _env
from ..ndarray import NDArray
from .mesh import make_mesh

__all__ = ["DataParallelRunner", "FusedTrainStep", "shard_batch",
           "replicate", "zero1_stage", "zero1_momentum_buffers",
           "zero1_bucketed_update", "momenta_bytes_per_device"]


def _jax():
    import jax

    return jax


def _donate_safe_put(jax, arr, sharding):
    """``device_put`` for a buffer the compiled step will DONATE.
    ``device_put`` aliases its input when the placement already matches
    — same object, or (single-device target) a NEW Array wrapping the
    SAME buffer.  Donating an alias would consume a buffer the CALLER
    still owns (their NDArray would die mid-training), so copy in the
    aliased cases.  A genuine reshard onto multiple devices always
    materializes fresh per-shard buffers and passes through free.

    Exception: the async input pipeline (io_pipeline.py) marks its
    prefetched batches *disposable* — ownership transfers with the
    batch, nothing reads them afterwards — so those donate as-is, which
    is the zero-copy handoff the prefetch stage exists for."""
    placed = jax.device_put(arr, sharding)
    if placed is not arr:
        try:
            # both single-shard: alias iff the device buffer is shared
            if placed.unsafe_buffer_pointer() != \
                    arr.unsafe_buffer_pointer():
                return placed
        except Exception:
            # either side multi-shard: the reshard made fresh buffers
            # (the matching-sharding case returns `arr` itself above)
            return placed
    try:
        from .. import io_pipeline as _iop

        if _iop.take_disposable(arr):
            return placed
    except Exception:
        pass
    import jax.numpy as jnp

    return jax.device_put(jnp.copy(arr), sharding)


def _shardings(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())


def shard_batch(arr, mesh):
    """Place an array batch-sharded over the mesh's dp axis."""
    jax = _jax()
    data_sh, _ = _shardings(mesh)
    if isinstance(arr, NDArray):
        arr._data = jax.device_put(arr._data, data_sh)
        return arr
    return jax.device_put(arr, data_sh)


def replicate(arr, mesh):
    jax = _jax()
    _, rep = _shardings(mesh)
    if isinstance(arr, NDArray):
        arr._data = jax.device_put(arr._data, rep)
        return arr
    return jax.device_put(arr, rep)


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer-state sharding over the dp axis.  Replicating
# momenta on every rank (the default, and the reference's kvstore
# server-side-update layout mirrored onto every worker) wastes
# (dp-1)/dp of the optimizer-state HBM; ZeRO stage 1 gives each dp
# rank ownership of a 1/dp shard of every gradient bucket's momenta:
# the bucket's gradient arrives by REDUCE-SCATTER (each rank receives
# only its shard of the sum — half the wire bytes of an all-reduce),
# the momentum + parameter update runs on the shard (optimizer.py
# fused_sgd_mom_flat over the bucket's flat), and the updated parameter
# shard is ALL-GATHERED back to the replicated layout.  Composes with the
# bucketed reverse-layer-order schedule (parallel/buckets.py): bucket
# k's all-gather has no data dependency on bucket k+1's scatter or
# update, so XLA overlaps the gather with the next bucket's work.
# ---------------------------------------------------------------------------
def zero1_stage(override=None) -> int:
    """The selected ZeRO stage: explicit argument wins, else
    ``MXNET_ZERO_STAGE`` (0 = replicated, 1 = sharded momenta)."""
    stage = override if override is not None \
        else _env.get_int("MXNET_ZERO_STAGE")
    if stage not in (0, 1):
        raise ValueError("MXNET_ZERO_STAGE=%r: only stages 0 "
                         "(replicated) and 1 (sharded optimizer "
                         "state) exist" % (stage,))
    return int(stage)


def _dtype_itemsize(dtype) -> int:
    import numpy as np

    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        return {"bfloat16": 2, "float16": 2}.get(str(dtype), 4)


def momenta_bytes_per_device(moms) -> int:
    """Max per-device resident bytes across a momenta pytree, measured
    from the LIVE buffers' addressable shards (replicated arrays count
    full-size per device; zero1 flats count their 1/n shard) — the
    shared evidence both train-step tiers report."""
    import jax

    per_device = {}
    for m in jax.tree_util.tree_leaves(moms):
        try:
            for s in m.addressable_shards:
                key = repr(s.device)
                per_device[key] = per_device.get(key, 0) + \
                    int(s.data.nbytes)
        except Exception:
            per_device[""] = per_device.get("", 0) + int(m.nbytes)
    return max(per_device.values()) if per_device else 0


def zero1_momentum_buffers(plan, n: int):
    """GLOBAL flat zero momenta, one buffer per bucket, padded to a
    multiple of ``n`` — place them with ``P(dp_axis)`` so each device
    owns exactly its 1/n shard (the only copy anywhere)."""
    import jax.numpy as jnp

    bufs = []
    for b in plan:
        elems = int(b.nbytes) // _dtype_itemsize(b.dtype)
        padded = elems + ((-elems) % max(int(n), 1))
        bufs.append(jnp.zeros((padded,), dtype=b.dtype))
    return bufs


def zero1_bucket_elems(plan) -> list:
    """True (unpadded) element count of each bucket's flat buffer —
    the invariant the elastic restage re-slices by: padding depends on
    the dp size, the element count only on the bucket layout."""
    return [int(b.nbytes) // _dtype_itemsize(b.dtype) for b in plan]


def zero1_restage_flats(flats, plan, n_new: int):
    """Re-slice checkpointed GLOBAL flat momentum buffers for an
    ``n_new``-way dp axis (host numpy, before device placement): trim
    each bucket's flat to its true element count (dropping the old dp
    size's zero padding — the pad zone's momenta are zero by
    construction, gradients there are always zero) and re-pad to a
    multiple of ``n_new``.  Identity when the dp size is unchanged, so
    the bitwise same-world resume contract is untouched."""
    import numpy as np

    if len(flats) != len(plan):
        raise ValueError(
            "checkpoint has %d momentum buckets, this plan has %d — "
            "bucket caps changed between runs; pin bucket_bytes (or "
            "the same autotune plan) to resume"
            % (len(flats), len(plan)))
    out = []
    for bi, (flat, elems) in enumerate(zip(flats,
                                           zero1_bucket_elems(plan))):
        # host-side restage over checkpointed numpy blobs — no device
        # transfer hides here
        flat = np.asarray(flat).ravel()  # mxlint: disable=MXL004
        if flat.size < elems:
            raise ValueError(
                "momentum bucket %d holds %d elements, plan needs %d "
                "— the bucket LAYOUT changed (not just the dp size); "
                "elastic restage only re-slices identical bucket "
                "plans" % (bi, flat.size, elems))
        flat = flat[:elems]
        pad = (-elems) % max(int(n_new), 1)
        if pad:
            flat = np.pad(flat, (0, pad))
        out.append(flat)
    return out


def zero1_flats_to_tree(flats, plan, shapes):
    """Checkpointed stage-1 flat momenta → per-param momenta dict (the
    dp' = 1 / replicated side of the elastic restage).  ``shapes``
    maps param key → shape, in the plan's own key universe."""
    from .. import optimizer as _opt

    if len(flats) != len(plan):
        raise ValueError(
            "checkpoint has %d momentum buckets, this plan has %d"
            % (len(flats), len(plan)))
    out = {}
    for flat, bucket in zip(flats, plan):
        missing = [k for k in bucket.keys if k not in shapes]
        if missing:
            raise KeyError("restage: bucket keys %s not in the live "
                           "param tree" % missing[:4])
        arrs = _opt.unpack_flat_np(flat, [shapes[k]
                                          for k in bucket.keys])
        for k, a in zip(bucket.keys, arrs):
            out[k] = a
    return out


def zero1_tree_to_flats(tree, plan, n: int):
    """Per-param momenta dict → stage-1 GLOBAL flat buffers padded for
    an ``n``-way dp axis (the replicated → sharded side of the elastic
    restage); same packing order the in-graph update uses."""
    import numpy as np

    from .. import optimizer as _opt

    flats = []
    for bucket in plan:
        missing = [k for k in bucket.keys if k not in tree]
        if missing:
            raise KeyError("restage: checkpoint momenta missing keys "
                           "%s" % missing[:4])
        flat = _opt.pack_flat_np([tree[k] for k in bucket.keys])
        pad = (-flat.size) % max(int(n), 1)
        if pad:
            flat = np.pad(flat, (0, pad))
        flats.append(flat)
    return flats


def zero1_bucketed_update(grads, params, mom_shards, plan,
                          axis_name: str, n: int, *, lr, momentum, wd,
                          mean_n=None, sp_axis=None, chain=None,
                          flats=None):
    """One ZeRO-1 step over the bucket plan, inside shard_map.

    ``grads``/``params``: ``{key: local array}`` (grads are this
    device's UNreduced gradients; params are replicated views);
    ``mom_shards``: this device's per-bucket momentum shards (the
    device view of :func:`zero1_momentum_buffers`).  Per bucket, in
    reverse-layer issue order: flat-concat → (optional ``sp_axis``
    psum — sequence-parallel replicas contribute partial grads) →
    ``psum_scatter`` over ``axis_name`` → fused shard update →
    ``all_gather``.  Scatters are chained (optimization_barrier) like
    the replicated reduction schedule; gathers ride the dataflow, so
    bucket k's gather overlaps bucket k+1's scatter+update.  Returns
    ``({key: updated param}, [new momentum shards])``.

    ``flats`` (per-bucket pre-packed gradient buffers,
    :func:`buckets.pack_flats` layout — the accumulation scan's carry)
    replaces the concat; ``grads`` may then be None and ``params``
    supplies the per-key unpack shapes.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .. import optimizer as _opt
    from . import buckets as _buckets

    if chain is None:
        chain = _buckets.chain_enabled()
    mean_n = n if mean_n is None else int(mean_n)
    idx = lax.axis_index(axis_name)
    out = {}
    new_moms = []
    anchor = None
    for bi, bucket in enumerate(plan):
        leaves = [(grads if flats is None else params)[k]
                  for k in bucket.keys]
        flat_g = flats[bi] if flats is not None \
            else _opt.pack_flat(leaves)
        size = flat_g.shape[0]
        pad = (-size) % n
        if pad:
            flat_g = jnp.pad(flat_g, (0, pad))
        if sp_axis is not None:
            flat_g = lax.psum(flat_g, sp_axis)
        if chain and anchor is not None:
            # scatters issue in reverse layer order, NCCL-stream style
            flat_g, _ = lax.optimization_barrier((flat_g, anchor))
        # mxbkt<i>: bucket identity in the collective's HLO metadata —
        # the traceview walker's only handle on which reduce is which
        with jax.named_scope("mxbkt%03d" % bi):
            gsh = lax.psum_scatter(flat_g, axis_name,
                                   scatter_dimension=0, tiled=True)
        anchor = lax.slice(gsh, (0,), (1,))
        if mean_n > 1:
            gsh = gsh * jnp.asarray(1.0 / mean_n, gsh.dtype)
        flat_w = _opt.pack_flat([params[k] for k in bucket.keys])
        if pad:
            flat_w = jnp.pad(flat_w, (0, pad))
        shard = flat_w.shape[0] // n
        wsh = lax.dynamic_slice(flat_w, (idx * shard,), (shard,))
        with jax.named_scope("optimizer"):
            w_new, m_new = _opt.fused_sgd_mom_flat(
                wsh, gsh, mom_shards[bi], lr, momentum, wd)
        new_moms.append(m_new)
        with jax.named_scope("mxbkt%03d" % bi):
            full = lax.all_gather(w_new, axis_name, tiled=True)
        if pad:
            full = full[:size]
        off = 0
        for k, g in zip(bucket.keys, leaves):
            sz = g.size
            out[k] = lax.slice(full, (off,), (off + sz,)).reshape(g.shape)
            off += sz
    return out, new_moms


class DataParallelRunner:
    """Shards an Executor's data/label cells over the dp axis and
    replicates everything else (ref: executor_group.py decide_slices —
    except slicing becomes sharding metadata, not copies)."""

    def __init__(self, executor, contexts, data_names=None,
                 label_names=None):
        devices = []
        for c in contexts:
            d = c.jax_device()  # raises for a chip id that is not there
            if d not in devices:
                devices.append(d)
        if len(devices) < len(contexts):
            # reference cpu(i) contexts are logical views of the same
            # host pool: scripts like example/dsd/mlp.py bind
            # [cpu(0), cpu(1)] unconditionally.  Collapse onto the host
            # devices that exist (same math, fewer shards).  Naming one
            # chip twice has no such reading.
            if devices[0].platform != "cpu":
                raise ValueError(
                    "contexts %s name %d distinct %s device(s)"
                    % (list(contexts), len(devices),
                       devices[0].platform))
            import logging

            logging.getLogger(__name__).warning(
                "%d cpu contexts over %d host device(s) - collapsing "
                "(parallelism reduced)", len(contexts), len(devices))
        self.mesh = make_mesh((len(devices),), ("dp",), devices)
        self._executor = executor
        self._data_names = set(data_names or ())
        self._label_names = set(label_names or ())

    def set_input_names(self, data_names, label_names):
        self._data_names = set(data_names)
        self._label_names = set(label_names)

    def place(self) -> None:
        """(Re)apply shardings to the executor's live cells."""
        jax = _jax()
        data_sh, rep = _shardings(self.mesh)
        batch_names = self._data_names | self._label_names
        for name, cell in self._executor.arg_dict.items():
            sh = data_sh if name in batch_names else rep
            cell._data = jax.device_put(cell._data, sh)
        for cell in self._executor.aux_dict.values():
            cell._data = jax.device_put(cell._data, rep)


class FusedTrainStep:
    """One compiled XLA program per step: forward + loss + backward +
    optimizer update, gradients reduced over ICI by the SPMD partitioner.

    This is the structural equivalent of the reference's fully-cached
    GraphExecutor fast path (InitCachedOps + bulk segments + kvstore push),
    collapsed into a single jit.  Used by perfbench and dryrun_multichip.

    The SGD-momentum update has ONE path on one chip and on the
    replicated multi-chip path: ``optimizer.fused_sgd_mom_grouped``
    under the ``optimizer`` scope, each parameter updated where it lies
    in its own shape.  Parameters and momenta are donated, so each leaf
    is updated in place.  Only ZeRO-1 packs flats, a bucket at a time
    (``zero1_bucketed_update``: ``psum_scatter`` needs them).

    Parameters
    ----------
    block : initialized gluon HybridBlock
    loss_fn : gluon Loss block
    mesh : jax Mesh with a ``dp`` axis (optional extra axes for tp)
    optimizer : 'sgd' only fast-fused here (momentum supported)
    param_spec_fn : optional fn(param_name, shape) -> PartitionSpec for
        tensor-parallel parameter sharding over non-dp axes (ctx_group's
        TPU successor; see SURVEY.md §2.3 model-parallel row).
    """

    def __init__(self, block, loss_fn, mesh=None, learning_rate=0.05,
                 momentum=0.9, weight_decay=0.0, param_spec_fn=None,
                 dtype=None, bucket_bytes=None, zero_stage=None,
                 accum_steps=None):
        jax = _jax()
        self.mesh = mesh if mesh is not None else make_mesh((1,), ("dp",),
                                                            jax.devices()[:1])
        self._block = block
        self._loss_fn = loss_fn
        self._learning_rate = learning_rate
        self._momentum_cfg = momentum
        self._weight_decay = weight_decay
        self._param_spec_fn = param_spec_fn
        self._dtype = dtype
        # bucketed backward-overlapped gradient exchange (buckets.py):
        # None = MXNET_KVSTORE_BUCKET_BYTES (default 4 MiB), 0 = force
        # the monolithic SPMD reduction
        self._bucket_bytes = bucket_bytes
        # ZeRO stage: None = MXNET_ZERO_STAGE; 1 shards momenta over dp
        self._zero_stage = zero_stage
        # microbatch gradient accumulation inside the compiled step:
        # None = MXNET_GRAD_ACCUM_STEPS (default 1 = off)
        self._accum_steps = accum_steps
        self._zero1 = False
        self._bucketed = False
        self._bucket_plan = None
        self._built = False
        self._step_no = 0  # optimizer steps dispatched: mx.step's number

    def _build(self, sample_data):
        """Finish deferred param shapes with one eager forward, then compile
        the fused step (first call only)."""
        jax = _jax()
        from jax.sharding import NamedSharding, PartitionSpec as P

        # persistent XLA compilation cache (compile_cache.py):
        # a restarted run loads this step's executables from disk
        from ..compile_cache import enable as _cc_enable

        _cc_enable()

        from ..gluon.block import CachedOp

        block, loss_fn = self._block, self._loss_fn
        param_spec_fn = self._param_spec_fn
        learning_rate = self._learning_rate
        momentum = self._momentum_cfg
        weight_decay = self._weight_decay
        with autograd.pause():
            # settle deferred shapes in float32 — the user may hand a
            # bf16 or uint8 batch before the in-program cast happens
            settle = sample_data
            if str(sample_data.dtype) != "float32":
                settle = sample_data.astype("float32")
            # ...and where the parameters were initialised (the host,
            # unless the user named a context): an eager forward cannot
            # mix a chip-resident or mesh-sharded batch with
            # host-resident weights.  copyto, not as_in_context: the
            # sample's context TAG need not say where its buffer lives
            for p in block.collect_params().values():
                if p.list_ctx():
                    settle = settle.copyto(p.list_ctx()[0])
                    break
            block(settle)  # settles deferred initialization
        if self._dtype is not None:
            # whole-model cast — the reference's dtype-training story
            # (example/image-classification --dtype float16); on TPU the
            # natural choice is bfloat16 for MXU throughput
            block.cast(self._dtype)
        self._cached = CachedOp(block)
        self._cells = [p for (_, _, p) in self._cached._param_cells]
        self._aux_idx = set(self._cached._aux_positions)

        data_sh = NamedSharding(self.mesh, P("dp"))
        rep = NamedSharding(self.mesh, P())

        # parameter shardings (tensor parallel hooks)
        self._param_sh = []
        any_param_spec = False
        for (_, _, p) in self._cached._param_cells:
            spec = None
            if param_spec_fn is not None:
                spec = param_spec_fn(p.name, p.shape)
            if spec is not None:
                any_param_spec = True
            self._param_sh.append(
                NamedSharding(self.mesh, spec) if spec is not None else rep
            )
        self._data_sh, self._rep = data_sh, rep

        raw_fn = self._cached._raw_fn
        n_params = len(self._cells)
        loss_block = loss_fn
        aux_idx = self._aux_idx
        # ordered aux positions: the trace returns updated aux states in
        # this order, and the accumulation scan carries them as a tuple
        aux_order = list(self._cached._aux_positions)
        lr, mom_c, wd = learning_rate, momentum, weight_decay

        # scoped remat + microbatch accumulation (remat.py knobs), both
        # resolved at build time like the reference's graph-init reads
        from ..remat import grad_accum_steps, remat_policy

        accum = grad_accum_steps(self._accum_steps)
        self._grad_accum = accum
        remat_pol = remat_policy()

        import jax.numpy as _jnp
        from jax import lax as _lx

        compute_dtype = _jnp.dtype(self._dtype) if self._dtype else \
            _jnp.float32

        # ---- bucketed backward-overlapped gradient exchange ----------
        # pure-dp multi-device mesh: compile the step through shard_map
        # with per-bucket reductions (reverse layer order, buckets.py)
        # instead of the partitioner's single combined all-reduce.
        # Tensor-parallel param shardings keep the monolithic SPMD path
        # (their gradients are not pure dp replicas).
        from . import buckets as _buckets

        cap = self._bucket_bytes if self._bucket_bytes is not None \
            else _buckets.bucket_cap_bytes()
        n_dp = int(self.mesh.devices.size)
        self._bucketed = bool(
            cap != 0 and tuple(self.mesh.axis_names) == ("dp",)
            and n_dp > 1 and not any_param_spec)
        self._bucket_tuning = None
        if self._bucketed:
            grad_entries = [
                (i, tuple(self._cells[i].data()._data.shape),
                 self._cells[i].data()._data.dtype)
                for i in range(n_params) if i not in aux_idx]
            # autotuned caps (MXNET_AUTOTUNE_PLAN / MXNET_AUTOTUNE_DIR)
            # replace the fixed env cap when a tuned plan matches this
            # exchange; an explicit bucket_bytes= pins the cap and
            # bypasses tuning
            self._bucket_plan, self._bucket_tuning = \
                _buckets.plan_with_tuning(grad_entries,
                                          self._bucket_bytes)
            if self._bucket_tuning is not None:
                cap = self._bucket_tuning["cap_bytes"]
        plan = self._bucket_plan
        # ZeRO-1: shard the momenta over dp (zero1_bucketed_update
        # below).  Needs the bucketed shard_map path — its reduce-
        # scatter/all-gather ride the bucket schedule; a monolithic or
        # single-device build keeps the replicated layout.
        stage = zero1_stage(self._zero_stage)
        self._zero1 = bool(stage == 1 and self._bucketed)
        # SDC fingerprint vote (mxnet_tpu/sdc.py): per-bucket bit-exact
        # fingerprints of the post-update params (+ replicated momenta)
        # computed INSIDE the single-step program under lax.cond on the
        # step counter and all-gathered over dp.  Needs the bucketed
        # multi-device dp path (the buckets ARE the fingerprint units,
        # and a vote needs >1 replica); off by default — the disabled
        # path compiles the exact same graph as before.
        from .. import sdc as _sdcmod

        self._sdc_n = _sdcmod.check_every_n()
        self._sdc = bool(self._sdc_n > 0 and self._bucketed)
        if stage == 1 and not self._bucketed:
            import logging

            logging.getLogger(__name__).warning(
                "MXNET_ZERO_STAGE=1 requested but this step is not on "
                "the bucketed multi-device dp path — momenta stay "
                "replicated")
        # flight-recorder header: which reduction schedule this process
        # is issuing (diagnostics.py; --health cross-checks it per rank)
        from .. import diagnostics as _diag
        from .. import optimizer as _opt

        plan_meta_v = _buckets.plan_meta(plan, cap,
                                         tuning=self._bucket_tuning) \
            if self._bucketed else None
        # hierarchical impl: per-host device count along the dp axis
        # (None on unqualified topologies -> flat psum fallback)
        hier_local_n = _buckets.host_local_count(self.mesh) \
            if self._bucketed and _buckets.impl_name() == "hierarchical" \
            else None
        zero1 = self._zero1
        if self._bucketed:
            _diag.set_bucket_plan(plan_meta_v, owner=id(self))
        else:
            # clear a stale plan THIS step stamped on an earlier
            # bucketed build (it reduces monolithically now and its
            # dumps must say so); a plan another live step is
            # executing under is left alone
            _diag.set_bucket_plan(None, owner=id(self))

        def step_body(param_vals, mom_vals, data, label, key_root, ctr,
                      sharded: bool):
            # integer batches (uint8 pipelines — 4x less host->device
            # traffic) cast to the compute dtype INSIDE the program,
            # where XLA fuses the cast into the first conv
            if data.dtype != compute_dtype:
                with jax.named_scope("cast"):
                    data = data.astype(compute_dtype)
            # fold the per-step counter inside the fused program: no
            # separate host-side fold_in dispatch per step
            key = jax.random.fold_in(key_root, ctr)
            if sharded:
                # decorrelate per-device random ops (dropout masks)
                key = jax.random.fold_in(key, _lx.axis_index("dp"))
            diff = {i: v for i, v in enumerate(param_vals) if i not in aux_idx}
            aux = {i: v for i, v in enumerate(param_vals) if i in aux_idx}

            def pure_loss(diff_params):
                allp = [diff_params[i] if i in diff_params else aux[i]
                        for i in range(n_params)]
                outs = raw_fn(key, data, *allp, _training=True, _n_inputs=1)
                outs = outs if isinstance(outs, tuple) else (outs,)
                n_aux = len(aux_idx)
                visible = outs[: len(outs) - n_aux] if n_aux else outs
                new_aux = outs[len(outs) - n_aux:] if n_aux else ()
                out_nd = NDArray.from_raw(visible[0])
                lab_nd = NDArray.from_raw(label)
                with autograd._RecordingScope(False, True):
                    loss = loss_block(out_nd, lab_nd)
                return loss._data.mean(), (new_aux, visible[0])

            # MXNET_BACKWARD_DO_MIRROR: keep only conv/matmul residuals,
            # rematerialize activations in backward (remat.py)
            from ..remat import maybe_checkpoint

            flats = None
            if accum == 1:
                (loss_val, (new_aux, logits)), grads = jax.value_and_grad(
                    maybe_checkpoint(pure_loss), has_aux=True)(diff)
            else:
                # MXNET_GRAD_ACCUM_STEPS: lax.scan over microbatches
                # inside the SAME program — one microbatch of
                # activations live at a time, gradients accumulated
                # locally (per-bucket flats on the bucketed/zero1 paths,
                # riding the reduce layout) and reduced/applied ONCE
                # after the scan, so comm + optimizer cost stay
                # amortized over the effective batch.
                if data.shape[0] % accum:
                    raise ValueError(
                        "MXNET_GRAD_ACCUM_STEPS=%d does not divide the "
                        "per-device batch %d" % (accum, data.shape[0]))
                mb = data.shape[0] // accum
                mb_data = data.reshape((accum, mb) + data.shape[1:])
                mb_label = label.reshape((accum, mb) + label.shape[1:])
                aux0 = tuple(aux[i] for i in aux_order)
                # sharded == the bucketed shard_map path: accumulate
                # straight into the per-bucket flat buffers the one
                # reduce consumes
                use_flats = sharded

                def micro_loss(diff_params, aux_t, data_c, label_c,
                               key_c):
                    by_pos = dict(zip(aux_order, aux_t))
                    allp = [diff_params[i] if i in diff_params
                            else by_pos[i] for i in range(n_params)]
                    outs = raw_fn(key_c, data_c, *allp, _training=True,
                                  _n_inputs=1)
                    outs = outs if isinstance(outs, tuple) else (outs,)
                    n_aux = len(aux_idx)
                    visible = outs[: len(outs) - n_aux] if n_aux else outs
                    new_aux_t = outs[len(outs) - n_aux:] if n_aux else ()
                    out_nd = NDArray.from_raw(visible[0])
                    lab_nd = NDArray.from_raw(label_c)
                    with autograd._RecordingScope(False, True):
                        loss = loss_block(out_nd, lab_nd)
                    return loss._data.mean(), (new_aux_t, visible[0])

                def accum_body(carry, xs):
                    aux_c, acc = carry
                    data_c, label_c, idx = xs
                    # per-microbatch rng stream (dropout masks must not
                    # repeat across microbatches)
                    key_c = jax.random.fold_in(key, idx)
                    (loss_m, (new_aux_t, logits_m)), g = \
                        jax.value_and_grad(
                            maybe_checkpoint(
                                lambda d: micro_loss(d, aux_c, data_c,
                                                     label_c, key_c)),
                            has_aux=True)(diff)
                    if use_flats:
                        gf = _buckets.pack_flats(g, plan)
                        acc = [a + f for a, f in zip(acc, gf)]
                    else:
                        acc = {i: acc[i] + g[i] for i in acc}
                    return (new_aux_t, acc), (loss_m, logits_m)

                if use_flats:
                    acc0 = [_jnp.zeros(sum(diff[k].size for k in b.keys),
                                       dtype=_jnp.dtype(b.dtype))
                            for b in plan]
                else:
                    acc0 = {i: _jnp.zeros_like(v)
                            for i, v in diff.items()}
                (new_aux, acc), (losses, logits_m) = _lx.scan(
                    accum_body, (aux0, acc0),
                    (mb_data, mb_label, _jnp.arange(accum)))
                # mean of the microbatch means == the full-batch mean
                # (equal microbatches); 1/accum is dyadic for the
                # power-of-two factors the knob is used with, so the
                # scale costs no precision there
                loss_val = losses.mean()
                logits = logits_m.reshape((mb * accum,)
                                          + logits_m.shape[2:])
                grads = None
                if use_flats:
                    flats = [f * _jnp.asarray(1.0 / accum, f.dtype)
                             for f in acc]
                else:
                    grads = {i: g * _jnp.asarray(1.0 / accum, g.dtype)
                             for i, g in acc.items()}

            if sharded:
                loss_val = _lx.pmean(loss_val, "dp")
            if sharded and zero1:
                # ZeRO-1: raw per-device grads go straight into the
                # reduce-scatter → shard-update → all-gather schedule;
                # mom_vals is the per-bucket momentum-shard list
                upd, new_moms = zero1_bucketed_update(
                    grads, diff, mom_vals, plan, "dp", n_dp,
                    lr=lr, momentum=mom_c, wd=wd, flats=flats)
                aux_iter = iter(new_aux)
                new_params = [next(aux_iter) if i in aux_idx else upd[i]
                              for i in range(n_params)]
                return new_params, new_moms, loss_val, logits
            if sharded:
                # pmean of the per-device grads of the per-device mean
                # loss = the global-batch gradient; issued per bucket in
                # reverse layer order so later-layer reductions overlap
                # earlier-layer backward compute.  impl=hierarchical
                # reduces intra-host first, then rings the host tier
                # (local_n keyed off the mesh's host topology; an
                # unqualified topology falls back to the flat psum
                # inside bucketed_reduce)
                grads = _buckets.bucketed_reduce(
                    grads if flats is None else diff, plan, "dp",
                    n=n_dp, mean=True, local_n=hier_local_n,
                    flats=flats)

            # the update runs leaf by leaf on the arrays the step was
            # given (optimizer.py: no flat copy of parameters, gradients
            # or momenta), so each donated leaf can alias its output
            diff_keys = [i for i in range(n_params) if i not in aux_idx]
            with jax.named_scope("optimizer"):
                new_p, new_m = _opt.fused_sgd_mom_grouped(
                    diff_keys, param_vals, grads, mom_vals,
                    lr, mom_c, wd)
            aux_iter = iter(new_aux)
            new_params = [next(aux_iter) if i in aux_idx else new_p[i]
                          for i in range(n_params)]
            new_moms = [mom_vals[i] if i in aux_idx else new_m[i]
                        for i in range(n_params)]
            return new_params, new_moms, loss_val, logits

        if self._bucketed:
            from jax import shard_map

            from ..ops import nn as _nn_ops

            def local_step(param_vals, mom_vals, data, label, key_root,
                           ctr):
                # batch-statistics ops (BatchNorm moments, SoftmaxOutput
                # batch/valid normalization) reduce over dp during this
                # trace: per-device program, GLOBAL-batch semantics
                with _nn_ops.cross_device_batch_stats("dp"):
                    return step_body(param_vals, mom_vals, data, label,
                                     key_root, ctr, sharded=True)

            # zero1: the momenta list is per-bucket flats SHARDED over
            # dp (each device's view is its own 1/n shard); replicated
            # otherwise
            mom_spec = [P("dp")] * len(plan) if zero1 else P()
            step = shard_map(
                local_step, mesh=self.mesh,
                in_specs=(P(), mom_spec, P("dp"), P("dp"), P(), P()),
                out_specs=(P(), mom_spec, P(), P("dp")),
                check_vma=False)
            step_sdc = None
            if self._sdc:
                sdc_n = self._sdc_n

                def local_step_sdc(param_vals, mom_vals, data, label,
                                   key_root, ctr):
                    with _nn_ops.cross_device_batch_stats("dp"):
                        new_params, new_moms, loss_val, logits = \
                            step_body(param_vals, mom_vals, data,
                                      label, key_root, ctr,
                                      sharded=True)
                    groups = []
                    for b in plan:
                        leaves = [new_params[i] for i in b.keys]
                        if not zero1:
                            # replicated momenta vote too; zero1
                            # shards differ per rank by design
                            leaves += [new_moms[i] for i in b.keys]
                        groups.append(leaves)

                    def _fps():
                        return _jnp.stack(
                            [_sdcmod.tree_fingerprint(g)
                             for g in groups])

                    # the param-bytes pass runs ONLY on cadence steps
                    # (lax.cond); the always-on all_gather moves
                    # n_buckets uint32s — noise
                    fp = _lx.cond(
                        ctr % sdc_n == 0, _fps,
                        lambda: _jnp.zeros((len(plan),), _jnp.uint32))
                    rows = _lx.all_gather(fp, "dp")
                    return new_params, new_moms, loss_val, logits, rows

                step_sdc = shard_map(
                    local_step_sdc, mesh=self.mesh,
                    in_specs=(P(), mom_spec, P("dp"), P("dp"), P(),
                              P()),
                    out_specs=(P(), mom_spec, P(), P("dp"), P()),
                    check_vma=False)
        else:
            step_sdc = None

            def step(param_vals, mom_vals, data, label, key_root, ctr):
                return step_body(param_vals, mom_vals, data, label,
                                 key_root, ctr, sharded=False)

        # momenta shardings: per-bucket flats sharded over dp under
        # zero1 (the 1/n shard is the only copy), else the param
        # shardings (replicated / tensor-parallel)
        from jax.sharding import PartitionSpec as _PS

        self._mom_sh = [NamedSharding(self.mesh, _PS("dp"))
                        for _ in plan] if self._zero1 else self._param_sh
        donate = (0, 1)  # params + momenta buffers are donated: in-place update
        # the K-step variants additionally donate the batch buffers
        # (argnums 2, 3): run_steps re-places them per dispatch through
        # _donate_safe_put, so the program may reuse K batches of HBM
        # as scratch (ROADMAP item 5).  The single-step path keeps
        # data/label UNdonated: bench and user loops legitimately feed
        # the same committed batch every call (the auditor's committed
        # baseline records this as accepted).
        donate_k = (0, 1, 2, 3)
        # per-site audit metadata: the auditor cross-checks THIS
        # step's traced collective schedule against THIS plan (the
        # global flight-recorder header may belong to another step)
        step_meta = {"compute_dtype": str(_jnp.dtype(compute_dtype)),
                     "bucket_plan": plan_meta_v,
                     # the auditor cross-checks the declared remat
                     # policy against the traced program (a policy that
                     # rematerializes nothing is a finding) and scores
                     # overlap accum-aware
                     "remat_policy": remat_pol,
                     "grad_accum_steps": accum}
        # recompile tracking (diagnostics.py): count/time every XLA
        # compilation these step programs trigger and warn on
        # shape/dtype churn — a silent recompilation storm doubles step
        # time with no error anywhere
        # the sdc variant additionally returns the gathered
        # (n_dp, n_buckets) fingerprint matrix; the K-step scan
        # variants below keep the plain program (per-step cadence
        # needs per-step dispatch)
        step_fn, step_out_sh = (step, (self._param_sh, self._mom_sh,
                                       rep, data_sh))
        if step_sdc is not None:
            step_fn = step_sdc
            step_out_sh = step_out_sh + (rep,)
        self._step = _diag.instrument_jit(
            "FusedTrainStep.step",
            jax.jit(
                step_fn,
                in_shardings=(self._param_sh, self._mom_sh, data_sh,
                              data_sh, rep, rep),
                out_shardings=step_out_sh,
                donate_argnums=donate,
            ), meta=step_meta)

        # K steps inside ONE program via lax.scan — the TPU analogue of
        # the reference engine's bulk execution (engine.set_bulk_size):
        # per-dispatch host latency amortizes over K, which
        # dominates at small batch.  Batches carry a leading K dim.
        from jax import lax as _lax

        def multi_step(param_vals, mom_vals, datas, labels, key_root,
                       ctr0):
            def body(carry, xs):
                params, moms, ctr = carry
                data, label = xs
                new_params, new_moms, loss_val, _ = step(
                    params, moms, data, label, key_root, ctr)
                return (new_params, new_moms, ctr + 1), loss_val

            (fparams, fmoms, _), losses = _lax.scan(
                body, (param_vals, mom_vals, ctr0), (datas, labels))
            return fparams, fmoms, losses

        from jax.sharding import PartitionSpec as _P

        kdata_sh = NamedSharding(self.mesh, _P(None, "dp"))
        self._multi_step = _diag.instrument_jit(
            "FusedTrainStep.multi_step",
            jax.jit(
                multi_step,
                in_shardings=(self._param_sh, self._mom_sh, kdata_sh,
                              kdata_sh, rep, rep),
                out_shardings=(self._param_sh, self._mom_sh, rep),
                donate_argnums=donate_k,
            ), meta=step_meta)

        # same-batch variant: the batch is closed over once instead of
        # materializing K copies in HBM (bench/burn-in path)
        def multi_step_same(k):
            def fn(param_vals, mom_vals, data, label, key_root, ctr0):
                def body(carry, _):
                    params, moms, ctr = carry
                    new_params, new_moms, loss_val, _ = step(
                        params, moms, data, label, key_root, ctr)
                    return (new_params, new_moms, ctr + 1), loss_val

                (fparams, fmoms, _), losses = _lax.scan(
                    body, (param_vals, mom_vals, ctr0), None, length=k)
                return fparams, fmoms, losses

            # k in the name: each K-variant is its own jit whose first
            # compile is expected, not shape churn — one shared row
            # would fire a false RECOMPILATION STORM on the second k
            return _diag.instrument_jit(
                "FusedTrainStep.multi_step_same[k=%d]" % k,
                jax.jit(
                    fn,
                    in_shardings=(self._param_sh, self._mom_sh, data_sh,
                                  data_sh, rep, rep),
                    out_shardings=(self._param_sh, self._mom_sh, rep),
                    donate_argnums=donate_k,
                ), meta=step_meta)

        self._multi_step_same = {}
        self._multi_step_same_fn = multi_step_same

        import jax.numpy as jnp

        from .. import random as _random

        if self._zero1:
            # ZeRO-1 momenta: one flat padded buffer per bucket,
            # sharded over dp at placement (the 1/dp per-rank shard
            # is the whole point — see optimizer_state_bytes_per_rank)
            self._moms = zero1_momentum_buffers(plan, n_dp)
        else:
            self._moms = [jnp.zeros_like(p.data()._data)
                          for p in self._cells]
        try:
            self._key_root = jax.device_put(_random._next_key(), rep)
        except Exception:
            # abstract-topology mesh (AOT lowering via lower_only):
            # nothing executes, so placement is irrelevant
            self._key_root = _random._next_key()
        self._key_gen = _random._generation
        self._key_ctr = 0
        self._placed = False
        self._last_sdc_rows = None
        self._sdc_guard = _sdcmod.SDCGuard(every_n=self._sdc_n) \
            if self._sdc else None
        self._built = True

    @property
    def bucketed(self) -> bool:
        """True once built on the bucketed shard_map path."""
        return self._built and self._bucketed

    @property
    def zero1(self) -> bool:
        """True once built with ZeRO-1 sharded optimizer state."""
        return self._built and self._zero1

    def optimizer_state_bytes_per_rank(self):
        """Momenta bytes RESIDENT on one device, measured from the
        live buffers' addressable shards (not computed from the plan)
        — the bench memory block's evidence that ZeRO-1 really holds
        ~1/dp of the replicated optimizer state per rank."""
        if not self._built:
            return None
        if not self._placed:
            self._place_params()
        return momenta_bytes_per_device(self._moms)

    def bucket_accounting(self):
        """Per-bucket collective accounting rows ({bucket, n_grads,
        bytes, dtype}; None on the monolithic path)."""
        if not (self._built and self._bucketed):
            return None
        from . import buckets as _buckets

        return _buckets.accounting(self._bucket_plan)

    def bucket_tuning(self):
        """The autotune meta the bucket plan was built under (caps +
        plan-file provenance; None when the env default applied or the
        step is monolithic)."""
        if not (self._built and self._bucketed):
            return None
        return self._bucket_tuning

    def _stamp_bucket_telemetry(self):
        """Per-bucket flight-recorder entries + byte counters at
        dispatch time: the issue schedule.  The reductions execute
        inside XLA, where the ``mxbkt%03d`` scopes name them."""
        if self._bucketed:
            from . import buckets as _buckets

            _buckets.stamp_profiler(self._bucket_plan)

    def _place_params(self):
        jax = _jax()
        for p, sh in zip(self._cells, self._param_sh):
            p.data()._data = jax.device_put(p.data()._data, sh)
        self._moms = [jax.device_put(m, sh)
                      for m, sh in zip(self._moms, self._mom_sh)]
        self._param_vals = [p.data()._data for p in self._cells]
        self._param_vt = [p.data()._vt for p in self._cells]
        self._placed = True

    def _fresh_params(self):
        """Last step's outputs as this step's inputs, unless someone
        mutated a parameter cell in between (version token check — the
        NDArray cell's write-versioning contract)."""
        params = self._param_vals
        for i, p in enumerate(self._cells):
            cell = p.data()
            if cell._vt is not self._param_vt[i]:
                params[i] = cell._data
        return params

    def _refresh_key(self):
        """Honor an ``mx.random.seed()`` called since build."""
        from .. import random as _random

        if self._key_gen != _random._generation:
            self._key_root = _jax().device_put(_random._next_key(),
                                               self._rep)
            self._key_gen = _random._generation
            self._key_ctr = 0

    def run_steps(self, data, label, steps=None):
        """Run K optimizer steps as ONE compiled program (lax.scan).

        ``data``/``label`` either carry a leading K dimension (one batch
        per step) or are single batches reused ``steps`` times (bench /
        burn-in).  Returns the per-step losses as an NDArray of shape
        (K,).  Amortizes per-dispatch latency — the reference's bulk
        path (engine.set_bulk_size, MXNET_ENGINE_BULK_SIZE), TPU-style.
        """
        from .. import profiler as _profiler

        first = self._step_no + 1
        with _profiler.span("mx.step", cat="dispatch", step=first):
            losses, k = self._k_steps(data, label, steps)
        self._step_no += k
        return losses

    def _k_steps(self, data, label, steps):
        jax = _jax()
        from .. import profiler as _profiler

        if not self._built:
            d0 = data if isinstance(data, NDArray) else NDArray(data)
            if steps is None:  # leading dim is K: build on one batch
                d0 = NDArray.from_raw(d0._data[0])
            self._build(d0)
        if not self._placed:
            self._place_params()
        with _profiler.span("mx.step.feed", cat="dispatch"):
            raw_data = data._data if isinstance(data, NDArray) else data
            raw_label = label._data if isinstance(label, NDArray) \
                else label
            if self._dtype is not None:
                raw_data = raw_data.astype(self._dtype)
            from jax.sharding import NamedSharding, PartitionSpec as P

            if steps is not None:
                # same batch every step: close over ONE on-device copy
                # instead of materializing K in HBM (donated to the
                # program — _donate_safe_put never aliases the caller's
                # buffer)
                k = int(steps)
                raw_data = _donate_safe_put(jax, raw_data, self._data_sh)
                raw_label = _donate_safe_put(jax, raw_label,
                                             self._data_sh)
                runner = self._multi_step_same.get(k)
                if runner is None:
                    runner = self._multi_step_same_fn(k)
                    self._multi_step_same[k] = runner
            else:
                k = raw_data.shape[0]
                kdata_sh = NamedSharding(self.mesh, P(None, "dp"))
                raw_data = _donate_safe_put(jax, raw_data, kdata_sh)
                raw_label = _donate_safe_put(jax, raw_label, kdata_sh)
                runner = self._multi_step
            params = self._fresh_params()
            self._refresh_key()
            ctr0 = self._key_ctr + 1
            self._key_ctr += k
        from .. import traceview as _traceview

        if _profiler.is_running():
            # profiling path: block on the dispatch so the span is the
            # step's DEVICE wall time — the lane io:* prefetch spans
            # must be judged against (the merged-trace overlap
            # evidence); same block-when-profiling stance as the bulk
            # fit path's step timing
            t0 = _profiler._now_us()
            with _traceview.step_window("FusedTrainStep", k=k) as _tvw:
                new_params, self._moms, losses = runner(
                    params, self._moms, raw_data, raw_label,
                    self._key_root, ctr0)
                if _tvw is not None:
                    _tvw.block(losses)
            jax.block_until_ready(losses)
            _profiler.record_span("FusedTrainStep.run_steps[k=%d]" % k,
                                  t0, _profiler._now_us() - t0,
                                  cat="step")
        else:
            with _traceview.step_window("FusedTrainStep", k=k) as _tvw:
                new_params, self._moms, losses = runner(
                    params, self._moms, raw_data, raw_label,
                    self._key_root, ctr0)
                if _tvw is not None:
                    _tvw.block(losses)
        self._stamp_bucket_telemetry()
        self._param_vals = new_params
        for i, (p, v) in enumerate(zip(self._cells, new_params)):
            cell = p.data()
            cell._data = v
            token = object()
            cell._vt = token
            self._param_vt[i] = token
        return NDArray.from_raw(losses), k

    def lower_only(self, data, label):
        """AOT-lower the single-step program WITHOUT executing — shape
        specs only, so the mesh may be built from an abstract topology
        (jax.experimental.topologies) with no attached hardware.  Used
        by parallel/overlap.py to measure collective/compute overlap
        from the compiled schedule of the REAL dryrun program."""
        jax = _jax()
        import numpy as np

        if not self._built:
            self._build(data if isinstance(data, NDArray) else
                        NDArray(data))
        raw_data = data._data if isinstance(data, NDArray) else data
        raw_label = label._data if isinstance(label, NDArray) else label
        dtype = self._dtype if self._dtype is not None else raw_data.dtype

        def spec(shape, dt, sh):
            return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=sh)

        p_specs = [spec(p.data()._data.shape, p.data()._data.dtype, sh)
                   for p, sh in zip(self._cells, self._param_sh)]
        m_specs = [spec(m.shape, m.dtype, sh)
                   for m, sh in zip(self._moms, self._mom_sh)]
        d_spec = spec(raw_data.shape, dtype, self._data_sh)
        l_spec = spec(raw_label.shape, raw_label.dtype, self._data_sh)
        from .. import random as _random

        key = _random._next_key()
        k_spec = spec(key.shape, key.dtype, self._rep)
        c_spec = spec((), np.int32, self._rep)
        return self._step.lower(p_specs, m_specs, d_spec, l_spec, k_spec,
                                c_spec)

    def __call__(self, data, label):
        """Run one optimizer step; returns (loss, logits) NDArrays."""
        from .. import profiler as _profiler

        self._step_no += 1
        with _profiler.span("mx.step", cat="dispatch", step=self._step_no):
            return self._one_step(data, label)

    def _one_step(self, data, label):
        jax = _jax()
        from .. import profiler as _profiler

        if not self._built:
            self._build(data if isinstance(data, NDArray) else NDArray(data))
        if not self._placed:
            self._place_params()
        with _profiler.span("mx.step.feed", cat="dispatch"):
            raw_data = data._data if isinstance(data, NDArray) else data
            raw_label = label._data if isinstance(label, NDArray) \
                else label
            if self._dtype is not None:
                raw_data = raw_data.astype(self._dtype)
            raw_data = jax.device_put(raw_data, self._data_sh)
            raw_label = jax.device_put(raw_label, self._data_sh)
            params = self._fresh_params()
            self._refresh_key()
            self._key_ctr += 1
        from .. import traceview as _traceview

        if self._sdc:
            with _traceview.step_window("FusedTrainStep") as _tvw:
                new_params, self._moms, loss, logits, rows = self._step(
                    params, self._moms, raw_data, raw_label,
                    self._key_root, self._key_ctr)
                if _tvw is not None:
                    _tvw.block(loss)
            self._last_sdc_rows = rows
            if self._key_ctr % self._sdc_n == 0:
                # one tiny host read per cadence step; a corrupt
                # device trips dump + exit 87 (supervised) inside
                self._sdc_guard.check_rows(rows, step=self._key_ctr)
        else:
            with _traceview.step_window("FusedTrainStep") as _tvw:
                new_params, self._moms, loss, logits = self._step(
                    params, self._moms, raw_data, raw_label,
                    self._key_root, self._key_ctr
                )
                if _tvw is not None:
                    _tvw.block(loss)
        self._stamp_bucket_telemetry()
        self._param_vals = new_params
        for i, (p, v) in enumerate(zip(self._cells, new_params)):
            cell = p.data()
            cell._data = v
            token = object()
            cell._vt = token
            self._param_vt[i] = token
        return NDArray.from_raw(loss), NDArray.from_raw(logits)
