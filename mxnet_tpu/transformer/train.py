"""Transformer-LM training: one compiled step + a checkpointed fit loop.

``TransformerTrainStep`` is the functional-tier sibling of
``parallel/dp.py``'s FusedTrainStep: forward + loss + backward +
optimizer in ONE XLA program, compiled through shard_map over a mesh
with a ``dp`` axis and (for long-context runs) an ``sp`` axis the
attention impl shards the sequence over.  The gradient exchange rides
the SAME bucket machinery as the conv workloads
(``buckets.plan_with_tuning`` — so ``mxnet_tpu.autotune`` plans apply
to the attention-dominated comm pattern too), and the optimizer update
is either:

  * replicated (ZeRO stage 0, and one chip): bucketed all-reduce,
    then the update leaf by leaf, each parameter where it lies
    (optimizer.py ``fused_sgd_mom_grouped``), or
  * ZeRO-1 (``MXNET_ZERO_STAGE=1``): per-bucket reduce-scatter →
    fused update on this rank's momentum shard → param all-gather
    (parallel/dp.py ``zero1_bucketed_update``), so each dp rank holds
    1/dp of the optimizer state.

The step trains whatever block ``TransformerConfig`` spells (model.py):
for a configuration with expert layers the routers' selection biases
live in the parameter dict (so they are saved and restored with it) but
take no gradient, have no momentum and never reach the optimizer: the
step moves them by their own rule from the assignment counts, which it
returns beside the loss (``routing_counters``).  Such a configuration
trains on ``dp`` meshes; ``sp`` and the generation forwards raise.

``fit`` rides the existing robustness stack unchanged: elastic
checkpoint shards (checkpoint.py manifest — the sharded momenta travel
in ``optimizer_states``), chaos kill/delay hooks at the same loop
points Module.fit exposes, flight-recorder stamping per step, and
step metrics (tokens/s) through diagnostics.
"""
from __future__ import annotations

import pickle
import time
from typing import Dict, List, Optional

from .. import env as _env
from ..remat import remat_policy
from . import model as _model
from .model import TransformerConfig

__all__ = ["TransformerTrainStep"]


def _jax():
    import jax

    return jax


class TransformerTrainStep:
    """One compiled train step over a ``TransformerConfig``.

    Parameters
    ----------
    cfg : TransformerConfig (``dtype`` is the compute dtype; params are
        stored in ``param_dtype``).
    mesh : jax Mesh with a ``dp`` axis and optionally an ``sp`` axis
        (sequence parallelism).  Default: one device, dp only.
    attn_impl / remat / zero_stage : explicit overrides for
        ``MXNET_ATTENTION_IMPL`` / ``MXNET_REMAT_POLICY`` /
        ``MXNET_ZERO_STAGE`` (None = read the env knob at build).
    bucket_bytes : pins the gradient bucket cap (bypasses autotune);
        None resolves MXNET_AUTOTUNE_PLAN/_DIR then the env default.
    params : the state to start from, as ``{name: device array}`` over
        ``param_shapes(cfg)`` (the step takes them over: its first
        call donates them); None initialises from ``seed``.  With it
        set-up holds the parameters once, not beside a seeded copy.
    """

    def __init__(self, cfg: TransformerConfig, mesh=None,
                 learning_rate: float = 0.01, momentum: float = 0.9,
                 weight_decay: float = 0.0,
                 attn_impl: Optional[str] = None,
                 remat: Optional[str] = None,
                 zero_stage: Optional[int] = None,
                 bucket_bytes: Optional[int] = None, seed: int = 0,
                 params: Optional[Dict] = None):
        jax = _jax()
        from ..parallel.mesh import make_mesh

        self.cfg = cfg
        self.mesh = mesh if mesh is not None else \
            make_mesh((1,), ("dp",), jax.devices()[:1])
        if "dp" not in self.mesh.axis_names:
            raise ValueError("transformer mesh needs a 'dp' axis "
                             "(got %s)" % (self.mesh.axis_names,))
        self._lr = float(learning_rate)
        self._momentum = float(momentum)
        self._wd = float(weight_decay)
        self._attn_impl = attn_impl
        self._remat = remat
        self._zero_stage = zero_stage
        self._bucket_bytes = bucket_bytes
        self._seed = int(seed)
        self._given_params = params
        # what the expert layers of the newest steps counted, unread
        self._aux_log: List[Dict] = []
        # how the newest traced step's calls of flash_attention, and of
        # hyper_residual, lower
        self._attn_sites: Dict[str, int] = {}
        self._mhc_sites: Dict[str, int] = {"kernel": 0, "plain": 0}
        self._built = False
        self._step_no = 0  # optimizer steps dispatched: mx.step's number

    # -- mesh geometry --------------------------------------------------
    @property
    def n_dp(self) -> int:
        return int(dict(zip(self.mesh.axis_names,
                            self.mesh.devices.shape))["dp"])

    @property
    def n_sp(self) -> int:
        return int(dict(zip(self.mesh.axis_names,
                            self.mesh.devices.shape)).get("sp", 1))

    # -- build ----------------------------------------------------------
    def _build(self):
        jax = _jax()
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .. import diagnostics as _diag
        from ..compile_cache import enable as _cc_enable
        from ..parallel import buckets as _buckets
        from ..parallel.dp import (zero1_bucketed_update,
                                   zero1_momentum_buffers, zero1_stage)

        _cc_enable()
        cfg = self.cfg
        n_dp, n_sp = self.n_dp, self.n_sp
        n_total = int(self.mesh.devices.size)
        sp_axis = "sp" if n_sp > 1 else None
        self._impl = _model.attention_impl(self._attn_impl)
        self._policy = remat_policy(self._remat)
        attn_fn = _model.make_attn_fn(self._impl, sp_axis)
        if sp_axis and self._impl == "ulysses" and cfg.n_heads % n_sp:
            raise ValueError(
                "ulysses attention shards heads over sp: n_heads %d "
                "must divide by sp axis size %d" % (cfg.n_heads, n_sp))

        if sp_axis and (cfg.attn_kind != "mha" or cfg.mtp_layers):
            raise NotImplementedError(
                "latent attention and multi-token modules are not "
                "sequence-sharded; build the step over a dp-only mesh")
        params, self._given_params = self._given_params, None
        if params is None:
            params = _model.init_params(jax.random.PRNGKey(self._seed),
                                        cfg)
        else:
            want = [(n, tuple(sh)) for n, sh, _ in
                    _model.param_shapes(cfg)]
            got = [(k, tuple(v.shape)) for k, v in params.items()]
            if got != want:
                raise ValueError(
                    "params given are not param_shapes(cfg): first "
                    "difference %s" % (next(
                        (a, b) for a, b in zip(got + [None], want + [None])
                        if a != b),))
        rep = NamedSharding(self.mesh, P())
        data_spec = P("dp", "sp") if sp_axis else P("dp")
        data_sh = NamedSharding(self.mesh, data_spec)
        self._rep, self._data_sh = rep, data_sh
        self._params = {k: jax.device_put(v, rep)
                        for k, v in params.items()}
        self._names = list(self._params)
        frozen = _model.frozen_names(cfg)
        # the leaves the optimizer sees; the rest are the routers'
        # selection biases, in expert-layer order
        self._trained = [k for k in self._names if k not in frozen]
        del params

        # gradient bucket plan over the param leaves (layer order) —
        # the autotuner's resolution precedence applies, so a tuned
        # plan for THIS exchange's fingerprint supplies the caps
        entries = [(k, tuple(self._params[k].shape),
                    str(self._params[k].dtype)) for k in self._trained]
        cap = self._bucket_bytes if self._bucket_bytes is not None \
            else _buckets.bucket_cap_bytes()
        if cap == 0:
            # monolithic request: one bucket per dtype run through the
            # same code path (the step still compiles via shard_map)
            plan, tuning = _buckets.partition(entries, 1 << 62), None
        else:
            plan, tuning = _buckets.plan_with_tuning(
                entries, self._bucket_bytes)
        self._bucket_plan, self._bucket_tuning = plan, tuning
        sharded = n_total > 1

        from .. import sdc as _sdc

        stage = zero1_stage(self._zero_stage)
        self._zero1 = bool(stage == 1 and sharded and n_dp > 1)
        # SDC fingerprint vote (mxnet_tpu/sdc.py): per-bucket bit-exact
        # fingerprints of the post-update params computed INSIDE the
        # compiled step under lax.cond on the step counter and
        # all-gathered over dp.  Off (the default) leaves the graph
        # untouched; voting needs >1 dp replica.
        self._sdc_n = _sdc.check_every_n()
        self._sdc = bool(self._sdc_n > 0 and sharded and n_dp > 1)
        if stage == 1 and not self._zero1:
            import logging

            logging.getLogger(__name__).warning(
                "MXNET_ZERO_STAGE=1 needs a multi-device dp axis — "
                "momenta stay replicated")

        plan_meta_v = _buckets.plan_meta(plan, cap if cap else None,
                                         tuning=tuning)
        plan_meta_v["workload"] = "transformer_lm"
        plan_meta_v["zero_stage"] = 1 if self._zero1 else 0
        if sharded:
            _diag.set_bucket_plan(plan_meta_v, owner=id(self))
        self._plan_meta = plan_meta_v

        lr, mom_c, wd = self._lr, self._momentum, self._wd
        zero1 = self._zero1
        policy = self._policy
        reduce_axes = ("dp", "sp") if sp_axis else ("dp",)

        from .. import optimizer as _opt

        trained, bias_rate = self._trained, cfg.router_bias_rate

        def step_body(params_d, moms, tokens, labels):
            t_local = tokens.shape[1]
            pos_offset = lax.axis_index("sp") * t_local if sp_axis \
                else 0

            def pure_loss(p):
                return _model.loss_and_aux(
                    dict(params_d, **p), tokens, labels, cfg,
                    attn_fn=attn_fn, pos_offset=pos_offset, remat=policy)

            (loss, aux), grads = jax.value_and_grad(
                pure_loss, has_aux=True)({k: params_d[k] for k in trained})
            if sharded:
                loss = lax.pmean(loss, reduce_axes)
                # the counts of every replica's tokens; which expert
                # each token chose stays with its own replica
                aux = {k: lax.psum(aux[k], reduce_axes)
                       for k in ("counts", "dropped") if k in aux}
            if zero1:
                # the shard update inside carries the optimizer scope
                new_p, new_m = zero1_bucketed_update(
                    grads, params_d, moms, plan, "dp", n_dp,
                    lr=lr, momentum=mom_c, wd=wd, mean_n=n_total,
                    sp_axis=sp_axis)
            else:
                if sharded:
                    # the replicated exchange: bucketed all-reduce over
                    # every model-replica axis (psum accepts the tuple;
                    # ring/hierarchical impls are dp-only, so force
                    # psum when an sp axis is present)
                    grads = _buckets.bucketed_reduce(
                        grads, plan, reduce_axes if sp_axis else "dp",
                        n=n_total, mean=True,
                        impl="psum" if sp_axis else None)
                # leaf by leaf, each parameter where it lies
                # (optimizer.py; the same helper FusedTrainStep's
                # replicated path runs)
                with jax.named_scope("optimizer"):
                    new_p, new_m = _opt.fused_sgd_mom_grouped(
                        trained, params_d, grads, moms, lr, mom_c, wd)
            if frozen:
                # an expert that got more than its share of this step's
                # assignments is picked a little less readily next step
                with jax.named_scope("mlp"), jax.named_scope("moe_route"):
                    for name, c in zip(frozen, aux["counts"]):
                        c = c.astype(jnp.float32)
                        new_p[name] = params_d[name] + bias_rate * \
                            jnp.sign(jnp.mean(c) - c)
            return new_p, new_m, loss, aux

        sdc_on, sdc_n = self._sdc, self._sdc_n

        def step_body_sdc(params_d, moms, tokens, labels, ctr):
            new_p, new_m, loss, aux = step_body(params_d, moms, tokens,
                                                labels)
            from .. import sdc as _sdcmod

            groups = []
            for bucket in plan:
                leaves = [new_p[k] for k in bucket.keys]
                if not zero1:
                    # replicated momenta must match across dp too;
                    # zero1 shards are legitimately different per rank
                    leaves += [new_m[k] for k in bucket.keys]
                groups.append(leaves)

            def _fps():
                return jnp.stack([_sdcmod.tree_fingerprint(g)
                                  for g in groups])

            # the param-bytes pass is paid ONLY on cadence steps; the
            # always-on all_gather moves n_buckets uint32s — noise
            fp = lax.cond(ctr % sdc_n == 0, _fps,
                          lambda: jnp.zeros((len(plan),), jnp.uint32))
            rows = lax.all_gather(fp, "dp")
            return new_p, new_m, loss, aux, rows

        if sharded:
            from jax import shard_map

            mom_spec = [P("dp")] * len(plan) if zero1 else P()
            step = shard_map(
                step_body, mesh=self.mesh,
                in_specs=(P(), mom_spec, data_spec, data_spec),
                out_specs=(P(), mom_spec, P(), P()),
                check_vma=False)
            if sdc_on:
                step_sdc = shard_map(
                    step_body_sdc, mesh=self.mesh,
                    in_specs=(P(), mom_spec, data_spec, data_spec,
                              P()),
                    out_specs=(P(), mom_spec, P(), P(), P()),
                    check_vma=False)
        else:
            step = step_body

        if zero1:
            self._moms = [jax.device_put(m, NamedSharding(self.mesh,
                                                          P("dp")))
                          for m in zero1_momentum_buffers(plan, n_dp)]
            mom_sh = [NamedSharding(self.mesh, P("dp"))] * len(plan)
        else:
            self._moms = {k: jax.device_put(
                jnp.zeros_like(self._params[k]), rep) for k in trained}
            mom_sh = {k: rep for k in trained}
        self._mom_sh = mom_sh

        step_meta = {"compute_dtype": str(jnp.dtype(cfg.dtype)),
                     "bucket_plan": plan_meta_v}
        # the sdc variant takes the step counter and returns the
        # gathered (n_dp, n_buckets) fingerprint rows; the K-step
        # bench scan below keeps the plain program — per-step cadence
        # needs per-step dispatch
        p_sh = {k: rep for k in self._params}
        # the last output is what the expert layers counted (an empty
        # dict for a configuration without any)
        step_fn, in_sh, out_sh = step, (p_sh, mom_sh, data_sh,
                                        data_sh), (p_sh, mom_sh, rep, rep)
        if sdc_on:
            step_fn, in_sh, out_sh = (step_sdc, in_sh + (rep,),
                                      out_sh + (rep,))
        self._step = _diag.instrument_jit(
            "TransformerTrainStep.step",
            jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=(0, 1)),
            meta=step_meta)

        # K steps of the SAME batch in one program (lax.scan) — the
        # bench/burn-in path, per-dispatch latency amortized like the
        # conv workloads' multi_step_same
        def multi_step_same(k):
            def fn(params_d, moms, tokens, labels):
                def body(carry, _):
                    p, m = carry
                    p2, m2, loss, _ = step(p, m, tokens, labels)
                    return (p2, m2), loss

                (p2, m2), losses = lax.scan(
                    body, (params_d, moms), None, length=k)
                return p2, m2, losses

            return _diag.instrument_jit(
                "TransformerTrainStep.multi_step_same[k=%d]" % k,
                jax.jit(fn,
                        in_shardings=({k2: rep for k2 in self._params},
                                      mom_sh, data_sh, data_sh),
                        out_shardings=({k2: rep for k2 in self._params},
                                       mom_sh, rep),
                        donate_argnums=(0, 1)),
                meta=step_meta)

        self._multi_same: Dict[int, object] = {}
        self._multi_same_fn = multi_step_same
        self._sharded = sharded
        self._sdc_ctr = 0
        self._last_sdc_rows = None
        self._built = True

    # -- introspection --------------------------------------------------
    @property
    def zero1(self) -> bool:
        return self._built and self._zero1

    @property
    def attention_impl(self) -> str:
        if not self._built:
            self._build()
        return self._impl

    def bucket_plan_meta(self):
        if not self._built:
            self._build()
        return self._plan_meta

    def bucket_tuning(self):
        if not self._built:
            self._build()
        return self._bucket_tuning

    def optimizer_state_bytes_per_rank(self) -> Optional[int]:
        """Momenta bytes resident on ONE device, measured from the
        live buffers (the ZeRO-1 acceptance evidence; the same helper
        FusedTrainStep reports through)."""
        if not self._built:
            return None
        from ..parallel.dp import momenta_bytes_per_device

        return momenta_bytes_per_device(self._moms)

    def params_numpy(self) -> Dict:
        """Host copies of the (replicated) parameters."""
        import numpy as np

        if not self._built:
            self._build()
        return {k: np.asarray(v) for k, v in self._params.items()}

    # -- stepping -------------------------------------------------------
    def _put_batch(self, tokens, labels):
        jax = _jax()
        import numpy as np

        from ..ndarray import NDArray

        def raw(x):
            if isinstance(x, NDArray):
                return x._data
            return np.asarray(x)

        return (jax.device_put(raw(tokens), self._data_sh),
                jax.device_put(raw(labels), self._data_sh))

    def _bitflip_param(self, rule) -> None:
        """Chaos 'bitflip_param' for the functional tier: flip one bit
        in a (replicated) parameter — uniform across replicas, so the
        in-graph vote cannot see it; the offline replay audit
        (``python -m mxnet_tpu.sdc --replay``) is what must catch it."""
        import numpy as np

        from .. import chaos as _chaos

        jax = _jax()
        host = {k: np.asarray(v) for k, v in self._params.items()}
        name = _chaos.apply_bitflip(rule, host)
        if name is not None:
            self._params[name] = jax.device_put(host[name], self._rep)
            import logging

            logging.getLogger(__name__).warning(
                "chaos: bitflip_param flipped bit %s of %r",
                rule.params.get("bit", 12), name)

    def _replay_spec(self, train_iter) -> dict:
        """Everything ``sdc.replay_audit`` needs to re-execute this
        run's steps offline: config dims, hyperparameters (with the
        RESOLVED attention/remat choices, not the env defaults they
        came from) and the data source's reconstruction spec."""
        spec_fn = getattr(train_iter, "replay_spec", None)
        return {
            "cfg": dict(self.cfg._asdict()),
            "hyper": {
                "learning_rate": self._lr,
                "momentum": self._momentum,
                "weight_decay": self._wd,
                "seed": self._seed,
                "attn_impl": self._impl,
                "remat": self._policy,
                "bucket_bytes": self._bucket_bytes,
            },
            "data": spec_fn() if spec_fn is not None
            else {"kind": "unknown"},
        }

    @staticmethod
    def _site_tallies():
        from ..parallel import attention as _attention
        from . import blocks as _blocks

        return _attention.site_tally(), _blocks.site_tally()

    def _note_sites(self, before) -> None:
        """After a call of a compiled step: if the call traced it, keep
        how many of its calls of ``flash_attention`` run as the tiled
        kernels and how many as the scan ON THIS MESH, and how many of
        its calls of ``hyper_residual`` run as the fused passes and how
        many as the plain formulation (a site whose shapes the kernels
        take still lowers to the other for anything but a TPU)."""
        from ..parallel import attention as _attention
        from . import blocks as _blocks

        attn = _attention.site_tally(since=before[0])
        mhc = _blocks.site_tally(since=before[1])
        if not any(attn.values()) and not any(mhc.values()):
            return
        if self.mesh.devices.flat[0].platform != "tpu":
            attn = {"kernel": 0, "scan": attn["kernel"] + attn["scan"]}
            mhc = {"kernel": 0, "plain": mhc["kernel"] + mhc["plain"]}
        self._attn_sites, self._mhc_sites = attn, mhc

    def _stamp_telemetry(self):
        from .. import profiler as _profiler

        for how, n in self._attn_sites.items():
            _profiler.record_counter("attn.%s_sites" % how, n)
        for how, n in self._mhc_sites.items():
            _profiler.record_counter("mhc.%s_sites" % how, n)
        if self._sharded:
            from ..parallel import buckets as _buckets

            _buckets.stamp_profiler(self._bucket_plan,
                                    store_type="transformer")

    def step(self, tokens, labels):
        """One optimizer step; returns the (scalar) loss as a jax
        array — not blocked on, so steps pipeline."""
        from .. import profiler as _profiler
        from .. import traceview as _traceview

        self._step_no += 1
        with _profiler.span("mx.step", cat="dispatch",
                            step=self._step_no):
            if not self._built:
                self._build()
            with _profiler.span("mx.step.feed", cat="dispatch"):
                tokens, labels = self._put_batch(tokens, labels)
            args = (self._params, self._moms, tokens, labels)
            if self._sdc:
                self._sdc_ctr += 1
                args += (self._sdc_ctr,)
            sites = self._site_tallies()
            with _traceview.step_window("TransformerTrainStep") as _tvw:
                out = self._step(*args)
                if _tvw is not None:
                    _tvw.block(out[2])
            self._note_sites(sites)
            if self._sdc:
                (self._params, self._moms, loss, aux,
                 self._last_sdc_rows) = out
            else:
                self._params, self._moms, loss, aux = out
            if aux:
                self._aux_log.append(aux)
                del self._aux_log[:-4096]
            self._stamp_telemetry()
        return loss

    def routing_counters(self) -> Optional[Dict]:
        """What the expert layers counted over the steps since the last
        call (one read of the small arrays each step returned beside its
        loss: call it outside a timed window), also stamped as profiler
        counters ``moe.*``; None where no step with expert layers ran.

        ``assignments_total`` and ``assignments_here`` (to every expert
        and to those held here), ``dropped`` (assignments to a held
        expert that were not computed: 0), ``load_max_over_mean`` (the
        busiest held expert's assignments over the mean held expert's,
        the mean over those steps and layers), ``steps``, and ``choice`` and
        ``counts`` of the FIRST of those steps ((L, N, k) and
        (L, n_experts)), for a comparison with a reference."""
        import numpy as np

        from .. import profiler as _profiler

        log, self._aux_log = _jax().device_get(self._aux_log), []
        if not log:
            return None
        counts = np.stack([a["counts"] for a in log])
        held = counts[..., list(self.cfg.held_experts)]
        out = {
            "steps": len(log),
            "assignments_total": int(counts.sum()),
            "assignments_here": int(held.sum()),
            "dropped": int(sum(a["dropped"].sum() for a in log)),
            "load_max_over_mean": float(np.mean(
                held.max(axis=-1) / np.maximum(held.mean(axis=-1), 1e-30))),
        }
        for k, v in out.items():
            if k != "steps":
                _profiler.record_counter("moe." + k, v)
        return dict(out, counts=log[0]["counts"],
                    choice=log[0].get("choice"))

    def sdc_rows(self, step: Optional[int] = None):
        """The newest gathered fingerprint matrix ((n_dp, n_buckets)
        uint32 — one row per dp replica), meaningful only on cadence
        steps; None when the detector is off."""
        if not self._sdc or self._last_sdc_rows is None:
            return None
        if step is not None and step % self._sdc_n != 0:
            return None
        return self._last_sdc_rows

    def run_steps(self, tokens, labels, steps: int):
        """K same-batch steps as ONE compiled program; returns the
        per-step losses (K,)."""
        from .. import profiler as _profiler
        from .. import traceview as _traceview

        k = int(steps)
        with _profiler.span("mx.step", cat="dispatch",
                            step=self._step_no + 1):
            if not self._built:
                self._build()
            with _profiler.span("mx.step.feed", cat="dispatch"):
                tokens, labels = self._put_batch(tokens, labels)
            runner = self._multi_same.get(k)
            if runner is None:
                runner = self._multi_same_fn(k)
                self._multi_same[k] = runner
            sites = self._site_tallies()
            with _traceview.step_window("TransformerTrainStep",
                                        k=k) as _tvw:
                self._params, self._moms, losses = runner(
                    self._params, self._moms, tokens, labels)
                if _tvw is not None:
                    _tvw.block(losses)
            self._note_sites(sites)
            for _ in range(k):
                self._stamp_telemetry()
        self._step_no += k
        return losses

    # -- checkpoint state ----------------------------------------------
    def optimizer_states_bytes(self) -> bytes:
        """The momenta as a pickled host blob for the checkpoint
        shard's ``optimizer_states`` slot — sharded (ZeRO-1) momenta
        ride the SAME elastic manifest as everything else."""
        import numpy as np

        if not self._built:
            self._build()
        from ..parallel.dp import zero1_bucket_elems

        if self._zero1:
            moms = [np.asarray(m) for m in self._moms]
        else:
            moms = {k: np.asarray(v) for k, v in self._moms.items()}
        return pickle.dumps({
            "workload": "transformer_lm",
            "zero_stage": 1 if self._zero1 else 0,
            "dp": self.n_dp,
            "n_buckets": len(self._bucket_plan),
            # the restage invariant: padding depends on dp, these don't
            "bucket_elems": zero1_bucket_elems(self._bucket_plan),
            "momenta": moms,
        })

    def load_state(self, payload: dict) -> None:
        """Restore params + momenta from a checkpoint payload
        (``checkpoint.load_checkpoint``'s dict).  Host arrays are
        copied to the device; a ``jax.Array`` already there is taken
        over as it is (no second copy; the next step donates it)."""
        jax = _jax()
        import numpy as np

        def place(x):
            if not isinstance(x, jax.Array):
                x = np.asarray(x)
            return jax.device_put(x, self._rep)

        if not self._built:
            self._build()
        params = payload.get("params") or {}
        missing = [k for k in self._names if k not in params]
        if missing:
            raise KeyError("checkpoint payload is missing transformer "
                           "params: %s" % missing[:4])
        self._params = None   # the seeded copy goes before the new one
        self._params = {k: place(params[k]) for k in self._names}
        blob = payload.get("optimizer_states")
        if not blob:
            return
        state = pickle.loads(blob) if isinstance(blob, bytes) else blob
        self._restore_momenta(state)

    def _restore_momenta(self, state: dict) -> None:
        """Momenta from a checkpoint state blob, ELASTICALLY: a
        stage-1 checkpoint written at one dp resumes at any other —
        the per-bucket flat buffers are re-sliced by the (identical)
        bucket layout and re-padded for the new dp; 2→1 lands as the
        replicated per-param dict, 1→2 packs the dict back into
        sharded flats.  Same stage + same dp stays the bitwise
        exact-resume path (the restage transform is the identity
        there).  A bucket-LAYOUT mismatch (caps changed between runs)
        still rejects loudly — restage re-slices, it cannot re-bucket."""
        jax = _jax()
        import logging

        import numpy as np

        from ..parallel.dp import (zero1_bucket_elems,
                                   zero1_flats_to_tree,
                                   zero1_restage_flats,
                                   zero1_tree_to_flats)

        saved_stage = int(state.get("zero_stage", 0))
        saved_dp = state.get("dp")
        cur_stage = 1 if self._zero1 else 0
        moms = state["momenta"]
        plan = self._bucket_plan

        if saved_stage == 1:
            if len(moms) != len(plan):
                raise ValueError(
                    "checkpoint has %d momentum buckets, this plan has "
                    "%d — bucket caps changed between runs; pin "
                    "bucket_bytes (or the same autotune plan) to "
                    "resume" % (len(moms), len(plan)))
            saved_elems = state.get("bucket_elems")
            if saved_elems is not None and \
                    list(saved_elems) != zero1_bucket_elems(plan):
                raise ValueError(
                    "checkpoint bucket layout %s != this plan's %s — "
                    "elastic restage re-slices identical bucket plans "
                    "only; pin bucket_bytes (or the same autotune "
                    "plan) to resume"
                    % (list(saved_elems), zero1_bucket_elems(plan)))
        restaged = saved_stage != cur_stage or \
            (saved_dp is not None and int(saved_dp) != self.n_dp)
        if saved_stage == 1 and self._zero1:
            # flats → flats: trim to the layout's true element counts,
            # re-pad for THIS dp (identity when the dp is unchanged —
            # the same-world bitwise contract rides this line)
            flats = zero1_restage_flats([np.asarray(m) for m in moms],
                                        plan, self.n_dp)
            self._moms = [jax.device_put(m, sh)
                          for m, sh in zip(flats, self._mom_sh)]
        elif saved_stage == 0 and not self._zero1:
            missing = [k for k in self._trained if k not in moms]
            if missing:
                raise KeyError("checkpoint momenta missing params: %s"
                               % missing[:4])
            self._moms = {k: jax.device_put(np.asarray(moms[k]),
                                            self._rep)
                          for k in self._trained}
        elif saved_stage == 1:
            # sharded → replicated (e.g. dp=2 stage-1 resuming at
            # dp=1, where stage 1 degenerates to replicated)
            shapes = {k: tuple(self._params[k].shape)
                      for k in self._trained}
            trimmed = zero1_restage_flats([np.asarray(m) for m in moms],
                                          plan, 1)
            tree = zero1_flats_to_tree(trimmed, plan, shapes)
            self._moms = {k: jax.device_put(np.asarray(tree[k]),
                                            self._rep)
                          for k in self._trained}
        else:
            # replicated → sharded (dp=1 checkpoint resuming at dp>1
            # with MXNET_ZERO_STAGE=1)
            tree = {k: np.asarray(v) for k, v in moms.items()}
            flats = zero1_tree_to_flats(tree, plan, self.n_dp)
            self._moms = [jax.device_put(m, sh)
                          for m, sh in zip(flats, self._mom_sh)]
        if restaged:
            logging.getLogger(__name__).warning(
                "ZERO-1 ELASTIC RESTAGE: momenta written at stage %d "
                "(dp=%s) re-sliced for stage %d (dp=%d) over the same "
                "%d-bucket layout — per-rank optimizer state is now "
                "~1/%d of replicated",
                saved_stage, saved_dp, cur_stage, self.n_dp,
                len(plan), max(self.n_dp if self._zero1 else 1, 1))

    # -- fit loop -------------------------------------------------------
    def fit(self, train_iter, num_steps: int,
            checkpoint_every_n: Optional[int] = None,
            checkpoint_dir: Optional[str] = None,
            resume_from: Optional[str] = None,
            log_every: int = 0) -> List[float]:
        """Train ``num_steps`` batches from ``train_iter`` (any io.py
        DataIter yielding (tokens, next_tokens) int batches; wraps
        around epoch ends).  Rides the robustness stack: elastic
        checkpoints every N steps, exact resume (same world + bucket
        plan -> bitwise), chaos kill/delay at the loop points the
        harness expects.  Returns the per-step losses (floats)."""
        from .. import chaos as _chaos
        from .. import checkpoint as _ckpt
        from .. import diagnostics as _diag

        if not self._built:
            self._build()
        every = checkpoint_every_n if checkpoint_every_n is not None \
            else _env.get_int("MXNET_CKPT_EVERY_N")
        ckpt_dir = checkpoint_dir or _env.get_str("MXNET_CKPT_DIR")
        mgr = None
        if every and ckpt_dir:
            mgr = _ckpt.CheckpointManager(ckpt_dir)
        start = 0
        if resume_from:
            payload = _ckpt.load_checkpoint(resume_from)
            self.load_state(payload)
            start = int(payload["step"])
            train_iter.reset()
            skip = int((payload.get("iterator") or {})
                       .get("nbatch", start))
            if payload.get("elastic"):
                # W→W' elastic resume: the checkpointed per-rank batch
                # count is in the OLD fleet's units — the invariant is
                # the GLOBAL sample position, re-divided by THIS
                # fleet's per-rank batch x world size
                # (checkpoint.scale_resume_skip; without this, a
                # mid-epoch shard resumed at a different W replays or
                # skips the partial epoch's data)
                skip = _ckpt.scale_resume_skip(
                    payload, getattr(train_iter, "batch_size", None))
            if hasattr(train_iter, "skip_batches"):
                train_iter.skip_batches(skip)
            else:
                for _ in range(skip):
                    if not train_iter.iter_next():
                        train_iter.reset()
                        train_iter.iter_next()
        from .. import sdc as _sdc

        chaos_on = _chaos.enabled()
        guard = _diag.DivergenceGuard()
        sdc_guard = _sdc.SDCGuard() if self._sdc else None
        tps = _diag.metrics.gauge(
            "mxnet_transformer_tokens_per_second",
            "transformer fit throughput (tokens/s, this rank)")
        losses: List[float] = []
        loss_dev = None
        t_last = time.monotonic()
        for step_i in range(start, int(num_steps)):
            batch = self._next_batch(train_iter)
            tokens, labels = batch.data[0], batch.label[0]
            if chaos_on:
                _chaos.maybe_delay("transformer_step", step=step_i)
            loss_dev = self.step(tokens, labels)
            if chaos_on:
                # mid-run preemption that didn't say goodbye — the
                # kill/resume harness's injection point
                _chaos.should_kill(step_i + 1)
                rule = _chaos.should_bitflip_param(step_i + 1)
                if rule is not None:
                    self._bitflip_param(rule)
            # block before sampling the clock: an async dispatch
            # interval is host cost, not step time — same truthful-
            # metric stance as the bulk fit path's step timing
            _jax().block_until_ready(loss_dev)  # mxlint: disable=MXL004
            if guard.enabled and guard.check(float(loss_dev),
                                             step=step_i + 1):
                # loss spiked past the windowed threshold: under the
                # supervisor this exits EXIT_DIVERGED (restore from
                # the last VERIFIED checkpoint, automatically);
                # standalone it raises instead of training through
                # garbage
                guard.trip(step_i + 1)
            if sdc_guard is not None:
                rows = self.sdc_rows(self._sdc_ctr)
                if rows is not None:
                    # one tiny host read per cadence step; a corrupt
                    # device trips dump + exit 87 (supervised) inside
                    sdc_guard.check_rows(rows, step=step_i + 1)
            _diag.touch_heartbeat()
            now = time.monotonic()
            n_tok = int(tokens.shape[0]) * int(tokens.shape[1])
            if now > t_last:
                tps.set(n_tok / (now - t_last))
            t_last = now
            losses.append(loss_dev)
            if log_every and (step_i + 1) % log_every == 0:
                import logging

                logging.getLogger(__name__).info(
                    "transformer step %d loss %.5f", step_i + 1,
                    float(losses[-1]))
            if mgr is not None and (step_i + 1) % every == 0:
                # the per-step block above guarantees the snapshot
                # sees THIS step's params; hand the write to the
                # manager
                mgr.save(step_i + 1, params=self._params,
                         optimizer_states=self.optimizer_states_bytes(),
                         nbatch=step_i + 1,
                         iterator_state={
                             "nbatch": step_i + 1,
                             "cursor": getattr(train_iter, "cursor",
                                               None),
                             # recorded so a W→W' elastic resume can
                             # re-derive the global sample position
                             "batch_size": getattr(train_iter,
                                                   "batch_size", None)},
                         extra={"workload": "transformer_lm",
                                # sdc.replay_audit's reconstruction
                                # spec: the offline corruption bisector
                                # re-executes from exactly this state
                                "replay": self._replay_spec(
                                    train_iter)})
        if mgr is not None:
            mgr.wait()
        return [float(v) for v in losses]

    @staticmethod
    def _next_batch(train_iter):
        try:
            return train_iter.next()
        except StopIteration:
            train_iter.reset()
            return train_iter.next()
