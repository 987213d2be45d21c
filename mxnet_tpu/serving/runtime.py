"""Model runtime: checkpoint -> AOT-compiled bf16 inference executors,
one per bucketed batch shape.

The amalgamation (``mxnet_predict_lite.cc`` + the c_predict ABI)
proved python-free inference of ONE shape; a server sees every batch
size between 1 and ``MXNET_SERVE_MAX_BATCH``.  Compiling per arriving
shape would be the recompilation storm diagnostics.py warns about —
so, reusing the size-capped bucket-planning idiom from
``parallel/buckets.py`` (a deterministic plan computed once, every
payload landing in exactly one bucket), the runtime compiles a
doubling ladder of batch buckets ahead of time (AOT ``lower().
compile()``, not first-request JIT), pads each dynamic batch to the
nearest bucket, and runs a warmup pass at load so the FIRST request
never pays compile latency.  Weights are cast to the compute dtype
(bf16 by default — the TPU-native inference dtype) once at load.

``from_checkpoint`` loads elastic checkpoints (``mx.checkpoint``); an
incomplete step fails with the exact ranks whose shards are missing,
because "the model won't load" must explain itself at server startup.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from .bucket_ladder import ladder as _ladder
from .errors import ExecutorFailure

__all__ = ["plan_batch_buckets", "ModelRuntime", "demo_runtime",
           "demo_params"]

_log = logging.getLogger(__name__)


def plan_batch_buckets(max_batch: int,
                       batch_sizes: Optional[Sequence[int]] = None
                       ) -> Tuple[int, ...]:
    """The compiled-batch ladder: explicit ``batch_sizes`` (sorted,
    deduped, capped) or a doubling ladder 1,2,4,...,max_batch.  Same
    planning contract as ``parallel/buckets.partition``: deterministic,
    size-capped, and every request batch maps to exactly one bucket
    (the smallest holding it) — at most 2x padding waste, log2(max)
    compiled programs.  Delegates to the shared
    :mod:`~mxnet_tpu.serving.bucket_ladder` helper (min_size=1), whose
    1-D plan is bit-for-bit this function's historical output — the
    fixed-shape predictors' ladders are pinned."""
    return _ladder(max_batch, batch_sizes, min_size=1)


class ModelRuntime:
    """One served model: params + a pure ``apply_fn(params, aux, data)``
    compiled AOT for every batch bucket."""

    def __init__(self, name: str, apply_fn: Callable, params: Dict,
                 aux_params: Optional[Dict] = None, *,
                 sample_shape: Sequence[int],
                 input_dtype: str = "float32",
                 compute_dtype: Optional[str] = "bfloat16",
                 max_batch: Optional[int] = None,
                 batch_sizes: Optional[Sequence[int]] = None,
                 source: str = "inline"):
        from .. import env as _env

        self.name = str(name)
        self.source = source
        #: version number within a ModelServer (assigned by add_model/
        #: reload; labels serving metrics and the canary decision)
        self.version = 1
        self.sample_shape = tuple(int(d) for d in sample_shape)
        self.compute_dtype = compute_dtype
        self.max_batch = int(max_batch) if max_batch is not None \
            else _env.get_int("MXNET_SERVE_MAX_BATCH")
        self.plan = plan_batch_buckets(self.max_batch, batch_sizes)
        self._apply = apply_fn
        self._input_dtype_arg = input_dtype
        self._input_dtype = self._resolve_dtype(input_dtype)
        self._params = self._cast_tree(params or {})
        self._aux = self._cast_tree(aux_params or {})
        self._executables: Dict[int, Any] = {}
        self._compile_ms: Dict[int, float] = {}
        self._lock = threading.Lock()

    # -- dtype/casting -------------------------------------------------
    def _resolve_dtype(self, dtype):
        import numpy as np

        if self.compute_dtype and "float" in str(dtype):
            import jax.numpy as jnp

            return jnp.dtype(self.compute_dtype)
        return np.dtype(dtype)

    def _cast_tree(self, tree):
        """Host params -> device arrays, floats cast to the compute
        dtype ONCE at load (not per request)."""
        import jax
        import jax.numpy as jnp

        def put(v):
            arr = jnp.asarray(v)
            if self.compute_dtype and jnp.issubdtype(arr.dtype,
                                                     jnp.floating):
                arr = arr.astype(self.compute_dtype)
            return arr

        return jax.tree_util.tree_map(put, tree)

    # -- compilation ---------------------------------------------------
    @property
    def compiled(self) -> bool:
        return len(self._executables) == len(self.plan)

    def bucket_for(self, n: int) -> int:
        """Smallest compiled bucket holding ``n`` samples."""
        for b in self.plan:
            if n <= b:
                return b
        raise ValueError("%d samples > max batch %d for model %r"
                         % (n, self.plan[-1], self.name))

    def compile(self, warmup: bool = True) -> Dict[int, float]:
        """AOT-compile one executor per batch bucket and (default) run
        a warmup batch through each so the first real request pays
        neither compile nor first-dispatch cost.  Idempotent; returns
        {bucket: compile_ms}."""
        import jax
        import numpy as np

        from .. import diagnostics as _diag
        from ..compile_cache import enable as _cc_enable

        # a restarted server loads its AOT executors from the
        # persistent compilation cache instead of re-binding
        # every (model, bucket) program
        _cc_enable()

        jfn = jax.jit(self._apply)
        for b in self.plan:
            with self._lock:
                if b in self._executables:
                    continue
            spec = jax.ShapeDtypeStruct((b,) + self.sample_shape,
                                        self._input_dtype)
            t0 = time.perf_counter()
            exe = jfn.lower(self._params, self._aux, spec).compile()
            dur_ms = (time.perf_counter() - t0) * 1e3
            if warmup:
                zeros = np.zeros((b,) + self.sample_shape,
                                 dtype="float32")
                out = exe(self._params, self._aux,
                          self._to_device(zeros, b))
                # block: the warmup must actually execute, or the first
                # request still pays the first-dispatch allocation cost
                jax.block_until_ready(out)  # mxlint: disable=MXL004
            with self._lock:
                self._executables[b] = exe
                self._compile_ms[b] = dur_ms
            try:
                _diag.metrics.counter(
                    "mxnet_serve_compiles_total",
                    help="AOT-compiled serving executors",
                    labels={"model": self.name}).inc()
                _diag.metrics.gauge(
                    "mxnet_serve_compile_ms_last",
                    labels={"model": self.name}).set(dur_ms)
            except Exception:
                pass
            _log.info("serving: compiled %s bucket=%d in %.0f ms "
                      "(warmup=%s)", self.name, b, dur_ms, warmup)
        return dict(self._compile_ms)

    def compile_stats(self) -> Dict[int, float]:
        with self._lock:
            return dict(self._compile_ms)

    # -- execution -----------------------------------------------------
    def _to_device(self, batch, bucket: int):
        import jax.numpy as jnp
        import numpy as np

        arr = np.asarray(batch)
        n = arr.shape[0]
        if arr.shape[1:] != self.sample_shape:
            raise ValueError(
                "model %r expects sample shape %s, got %s"
                % (self.name, self.sample_shape, arr.shape[1:]))
        if n < bucket:  # pad to the compiled bucket
            pad = np.zeros((bucket - n,) + self.sample_shape,
                           dtype=arr.dtype)
            arr = np.concatenate([arr, pad], axis=0)
        return jnp.asarray(arr, dtype=self._input_dtype)

    def execute(self, batch):
        """Run one dynamic batch (shape ``(n, *sample_shape)``): pad to
        the nearest compiled bucket, execute, slice the padding back
        off.  Raises :class:`ExecutorFailure` on any executor error (or
        a chaos ``fail_execute`` injection) — the breaker's food."""
        import jax
        import numpy as np

        from .. import chaos as _chaos

        n = int(np.asarray(batch).shape[0])
        bucket = self.bucket_for(n)
        with self._lock:
            exe = self._executables.get(bucket)
        if exe is None:
            # compile() not called (or raced): do it now, once
            self.compile(warmup=False)
            with self._lock:
                exe = self._executables[bucket]
        if _chaos.enabled() and _chaos.should_fail_execute(self.name):
            err = ExecutorFailure(
                "chaos fail_execute injected for model %r" % self.name)
            # the request recorder tags the failure span injected=true
            # so a chaos drill never reads as an organic executor fault
            err.injected = True
            raise err
        try:
            out = exe(self._params, self._aux,
                      self._to_device(batch, bucket))
        except ValueError:
            raise  # bad input shape — the caller's fault, not the chip's
        except Exception as e:
            raise ExecutorFailure(
                "executor for %r (bucket %d) failed: %r"
                % (self.name, bucket, e)) from e
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a)[:n], out)

    # -- constructors --------------------------------------------------
    @classmethod
    def from_checkpoint(cls, name: str, directory: str,
                        apply_fn: Callable, *,
                        sample_shape: Sequence[int],
                        step: Optional[int] = None,
                        num_ranks: int = 1, rank: int = 0,
                        **kw) -> "ModelRuntime":
        """Load params/aux from an elastic checkpoint directory
        (``mx.checkpoint`` layout).  An incomplete step surfaces the
        exact missing ranks — server startup must explain WHY a model
        won't load, not just that a file was absent."""
        from .. import checkpoint as _ckpt

        payload = _ckpt.load_checkpoint(directory, step=step, rank=rank,
                                        num_ranks=num_ranks)
        params = payload.get("params") or {}
        if not params:
            raise ValueError(
                "checkpoint step %s under %r holds no params — nothing "
                "to serve" % (payload.get("step"), directory))
        return cls(name, apply_fn, params,
                   aux_params=payload.get("aux_params"),
                   sample_shape=sample_shape,
                   source="checkpoint:%s@step%s"
                   % (directory, payload.get("step")), **kw)

    def successor_from_checkpoint(self, directory: str,
                                  step: Optional[int] = None
                                  ) -> "ModelRuntime":
        """A NEW version of this model from a (verified) checkpoint:
        same apply_fn, sample shape, dtypes, and bucket ladder — only
        the weights change.  What :meth:`ModelServer.reload` builds and
        canaries; the shared configuration is what makes the hot swap
        shape-safe."""
        return type(self).from_checkpoint(
            self.name, directory, self._apply,
            sample_shape=self.sample_shape, step=step,
            input_dtype=self._input_dtype_arg,
            compute_dtype=self.compute_dtype,
            max_batch=self.max_batch, batch_sizes=self.plan)


def demo_params(dim: int = 16, hidden: int = 32, classes: int = 4,
                seed: int = 0) -> Dict[str, Any]:
    """The demo MLP's fixed-seed host params — exposed so tests/bench
    can checkpoint them (``mx.checkpoint.save_checkpoint``) and drive
    the reload-from-checkpoint path with a distinguishable version."""
    import numpy as np

    rng = np.random.RandomState(seed)
    return {
        "w1": rng.randn(dim, hidden).astype("float32") * 0.1,
        "b1": np.zeros(hidden, dtype="float32"),
        "w2": rng.randn(hidden, classes).astype("float32") * 0.1,
        "b2": np.zeros(classes, dtype="float32"),
    }


def demo_runtime(name: str = "demo", dim: int = 16, hidden: int = 32,
                 classes: int = 4, seed: int = 0,
                 **kw) -> ModelRuntime:
    """A tiny fixed-seed MLP — the self-test / load-generator / bench
    model (real enough to compile, pad, and cast like production)."""
    params = demo_params(dim, hidden, classes, seed)

    def apply_fn(p, aux, x):
        import jax.numpy as jnp

        h = jnp.tanh(x @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        return jnp.argmax(logits, axis=-1), logits

    return ModelRuntime(name, apply_fn, params, sample_shape=(dim,),
                        **kw)
