"""Device contexts.

MXNet's ``Context`` (ref: include/mxnet/base.h:129-135, python/mxnet/context.py)
names a device as ``(device_type, device_id)`` and every NDArray / executor is
pinned to one.  The TPU rebuild maps contexts onto JAX devices.  A machine
with a chip has BOTH backends (``jax.devices()`` is the chips,
``jax.devices("cpu")`` the host), so the mapping says which one is meant:

  * ``mx.cpu(i)``   → the host, always: ``jax.devices("cpu")``.  Reference
                      ``cpu(i)`` contexts are logical views of one host
                      pool, so ``i`` wraps over the host devices that exist.
  * ``mx.tpu(i)``   → chip ``i`` of the accelerator backend
                      (``jax.devices()[i]``); an id that is not there raises.
  * ``mx.gpu(i)``   → alias of ``tpu(i)`` so reference scripts written for
                      ``mx.gpu()`` run unmodified (BASELINE.json north star:
                      "scripts run unmodified with ctx=mx.tpu()").

When the default backend IS the host (``JAX_PLATFORMS=cpu`` — the test
suite and the reference example scripts), ``tpu(i)``/``gpu(i)`` stand in
on host device ``i`` (wrapping), so multi-context scripts run on the
virtual CPU mesh.  ``chip_smoke.py`` refuses to start on such a backend,
so a chip run can never take that branch.

The default context is ``cpu(0)`` as in the reference, but it is a TAG, and
where untagged work lands is jax's decision.  Observed on a v5e host
(``chip_smoke.py``, PR 21):

  * built from host data — ``nd.array(numpy)``, iterator batches — or with
    an explicit ``ctx`` (which is how ``net.initialize()`` makes gluon
    parameters): COMMITTED to that context's device, i.e. the host;
  * a creation op that names no context (``nd.zeros(shape)``,
    ``nd.random.uniform(...)``), anything computed only from such arrays,
    and the buffers ``mx.mod.Module(sym)`` binds without a ``context``:
    left UNCOMMITTED, and jax runs uncommitted work on its default device —
    the chip — whatever the ``cpu(0)`` tag says;
  * a mix follows its committed operand.

Code that must run on the chip says so (``ctx=mx.tpu(0)``,
``Module(sym, context=mx.tpu(0))``); the compiled paths (``FusedTrainStep``,
``TransformerTrainStep``, ``GenerationRuntime``) place what they are given on
their own mesh, and ``Module`` copies each batch onto its context.

Unlike the reference there is no per-context worker thread pool
(src/engine/threaded_engine_perdevice.cc:45): ordering + overlap come from
XLA's async dispatch, so a Context is purely a placement tag.
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, List, Optional

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "host_only_children"]

_DEVICE_TYPES = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
_ID_TO_TYPE = {v: k for k, v in _DEVICE_TYPES.items()}


def _jax():
    import jax

    return jax


class Context:
    """A device placement tag (ref: python/mxnet/context.py Context)."""

    _default_ctx = threading.local()
    devtype2str = _ID_TO_TYPE
    devstr2type = _DEVICE_TYPES

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in _DEVICE_TYPES:
            raise ValueError("unknown device type %r" % (device_type,))
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ----------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return _DEVICE_TYPES[self.device_type]

    def __eq__(self, other: Any) -> bool:
        return (
            isinstance(other, Context)
            and self._canonical_type() == other._canonical_type()
            and self.device_id == other.device_id
        )

    def _canonical_type(self) -> str:
        # gpu is an alias for tpu in this build (scripts-run-unmodified goal)
        return "tpu" if self.device_type == "gpu" else self.device_type

    def __hash__(self) -> int:
        return hash((self._canonical_type(), self.device_id))

    def __repr__(self) -> str:
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self) -> str:
        return repr(self)

    # -- jax mapping -------------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device (see the module docstring)."""
        jax = _jax()
        if self._canonical_type() != "tpu":
            devs = jax.devices("cpu")
            return devs[self.device_id % len(devs)]
        devs = jax.devices()
        if devs[0].platform == "cpu":
            # host-only backend: the accelerator contexts stand in on
            # the (virtual) host devices
            return devs[self.device_id % len(devs)]
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                "%r: the %s backend has %d device(s)"
                % (self, devs[0].platform, len(devs)))
        return devs[self.device_id]

    # -- scope protocol: ``with mx.tpu(0):`` -------------------------------
    def __enter__(self) -> "Context":
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        Context._default_ctx.stack.pop()

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return _DEFAULT


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias of :func:`tpu` — lets reference scripts run unmodified."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


_DEFAULT = Context("cpu", 0)


def current_context() -> Context:
    return Context.default_ctx()


def num_gpus() -> int:
    return num_tpus()


@contextlib.contextmanager
def host_only_children():
    """Start worker processes inside this block: they inherit
    ``JAX_PLATFORMS=cpu``.  A chip belongs to one process, so a data
    worker must never open it.  jax reads the variable when it is
    imported, and a spawned child imports it while unpickling its
    target — before any line of the worker body runs — so the pin has to
    be in the environment the child is born with."""
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


def num_tpus() -> int:
    """Accelerator devices of the default backend (0 on a host-only one)."""
    return len([d for d in _jax().devices() if d.platform != "cpu"])
