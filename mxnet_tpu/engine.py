"""Engine control API compat (ref: python/mxnet/engine.py set_bulk_size:26,
bulk context manager).

The reference's dependency engine batches small ops into bulk segments
(MXNET_EXEC_BULK_EXEC_*, threaded_engine.h:386-458). Under XLA every
jitted program is already one fused "bulk segment", so per-op bulking
is the compiler's job; these knobs are accepted and recorded so
reference tuning code runs unmodified.  The step-level translation of
bulk execution lives in ``FusedTrainStep.run_steps`` (parallel/dp.py):
K optimizer steps inside one XLA program via ``lax.scan``, amortizing
per-dispatch latency the way the reference amortizes per-op engine
pushes; ``current_bulk_size()`` exposes the recorded setting for such
bulk-capable runners.
"""
from __future__ import annotations

from contextlib import contextmanager

from . import env as _env

__all__ = ["set_bulk_size", "bulk"]

_bulk_size = 15  # the reference default
# Step-level bulking in Module.fit only activates on an EXPLICIT opt-in
# (set_bulk_size call or MXNET_MODULE_BULK_SIZE env): it quantizes
# lr-scheduler updates to K batches and skips grad_dict materialization,
# which existing per-batch scripts must not inherit silently.
# None = env not consulted yet: the read is LAZY (first bulk-size
# query), not at import — launchers that set the env after this module
# imports (per-worker env injection, tests) are honored.
_bulk_explicit: bool | None = None


def _consult_env() -> None:
    global _bulk_size, _bulk_explicit
    if _bulk_explicit is not None:
        return
    k = _env.get_int("MXNET_MODULE_BULK_SIZE")
    if k:
        _bulk_size = int(k)
        _bulk_explicit = True
    else:
        _bulk_explicit = False


class _PreviousBulkSize(int):
    """What :func:`set_bulk_size` returns: the previous size, which
    also remembers whether that size had been asked for.  Handing it
    back — the reference's ``prev = set_bulk_size(k); ...;
    set_bulk_size(prev)``, and :func:`bulk` — then restores the opt-in
    state with the number, instead of leaving the default 15 EXPLICIT
    and every later ``Module.fit`` of the process in bulk mode."""

    explicit = True


def set_bulk_size(size: int) -> int:
    """Set the bulk-execution segment limit; returns the previous value
    (ref: engine.py:26).  Per-op fusion is XLA's job; the value is
    consumed at STEP granularity by Module.fit (K steps per dispatch,
    module/bulk.py) once this has been called."""
    global _bulk_size, _bulk_explicit
    _consult_env()
    prev = _PreviousBulkSize(_bulk_size)
    prev.explicit = _bulk_explicit
    _bulk_size = int(size)
    _bulk_explicit = getattr(size, "explicit", True)
    return prev


def fit_bulk_size() -> int:
    """K for Module.fit's bulk path: 1 (per-batch) unless the user
    explicitly opted in via set_bulk_size / MXNET_MODULE_BULK_SIZE."""
    _consult_env()
    return _bulk_size if _bulk_explicit else 1


@contextmanager
def bulk(size: int):
    """Scope form (ref: engine.py bulk)."""
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)


def current_bulk_size() -> int:
    """The configured bulk segment size (consumed by bulk-capable
    runners like FusedTrainStep.run_steps)."""
    _consult_env()
    return _bulk_size
