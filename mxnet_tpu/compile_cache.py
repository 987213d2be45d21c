"""mx.compile_cache — the one place that configures JAX's persistent
compilation cache.

Where the cache lives is decided OUTSIDE the program:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself at
    import and uses that directory; this module sets no other.
  * unset: a fixed directory inside the checkout (``.jax_cache/`` next
    to the ``mxnet_tpu`` package, git-ignored).  The path is part of
    JAX's cache key, so it is derived from the package's own location —
    never a temp name, a pid or a time — and every process of one
    checkout lands on the same entries.
  * ``JAX_ENABLE_COMPILATION_CACHE=false`` (JAX's own switch) turns the
    cache off; :func:`enable` then returns None.  tests/conftest.py does
    this so a tier-1 run leaves nothing in the checkout.

:func:`enable` zeroes the min-entry/min-compile-time thresholds so
every program is eligible, keys the cache on the programs' metadata
too (so that a cached executable carries the scope names of the tree
that asks for it), and every compiled-path build site calls it
(``FusedTrainStep._build``, ``BulkTrainLoop._build``,
``TransformerTrainStep``, ``ModelRuntime.compile``,
``GenerationRuntime``).  A cache that cannot be enabled raises: a run
that silently recompiles everything is a different run.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Optional

__all__ = ["enable", "enabled_dir", "default_dir", "entry_count"]

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_enabled_dir: Optional[str] = None

_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """The in-checkout cache directory used when the environment names
    none: the same path in every process of this checkout."""
    pkg = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable() -> Optional[str]:
    """Turn the persistent compilation cache on and return its
    directory (None when ``JAX_ENABLE_COMPILATION_CACHE`` disabled it).
    Idempotent.  Raises when the directory cannot be used."""
    global _enabled_dir
    import jax

    with _lock:
        if _enabled_dir is not None:
            return _enabled_dir
        if not jax.config.jax_enable_compilation_cache:
            return None
        env_dir = os.environ.get(_ENV)
        if env_dir:
            # JAX read the variable at import; configuring a directory
            # here as well would make two owners of one setting
            d = jax.config.jax_compilation_cache_dir
            if d != env_dir:
                raise RuntimeError(
                    "%s=%r but jax is using %r: set the variable before "
                    "jax is imported" % (_ENV, env_dir, d))
        else:
            d = default_dir()
        os.makedirs(d, exist_ok=True)
        if not os.access(d, os.W_OK | os.X_OK):
            raise RuntimeError("compile cache directory %r is not "
                               "writable" % (d,))
        if not env_dir:
            jax.config.update("jax_compilation_cache_dir", d)
        # every program is cache-eligible: a bound model is hundreds of
        # individually small, fast programs, exactly the ones the
        # default thresholds would exclude
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        # jax leaves locations, and the named scopes that live in them,
        # out of the cache key by default: an executable compiled by a
        # tree without the scope names (ndarray.invoke, Block.__call__,
        # transformer/model.py) would then be served to a tree with
        # them, and every trace of it would read unnamed.  The price: a
        # source edit that moves a traced line compiles anew.
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        _enabled_dir = d
        _log.info("persistent XLA compilation cache: %s", d)
        return d


def enabled_dir() -> Optional[str]:
    """The directory :func:`enable` activated (None if never)."""
    return _enabled_dir


def entry_count(cache_dir: Optional[str] = None) -> int:
    """Number of compiled programs in the cache directory (0 when the
    cache is off or the directory does not exist yet)."""
    d = cache_dir or _enabled_dir
    if not d or not os.path.isdir(d):
        return 0
    return sum(1 for f in os.listdir(d) if f.endswith("-cache"))
