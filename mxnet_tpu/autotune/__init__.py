"""mx.autotune — self-tuning collectives (ROADMAP item 3).

Closes the loop the repo has carried open since PR 4: the flight
recorder (diagnostics.py) records every bucket reduction's
seq/bucket/bytes/enqueue/complete and stamps the bucket plan into its
dumps; ``parallel/scaling.py`` carries the DDP pipeline simulator —
everything needed to SEARCH the comm schedule instead of hardcoding
the 4 MiB ``MXNET_KVSTORE_BUCKET_BYTES`` guess.

The pipeline:

  1. **extract** (``timing.py``) — flight-recorder dumps /
     ``merge_traces --bucket-timings`` exports / traceview summaries /
     raw gradient leaves → one replayable :class:`TimingModel`
     (payload units in issue order + measured step time + measured
     wire bandwidth where real durations exist);
  2. **search** (``search.py``) — sweep bucket caps 1–32 MiB with
     first/last-bucket asymmetry through
     ``scaling.simulate_bucketed_overlap`` (byte-weighted readiness +
     per-collective launch cost) and score projected efficiency at the
     target chip count, always scoring the 4 MiB default under the
     same model for an auditable tuned-vs-default delta;
  3. **apply** (``plan.py``) — persist the winning plan as JSON;
     ``parallel/buckets.plan_with_tuning`` consumes it at step-build
     time via ``MXNET_AUTOTUNE_PLAN`` (explicit file) or
     ``MXNET_AUTOTUNE_DIR`` (fingerprint-matched cache), and the
     chosen caps ride the plan_meta stamp into flight-recorder
     headers.

CLI: ``python -m mxnet_tpu.autotune --self-test | --tune <dump> |
--apply`` (see ``__main__.py``).
"""
from __future__ import annotations

from . import plan, search, timing
from .plan import load_plan, resolve_caps, save_plan
from .search import tune
from .timing import TimingModel, from_bucket_timings, from_flight_dump, \
    from_leaf_bytes, load_any

__all__ = [
    "timing", "search", "plan",
    "TimingModel", "from_flight_dump", "from_bucket_timings",
    "from_leaf_bytes", "load_any",
    "tune", "save_plan", "load_plan", "resolve_caps",
]
