"""Gradient-exchange accounting and the bucket-pipeline timing model.

What the bucket planner, the autotuner and their tests share:

* ``reduction_accounting()`` — one row per collective instruction of a
  compiled step's HLO, with its payload bytes: what the program will
  put on the wire, bucket by bucket.
* ``grad_entries()`` / ``grad_leaf_bytes()`` — the ``(name, shape,
  dtype)`` leaves of any model's gradient exchange in layer order, and
  their payload bytes: the input of ``buckets.partition`` and of the
  autotuner's timing model.
* ``simulate_bucketed_overlap()`` / ``project_efficiency_bucketed()`` —
  a DDP pipeline MODEL over a bucket plan and a step time: buckets
  become issueable through backward and serialize on the comm stream;
  what runs past the end of backward is exposed.  Its assumptions (ICI
  bandwidth 45 GB/s effective per direction, backward two thirds of the
  step) are returned with its output.  It has not been held against a
  run on chips (ROADMAP W1).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

from .overlap import hlo_bytes_in as _hlo_bytes_in

_COLL_RE = re.compile(
    r"=\s+(.*?)\s*\b"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)?\(")


def reduction_accounting(hlo_text: str) -> List[Dict[str, object]]:
    """Per-reduction rows from compiled HLO: one entry per all-reduce /
    reduce-scatter / collective-permute-chain instruction with payload
    bytes — the ground truth that the bucketed exchange really compiles
    to MANY reductions (count/bytes per reduction), not the round-5
    combined monolith."""
    rows: List[Dict[str, object]] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        shapes, op, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue
        rows.append({"op": op + (suffix or ""),
                     "bytes": int(_hlo_bytes_in(shapes))})
    return rows


def grad_entries(params, dtype: Optional[str] = None) -> List[tuple]:
    """MODEL-AGNOSTIC gradient-exchange leaves: ``(name, shape, dtype)``
    for every trainable entry of ``params`` in ITERATION (= layer)
    order — exactly what ``buckets.partition`` / the autotuner's
    leaf-granularity timing model consume.

    ``params`` is any ``{name: leaf}`` mapping whose leaves carry
    ``.shape`` — gluon ``collect_params()``, a transformer param dict
    (``mxnet_tpu.transformer.init_params``), plain jax/numpy arrays —
    or an already-built ``(name, shape, dtype)`` entry list (passed
    through, re-dtyped).  Entries with ``grad_req == 'null'`` are
    skipped (frozen params don't ride the exchange); ``dtype``
    overrides each leaf's own dtype (the bf16-wire projection over
    fp32-held params)."""
    out: List[tuple] = []
    items = params.items() if hasattr(params, "items") else None
    if items is None:
        # (name, shape, dtype) triples — e.g. transformer.param_shapes
        for name, shape, dt in params:
            out.append((name, tuple(shape), dtype or str(dt)))
        return out
    for name, p in items:
        if getattr(p, "grad_req", None) == "null":
            continue
        dt = dtype if dtype is not None else \
            str(getattr(p, "dtype", "float32"))
        out.append((name, tuple(p.shape), dt))
    return out


def grad_leaf_bytes(entries: Sequence[tuple]) -> List[int]:
    """Per-gradient payload bytes for ``grad_entries`` output, in the
    same order — the autotuner's exact-granularity input
    (``autotune.from_leaf_bytes``)."""
    from . import buckets as _buckets

    return [_buckets._nbytes(shape, dt) for _name, shape, dt in entries]


def simulate_bucketed_overlap(bucket_bytes: Sequence[int],
                              step_time_s: float, n: int,
                              ici_GBps: float = 45.0,
                              backward_frac: float = 2.0 / 3.0,
                              coll_latency_s: float = 0.0,
                              readiness: str = "uniform",
                              accum_steps: int = 1) -> Dict:
    """DDP pipeline model over a measured bucket plan: bucket k's
    reduction becomes issueable partway through backward (reverse layer
    order) and reductions serialize on the comm stream (the
    chained-psum / NCCL-stream semantics); whatever comm time runs past
    the end of backward is exposed.

    ``readiness`` picks the issueability model: ``'uniform'`` (the r6
    default — bucket k at (k+1)/B of backward, uniform compute per
    bucket) or ``'bytes'`` (bucket k when its cumulative byte share of
    backward has run — the autotuner's model, where a small FIRST
    bucket genuinely starts comm earlier).  ``coll_latency_s`` adds a
    per-reduction launch cost (ring setup + dispatch): with it the cap
    sweep has a real optimum — too-small buckets pay B launches,
    too-large buckets expose the comm tail.  Defaults reproduce the r6
    behavior exactly.

    ``accum_steps`` > 1 models microbatch gradient accumulation
    (MXNET_GRAD_ACCUM_STEPS): gradients only exist after the LAST
    microbatch's backward, so bucket k becomes issueable at
    ((A-1) + share)/A of the step's total backward time — the first
    A-1 microbatches offer no overlap window, compressing all comm
    into the final 1/A and cutting the achievable overlap (the honest
    cost of accumulation the autotuner must score).

    A MODEL, not a measured schedule — returned with its assumptions so
    the artifact can never pass it off as a measurement."""
    t_bwd = backward_frac * step_time_s
    A = max(int(accum_steps), 1)
    ring = 2.0 * (n - 1) / n
    clock, total = 0.0, 0.0
    B = max(len(bucket_bytes), 1)
    total_bytes = float(sum(bucket_bytes)) or 1.0
    cum = 0
    for k, nbytes in enumerate(bucket_bytes):
        cum += nbytes
        share = (cum / total_bytes if readiness == "bytes"
                 else (k + 1) / B)
        ready = ((A - 1) + share) / A * t_bwd
        dur = coll_latency_s + ring * nbytes / (ici_GBps * 1e9)
        clock = max(clock, ready) + dur
        total += dur
    exposed = max(0.0, clock - t_bwd)
    overlap = 1.0 - exposed / total if total else 1.0
    return {"overlap": round(max(0.0, min(1.0, overlap)), 4),
            "exposed_s": exposed, "t_comm_total_s": total,
            "t_backward_s": t_bwd, "n_buckets": len(bucket_bytes),
            "coll_latency_s": coll_latency_s, "readiness": readiness,
            "accum_steps": A}


def project_efficiency_bucketed(bucket_bytes: Sequence[int],
                                step_time_s: float,
                                chips: Sequence[int] = (8, 16, 32, 64,
                                                        128, 256),
                                ici_GBps: float = 45.0,
                                backward_frac: float = 2.0 / 3.0,
                                coll_latency_s: float = 0.0,
                                readiness: str = "uniform",
                                accum_steps: int = 1) -> Dict:
    """Scaling projection under the bucket-pipeline model:
    eff(n) = t_step / (t_step + exposed(n)).  ``coll_latency_s`` /
    ``readiness`` / ``accum_steps`` thread through to
    simulate_bucketed_overlap (the autotuner scores candidates under
    readiness='bytes' + a stated launch cost, accum-aware when
    MXNET_GRAD_ACCUM_STEPS>1; defaults reproduce r6)."""
    table = {}
    detail = {}
    for n in chips:
        sim = simulate_bucketed_overlap(bucket_bytes, step_time_s, n,
                                        ici_GBps, backward_frac,
                                        coll_latency_s=coll_latency_s,
                                        readiness=readiness,
                                        accum_steps=accum_steps)
        table[str(n)] = round(
            step_time_s / (step_time_s + sim["exposed_s"]), 4)
        detail[str(n)] = sim["overlap"]
    return {
        "model": "bucket-pipeline: reverse-layer-order buckets become "
                 "issueable through backward (%s readiness), serialize "
                 "on the comm stream; eff = t_step/(t_step + exposed). "
                 "A MODEL over the measured bucket plan and step time, "
                 "not a measured schedule" % readiness,
        "bucket_bytes": list(int(b) for b in bucket_bytes),
        "step_time_s": step_time_s,
        "ici_GBps_assumed": ici_GBps,
        "backward_frac_assumed": backward_frac,
        "coll_latency_s_assumed": coll_latency_s,
        "overlap_by_chips": detail,
        "projected_efficiency": table,
        "reference_resnet152_256gpu": 0.901,
    }
