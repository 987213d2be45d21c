"""Bucket-cap search: replay the recorded timing model through the
DDP pipeline simulator and pick the comm schedule instead of guessing.

PR 4 froze ``MXNET_KVSTORE_BUCKET_BYTES`` at 4 MiB — the NCCL-DDP
folk constant.  The right cap is a tradeoff the simulator makes
explicit once a per-collective launch cost is modeled:

  * caps too LARGE  → the last buckets' reductions run past the end of
    backward (exposed comm — the round-5 monolith is the limit case);
  * caps too SMALL  → B per-collective launch/latency costs dominate
    (each all-reduce pays ring setup + scheduling overhead the
    bytes/bandwidth term doesn't cover).

The sweep walks caps 1–32 MiB with first/last-bucket asymmetry — the
DDP trick: a SMALL first bucket puts the first reduction on the wire
while backward has barely started, a LARGE last bucket folds the tail
buckets (whose reductions can't overlap anything — backward is over)
into fewer launches.  Every candidate is scored by
``scaling.simulate_bucketed_overlap`` under byte-weighted readiness
(bucket k is issueable when its share of backward has run) at the
target chip count; the score is projected efficiency
eff = t_step / (t_step + exposed).

The DEFAULT 4 MiB plan is scored under the SAME model and returned in
the plan's ``score`` block, so "tuned beats default" is always an
auditable claim inside the artifact, with every assumption named.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from .timing import TimingModel

__all__ = ["CAPS_MIB", "DEFAULT_COLL_LATENCY_S", "DEFAULT_ICI_GBPS",
           "plan_bucket_bytes", "tune"]

#: the 1–32 MiB cap ladder (ROADMAP item 3's stated sweep range)
CAPS_MIB: Tuple[int, ...] = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32)

#: per-collective launch cost assumption (ring setup + scheduler
#: dispatch); stated in every emitted plan, overridable per tune()
DEFAULT_COLL_LATENCY_S = 5e-6

#: matches scaling.py's public-v5e effective per-direction figure
DEFAULT_ICI_GBPS = 45.0

_MIB = 1024 * 1024

#: first-bucket cap as a fraction of the mid cap (1.0 = symmetric)
FIRST_FRACS: Tuple[float, ...] = (1.0, 0.5, 0.25)

#: last-bucket cap as a multiple of the mid cap (1 = symmetric)
LAST_MULTS: Tuple[int, ...] = (1, 2, 4)


def _virtual_partition(units: Sequence[Tuple[int, str]], cap: int,
                       first_cap: Optional[int] = None,
                       last_cap: Optional[int] = None) -> List[int]:
    """Repartition RECORDED bucket atoms under new caps: greedy fill in
    issue order (same contract as buckets.partition — dtype never mixes,
    bucket 0 honors the first cap), except an atom LARGER than its cap
    splits into equal chunks (the recorded granularity hides the leaf
    boundaries, so an even split is the honest approximation)."""
    cap = max(int(cap), 1)
    fcap = cap if first_cap is None else max(int(first_cap), 1)
    out: List[Tuple[int, str]] = []  # (bytes, dtype) per bucket
    cur, cur_dtype = 0, None
    for nbytes, dtype in units:
        nbytes = int(nbytes)
        active = fcap if not out else cap
        if cur and (cur_dtype != dtype or cur + nbytes > active):
            out.append((cur, cur_dtype))
            cur, cur_dtype = 0, None
            active = fcap if not out else cap
        if nbytes > active and not cur:
            # split the oversized atom across ceil(n/cap) buckets
            n_chunks = -(-nbytes // active)
            chunk = nbytes // n_chunks
            sizes = [chunk] * n_chunks
            sizes[-1] += nbytes - chunk * n_chunks
            out.extend((s, dtype) for s in sizes)
            continue
        cur += nbytes
        cur_dtype = dtype
    if cur:
        out.append((cur, cur_dtype))
    if last_cap is not None and int(last_cap) > cap:
        lcap = int(last_cap)
        # fold trailing buckets together — never into bucket 0 (that
        # would undo the first-bucket asymmetry) and never across a
        # dtype boundary (the same contract buckets.partition enforces;
        # a cross-dtype fold would score a schedule the runtime
        # partitioner can never build)
        while len(out) > 2 and out[-2][1] == out[-1][1] \
                and out[-2][0] + out[-1][0] <= lcap:
            tail = out.pop()
            prev = out.pop()
            out.append((prev[0] + tail[0], prev[1]))
    return [b for b, _dt in out]


def plan_bucket_bytes(model: TimingModel, cap: int,
                      first_cap: Optional[int] = None,
                      last_cap: Optional[int] = None) -> List[int]:
    """Candidate bucket payloads under (cap, first, last).  Leaf
    granularity repartitions through buckets.partition itself — the
    plan the search scores IS the plan dp.py will build when the caps
    are applied; bucket granularity approximates over the recorded
    atoms (_virtual_partition)."""
    if model.granularity == "leaf":
        from ..parallel import buckets as _buckets

        entries = []
        # model.units are in issue order; partition() reverses its
        # (layer-order) input, so hand it the layer-order flip
        for i, (nbytes, dtype) in enumerate(reversed(model.units)):
            # itemsize via the partitioner's own dtype resolution (ONE
            # fallback table for extension dtypes, never two)
            item = _buckets._nbytes((1,), dtype)
            if nbytes % item:
                item, dtype = 1, "uint8"  # odd payload: count raw bytes
            entries.append((i, (nbytes // item,), dtype))
        plan = _buckets.partition(entries, cap,
                                  first_cap_bytes=first_cap,
                                  last_cap_bytes=last_cap)
        return [int(b.nbytes) for b in plan]
    return _virtual_partition(model.units, cap, first_cap, last_cap)


def tune(model: TimingModel, *, chips: int = 256,
         step_time_s: Optional[float] = None,
         ici_GBps: Optional[float] = None,
         backward_frac: float = 2.0 / 3.0,
         coll_latency_s: float = DEFAULT_COLL_LATENCY_S,
         caps_mib: Sequence[int] = CAPS_MIB,
         first_fracs: Sequence[float] = FIRST_FRACS,
         last_mults: Sequence[int] = LAST_MULTS,
         accum_steps: Optional[int] = None) -> Dict:
    """Sweep the cap ladder and return the tuned-plan dict (the JSON
    ``plan.save_plan`` persists and ``buckets.plan_with_tuning``
    consumes).

    ``accum_steps`` (default: the MXNET_GRAD_ACCUM_STEPS env, via
    remat.grad_accum_steps) makes the scoring accum-aware: under
    microbatch accumulation every bucket is only issueable during the
    LAST microbatch's backward (((A-1)+share)/A readiness), so the
    sweep stops rewarding small early buckets for overlap windows the
    accumulated schedule does not have."""
    from ..parallel import buckets as _buckets
    from ..parallel import scaling as _scaling
    from ..remat import grad_accum_steps as _accum

    accum = _accum(accum_steps)

    step = step_time_s if step_time_s is not None else model.step_time_s
    if step is None or step <= 0:
        raise ValueError(
            "no step time: the overlap model pivots on the measured "
            "single-chip step time — pass step_time_s/--step-time, or "
            "tune from a traceview summary (which carries it)")
    from_trace = (model.source or {}).get("kind") == "trace"
    bw = ici_GBps if ici_GBps is not None else \
        (model.measured_GBps or DEFAULT_ICI_GBPS)
    if ici_GBps is not None:
        bw_source, bandwidth_source = "explicit", "explicit"
    elif model.measured_GBps and from_trace:
        bw_source = "measured (device-trace collective occupancy)"
        bandwidth_source = "trace"
    elif model.measured_GBps:
        bw_source = "measured (flight-dump wire durations)"
        bandwidth_source = "flight"
    else:
        bw_source = "assumed (public v5e figure)"
        bandwidth_source = "assumed"

    # measured-overlap calibration: when the model came from a device
    # trace, the simulator's analytic overlap is checked against the
    # MEASURED compute/comm overlap of the recorded layout and every
    # candidate's exposed time is scaled by the resulting factor — a
    # simulator that is optimistic about this fabric (e.g. a serial
    # executor that overlaps nothing) stops ranking candidates by an
    # overlap it cannot deliver.
    o_meas = getattr(model, "measured_overlap_frac", None)
    exposure_scale = None
    if o_meas is not None:
        rec_sim = _scaling.simulate_bucketed_overlap(
            [b for b, _dt in model.units], step, chips, bw,
            backward_frac, coll_latency_s=coll_latency_s,
            readiness="bytes", accum_steps=accum)
        o_sim = rec_sim["overlap"]
        if o_sim < 1.0:
            exposure_scale = (1.0 - float(o_meas)) / (1.0 - o_sim)
            exposure_scale = min(max(exposure_scale, 0.25), 4.0)

    def score(bucket_bytes):
        sim = _scaling.simulate_bucketed_overlap(
            bucket_bytes, step, chips, bw, backward_frac,
            coll_latency_s=coll_latency_s, readiness="bytes",
            accum_steps=accum)
        exposed = sim["exposed_s"]
        if exposure_scale is not None:
            exposed = exposed * exposure_scale
        eff = step / (step + exposed)
        return eff, sim

    default_bb = plan_bucket_bytes(model, _buckets.DEFAULT_BUCKET_BYTES)
    default_eff, default_sim = score(default_bb)

    best = None
    n_candidates = 0
    for cap_mib in caps_mib:
        cap = int(cap_mib * _MIB)
        for ff in first_fracs:
            first = max(int(cap * ff), 1)
            for lm in last_mults:
                last = cap * int(lm)
                bb = plan_bucket_bytes(model, cap, first, last)
                eff, sim = score(bb)
                n_candidates += 1
                # tie-break toward fewer buckets (less launch-schedule
                # surface for the same modeled efficiency)
                key = (round(eff, 6), -len(bb))
                if best is None or key > best["key"]:
                    best = {"key": key, "eff": eff, "sim": sim,
                            "cap": cap, "first": first, "last": last,
                            "bucket_bytes": bb}
    assert best is not None

    assumptions = {
        "ici_GBps": bw, "ici_GBps_source": bw_source,
        "bandwidth_source": bandwidth_source,
        "backward_frac": backward_frac,
        "coll_latency_s": coll_latency_s,
        "readiness": "bytes",
        "step_time_s": step,
        "grad_accum_steps": accum,
    }
    if exposure_scale is not None:
        assumptions["overlap_calibration"] = {
            "measured_overlap_frac": float(o_meas),
            "simulated_overlap_recorded_layout": o_sim,
            "exposure_scale": exposure_scale,
        }
    projection = _scaling.project_efficiency_bucketed(
        best["bucket_bytes"], step, ici_GBps=bw,
        backward_frac=backward_frac, coll_latency_s=coll_latency_s,
        readiness="bytes", accum_steps=accum)
    return {
        "format": "mxnet-tpu-autotune-plan",
        "version": 1,
        "cap_bytes": best["cap"],
        "first_cap_bytes": best["first"],
        "last_cap_bytes": best["last"],
        "n_buckets": len(best["bucket_bytes"]),
        "bucket_bytes": [int(b) for b in best["bucket_bytes"]],
        "fingerprint": model.fingerprint(),
        "score": {
            "chips": int(chips),
            "eff": round(best["eff"], 4),
            "exposed_s": best["sim"]["exposed_s"],
            "overlap": best["sim"]["overlap"],
            "default_cap_bytes": _buckets.DEFAULT_BUCKET_BYTES,
            "default_eff": round(default_eff, 4),
            "default_exposed_s": default_sim["exposed_s"],
            "default_n_buckets": len(default_bb),
            "beats_default": bool(best["eff"] >= default_eff),
            "n_candidates": n_candidates,
            **({"measured": {
                "overlap_frac": float(o_meas),
                "bucket_occupancy": getattr(model, "bucket_occupancy",
                                            None),
                "source": "trace",
            }} if o_meas is not None else {}),
        },
        "assumptions": assumptions,
        "projection": projection,
        "source": model.source,
    }
