"""Jax-free walker over an XLA trace-event export: phase + bucket
attribution of the device timeline.

The capture side (capture.py) wraps ``jax.profiler`` and brackets each
training/serving dispatch with a ``mxnet:step:<i>:k=<k>`` annotation;
this module turns the resulting chrome trace-event JSON into the ONE
summary the consumers share (autotune ``from_trace``, ``merge_traces
--health`` phase-skew, ``profiler.summary()``'s phase table):

  * device lanes — XLA thunk/stream events, recognized by their
    ``args.hlo_op``/``args.hlo_module`` stamps (XLA:CPU's per-thunk
    events on the ``tf_XLATfrtCpuClient`` executor threads) or by a
    ``/device:``-named process (TPU stream lanes);
  * step phases — H2D, forward, backward, ``bucket-k`` reduce,
    optimizer, D2H.  Collectives match by op-name pattern
    (``all-reduce*``/``reduce-scatter*``/...) and are mapped onto the
    stamped ``plan_meta`` bucket plan by distinct-op issue order;
    compute splits around the comm envelope (ops ending before the
    first reduce are forward, ops after the last reduce are the
    optimizer) unless the op name carries an explicit
    ``mxnet-fwd``/``mxnet-bwd``/``mxnet-opt`` scope token;
  * measured numbers — per-bucket collective device occupancy,
    compute/comm overlap fraction (interval intersection per device),
    and the per-phase wall breakdown with p50/p99 over steps.

Everything here is stdlib-only on purpose: the walker must run on a
box with no jax at all (offline trace triage, merge_traces --health).
"""
from __future__ import annotations

import glob
import gzip
import io
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "SUMMARY_FORMAT", "SUMMARY_VERSION", "load_trace", "find_trace_file",
    "attribute", "classify_op", "is_traceview_summary",
    "find_xplane_file", "load_op_index",
]

SUMMARY_FORMAT = "mxnet-tpu-traceview-summary"
SUMMARY_VERSION = 1

#: the capture annotation: mxnet:step:<idx>[:k=<n>] (serve windows use
#: the same grammar with a different verb)
STEP_RE = re.compile(r"^mxnet:(step|serve):(\d+)(?::k=(\d+))?$")

COMM_RE = re.compile(
    r"(all-reduce|reduce-scatter|all-gather|collective-permute|"
    r"all-to-all|ncclAllReduce|cross-replica-sum)", re.IGNORECASE)
H2D_RE = re.compile(
    r"(TransferToDevice|CopyToDevice|DevicePut|BufferFromHost|"
    r"infeed|h2d)", re.IGNORECASE)
D2H_RE = re.compile(
    r"(TransferFromDevice|CopyFromDevice|TransferLiteral|"
    r"BufferToHost|outfeed|d2h)", re.IGNORECASE)
#: explicit scope tokens win over the timeline split (TPU traces carry
#: jax.named_scope in op metadata names; the committed fixture does too)
SCOPE_TOKENS = (("mxnet-fwd", "forward"), ("mxnet-bwd", "backward"),
                ("mxnet-opt", "optimizer"))

PHASES = ("h2d", "forward", "backward", "bucket_reduce", "optimizer",
          "d2h")


def is_traceview_summary(payload) -> bool:
    return isinstance(payload, dict) and \
        payload.get("format") == SUMMARY_FORMAT


def find_trace_file(dirpath: str) -> Optional[str]:
    """Newest ``*.trace.json(.gz)`` under a jax profiler dump dir
    (``<dir>/plugins/profile/<ts>/<host>.trace.json.gz``) or directly
    under ``dirpath``."""
    pats = [os.path.join(dirpath, "plugins", "profile", "*",
                         "*.trace.json.gz"),
            os.path.join(dirpath, "plugins", "profile", "*",
                         "*.trace.json"),
            os.path.join(dirpath, "*.trace.json.gz"),
            os.path.join(dirpath, "*.trace.json")]
    hits: List[str] = []
    for p in pats:
        hits.extend(glob.glob(p))
    return max(hits, key=os.path.getmtime) if hits else None


def load_trace(path: str) -> dict:
    """Trace-event payload from a ``.json``/``.json.gz`` file or a jax
    profiler dump directory."""
    if os.path.isdir(path):
        found = find_trace_file(path)
        if found is None:
            raise FileNotFoundError(
                "no *.trace.json(.gz) under %r — is it a jax profiler "
                "dump dir (plugins/profile/<ts>/)?" % path)
        path = found
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return json.load(io.TextIOWrapper(f, encoding="utf-8"))
    with open(path) as f:
        return json.load(f)


#: bucket identity scope stamped by buckets.bucketed_reduce /
#: dp.zero1_bucketed_update (jax.named_scope("mxbkt%03d" % i)) — the
#: only channel that survives into XLA op metadata on every backend
BUCKET_SCOPE_RE = re.compile(r"mxbkt(\d+)")

#: candidate metadata records in the xplane sidecar: field-1 name tag
#: (0x0a) + 1-byte length + an instruction-name-shaped string, with the
#: category field tag (0x12) right behind — cheap pre-filter before the
#: real wire-format parse
_XPLANE_REC_RE = re.compile(
    rb"\n([\x04-\x7f])([A-Za-z_][0-9A-Za-z._-]*)\x12")


def find_xplane_file(trace_path: str) -> Optional[str]:
    """The ``*.xplane.pb`` sibling of a trace-event file (jax writes
    both into the same ``plugins/profile/<ts>/`` dir)."""
    d = trace_path if os.path.isdir(trace_path) \
        else os.path.dirname(trace_path)
    hits = glob.glob(os.path.join(d, "*.xplane.pb"))
    return max(hits, key=os.path.getmtime) if hits else None


def _pb_varint(data: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = data[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7
        if shift > 63:
            raise ValueError("varint overflow")


def _pb_fields(data: bytes, pos: int, end: int):
    """Tolerant protobuf wire walk: yields (field_no, wire_type,
    value) until ``end`` or a malformed record."""
    while pos < end:
        tag, pos = _pb_varint(data, pos)
        f, wt = tag >> 3, tag & 7
        if wt == 0:
            v, pos = _pb_varint(data, pos)
        elif wt == 2:
            ln, pos = _pb_varint(data, pos)
            v = data[pos:pos + ln]
            pos += ln
            if pos > end:
                return
        elif wt == 1:
            v, pos = data[pos:pos + 8], pos + 8
        elif wt == 5:
            v, pos = data[pos:pos + 4], pos + 4
        else:
            return
        yield f, wt, v


def _pb_record_end(data: bytes, name_pos: int, min_len: int = 0) -> int:
    """End offset of the metadata record whose field-1 name starts at
    ``name_pos`` (the record is itself a length-delimited field, so
    the enclosing length varint sits just before the name tag).
    ``min_len`` rejects false tags: a continuation byte of the length
    varint can coincidentally decode as a wire-type-2 tag one position
    later (e.g. ``\\x12\\xba\\x01`` — 0xba & 7 == 2), yielding a bogus
    1-byte record; a real record must at least span the name field."""
    for nb in (1, 2, 3):
        tag_pos = name_pos - nb - 1
        if tag_pos >= 0 and data[tag_pos] & 7 == 2:
            try:
                ln, after = _pb_varint(data, tag_pos + 1)
            except (ValueError, IndexError):
                continue
            if after == name_pos and ln >= min_len \
                    and name_pos + ln <= len(data):
                return name_pos + ln
    return min(name_pos + 600, len(data))


def load_op_index(xplane_path: str) -> Dict[str, dict]:
    """HLO-op metadata sidecar from an ``*.xplane.pb``: maps each
    instruction name -> {scope, file, line} where ``scope`` is the jax
    op_name path (``jit(local_step)/.../mxbkt003/psum``) and file/line
    the python source of the issuing primitive.  The trace-event JSON
    carries only instruction names (``all-reduce.174``); this sidecar
    is what lets the walker (a) tell a ``mxbkt<i>``-scoped bucket-k
    gradient reduce from a BatchNorm statistics psum with the SAME
    instruction shape, and (b) split compute between forward and
    backward by jax's ``jvp(...)``/``transpose(...)`` scope markers
    instead of guessing from the timeline.  Byte-level scan on
    purpose — no protobuf dependency, and the schema touched is just
    (name, category, {op_name, source file, source line})."""
    with open(xplane_path, "rb") as f:
        data = f.read()
    out: Dict[str, dict] = {}
    for m in _XPLANE_REC_RE.finditer(data):
        ln, name_b = m.group(1)[0], m.group(2)
        # the name must fill its length field exactly, up to the
        # category tag the regex anchored on
        if ln != len(name_b):
            continue
        name = name_b.decode("ascii", "replace")
        if name in out:
            continue
        name_pos = m.start()  # at the \n tag byte
        end = _pb_record_end(data, name_pos, min_len=2 + len(name_b))
        try:
            info = None
            for f_no, wt, v in _pb_fields(data, name_pos, end):
                if f_no == 7 and wt == 2:
                    sub = {"scope": None, "file": None, "line": None}
                    for sf, swt, sv in _pb_fields(v, 0, len(v)):
                        if sf == 2 and swt == 2:
                            sub["scope"] = sv.decode("utf-8", "replace")
                        elif sf == 3 and swt == 2:
                            sub["file"] = sv.decode("utf-8", "replace")
                        elif sf == 4 and swt == 0:
                            sub["line"] = int(sv)
                    if sub["scope"]:
                        info = sub
                        break
        except (ValueError, IndexError):
            info = None
        if info:
            out[name] = info
    return out


def _phase_from_jax_scope(scope: str) -> Optional[str]:
    """forward/backward from the jax autodiff markers in an op_name
    scope path: ``transpose(...)`` ops are the backward pass,
    ``jvp(...)``-only ops the forward trace; anything outside both
    (data cast, optimizer update, key folding) stays None for the
    timeline split."""
    if "transpose(" in scope:
        return "backward"
    if "jvp(" in scope:
        return "forward"
    return None


def classify_op(name: str) -> str:
    """'h2d' | 'd2h' | 'comm' | 'compute' for one device-op name; the
    forward/backward/optimizer split of 'compute' needs the timeline
    context and happens in attribute()."""
    if COMM_RE.search(name):
        return "comm"
    if H2D_RE.search(name):
        return "h2d"
    if D2H_RE.search(name):
        return "d2h"
    return "compute"


def _scope_phase(name: str) -> Optional[str]:
    for token, phase in SCOPE_TOKENS:
        if token in name:
            return phase
    return None


def _comm_base(name: str) -> str:
    """Normalize async pairs: ``all-reduce-start.1``/``-done.1`` fold
    onto one logical collective."""
    return name.replace("-start.", ".").replace("-done.", ".")


def _percentile(vals: Sequence[float], q: float) -> Optional[float]:
    if not vals:
        return None
    s = sorted(vals)
    idx = min(int(round(q * (len(s) - 1))), len(s) - 1)
    return s[idx]


def _union(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _intersect_total(a: List[Tuple[float, float]],
                     b: List[Tuple[float, float]]) -> float:
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _device_lanes(events: Sequence[dict]) -> Dict[tuple, List[dict]]:
    """Group XLA device-op events into lanes.

    A device op is any 'X' event stamped with ``args.hlo_op`` /
    ``args.hlo_module`` (XLA:CPU thunk events), or any 'X' event on a
    pid whose process_name says ``/device:`` (TPU stream lanes).  Lane
    keys group by device: TPU lanes share their device pid (one device,
    several stream tids — overlap is measured ACROSS those streams);
    CPU thunk lanes are one executor thread per device, so (pid, tid)
    is the device."""
    proc_names: Dict[int, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc_names[e.get("pid")] = str(
                (e.get("args") or {}).get("name", ""))
    lanes: Dict[tuple, List[dict]] = {}
    for e in events:
        if e.get("ph") != "X" or e.get("dur") is None:
            continue
        args = e.get("args") or {}
        pname = proc_names.get(e.get("pid"), "")
        is_device_proc = "/device:" in pname
        if not (is_device_proc or "hlo_op" in args
                or "hlo_module" in args):
            continue
        key = (e.get("pid"),) if is_device_proc \
            else (e.get("pid"), e.get("tid"))
        lanes.setdefault(key, []).append(e)
    for evs in lanes.values():
        evs.sort(key=lambda e: float(e["ts"]))
    return lanes


def _step_windows(events: Sequence[dict]
                  ) -> List[Tuple[float, float, int, int]]:
    """(t0, t1, idx, k) per capture annotation, time-ordered."""
    wins = []
    for e in events:
        if e.get("ph") != "X" or e.get("dur") is None:
            continue
        m = STEP_RE.match(str(e.get("name", "")))
        if m:
            t0 = float(e["ts"])
            wins.append((t0, t0 + float(e["dur"]), int(m.group(2)),
                         int(m.group(3) or 1)))
    wins.sort()
    return wins


def _op_name(e: dict) -> str:
    args = e.get("args") or {}
    return str(args.get("hlo_op") or e.get("name") or "")


def attribute(trace: dict, plan_meta: Optional[dict] = None,
              flight_entries: Optional[Sequence[dict]] = None,
              workload: Optional[str] = None,
              op_index: Optional[Dict[str, dict]] = None) -> dict:
    """Walk one rank's trace-event payload into the traceview summary
    dict (format ``mxnet-tpu-traceview-summary`` v1).

    ``plan_meta`` is the stamped bucket plan (buckets.plan_meta) the
    collectives are matched against; ``flight_entries`` the rank's
    flight-recorder entries for the seq-order cross-check and the
    chaos ``injected`` tagging; ``op_index`` the xplane metadata
    sidecar (load_op_index) — with it, bucket identity comes from the
    ``mxbkt<i>`` scope the reduction was issued under (EXACT, and it
    separates gradient reduces from BatchNorm-stat psums / the loss
    pmean, which share the all-reduce instruction shape); without it,
    distinct-comm-name issue order is the fallback mapping."""
    events = trace.get("traceEvents") or []
    lanes = _device_lanes(events)
    windows = _step_windows(events)
    if not windows:
        # no annotations (a raw jax.profiler capture): the whole
        # device-event span is one window
        all_ts = [float(e["ts"]) for evs in lanes.values() for e in evs]
        all_te = [float(e["ts"]) + float(e["dur"])
                  for evs in lanes.values() for e in evs]
        if all_ts:
            windows = [(min(all_ts), max(all_te), 0, 1)]

    plan_buckets = sorted((plan_meta or {}).get("buckets") or [],
                          key=lambda r: int(r.get("bucket", 0)))
    n_plan = len(plan_buckets)

    # bucket mapping, best channel first:
    #   scope  — the op_index sidecar names the issuing scope; only
    #            mxbkt<i>-scoped collectives are bucket reduces, the
    #            rest (BatchNorm stats, loss pmean) are other-comm;
    #   order  — distinct comm op names in first-issue order across
    #            the whole capture (lax.scan repeats the same names
    #            every iteration, so distinct-order is iteration-
    #            invariant); only sound when nothing BUT the bucket
    #            reduces is a collective
    bucket_of: Dict[str, int] = {}
    bucket_map = "issue-order"
    if op_index:
        for opname, info in op_index.items():
            # only the collectives map to buckets — the scope also
            # covers the pack/unpack compute, which must not be able
            # to fake a complete bucket cover for plan_match
            if classify_op(opname) != "comm":
                continue
            sm = BUCKET_SCOPE_RE.search(str(info.get("scope") or ""))
            if sm is not None:
                base = _comm_base(opname)
                bucket_of[base] = int(sm.group(1))
        if bucket_of:
            bucket_map = "scope"
    if bucket_map == "scope":
        plan_match = bool(n_plan) and \
            sorted(set(bucket_of.values())) == list(range(n_plan))
    else:
        comm_order: List[str] = []
        for evs in lanes.values():
            for e in evs:
                name = _op_name(e)
                if classify_op(name) == "comm":
                    base = _comm_base(name)
                    if base not in comm_order:
                        comm_order.append(base)
            if comm_order:
                break
        bucket_of = {base: i for i, base in enumerate(comm_order)}
        plan_match = bool(n_plan) and len(comm_order) == n_plan

    # per-step accumulators, lane-meaned
    phase_steps: Dict[str, List[float]] = {p: [] for p in PHASES}
    bucket_steps: Dict[int, List[float]] = {}
    wall_s: List[float] = []
    comm_ps: List[float] = []
    comp_ps: List[float] = []
    ovl_ps: List[float] = []

    for (t0, t1, _idx, k) in windows:
        k = max(int(k), 1)
        per_lane: List[Dict[str, float]] = []
        per_lane_b: List[Dict[int, float]] = []
        per_lane_ovl: List[Tuple[float, float, float]] = []
        for evs in lanes.values():
            win = []
            for e in evs:
                ts = float(e["ts"])
                te = ts + float(e["dur"])
                lo, hi = max(ts, t0), min(te, t1)
                if hi > lo:
                    win.append((lo, hi, _op_name(e),
                                str(e.get("name") or "")))
            if not win:
                continue
            # in scope mode only mxbkt-scoped collectives are the
            # gradient exchange; BatchNorm-stat psums / the loss pmean
            # are computation that HAPPENS to be collective — they ride
            # the compute side of the overlap measurement and the
            # forward/backward timeline split
            def _is_bucket_comm(n):
                return classify_op(n) == "comm" and \
                    (bucket_map != "scope"
                     or _comm_base(n) in bucket_of)
            comm = [(a, b, n) for a, b, n, _d in win
                    if _is_bucket_comm(n)]
            first_comm = min((a for a, _b, _n in comm), default=None)
            last_comm = max((b for _a, b, _n in comm), default=None)
            # backward-start estimate from jax's transpose() autodiff
            # scope markers: a serial executor may schedule every
            # bucket reduce after the whole backward pass, which makes
            # "ends before the first reduce" a useless forward test —
            # the earliest transpose-scoped op is a far better anchor
            # for the ops that carry no metadata of their own
            bwd_start = None
            if op_index:
                bwd_start = min(
                    (a for a, _b, n, _d in win
                     if "transpose(" in str((op_index.get(n) or {})
                                            .get("scope") or "")),
                    default=None)
            ph: Dict[str, float] = {p: 0.0 for p in PHASES}
            bk: Dict[int, float] = {}
            comp_iv: List[Tuple[float, float]] = []
            for a, b, name, display in win:
                kind = classify_op(name)
                dur = b - a
                if kind == "comm" and _is_bucket_comm(name):
                    ph["bucket_reduce"] += dur
                    j = bucket_of.get(_comm_base(name))
                    if j is not None:
                        bk[j] = bk.get(j, 0.0) + dur
                    continue
                if kind in ("h2d", "d2h"):
                    ph[kind] += dur
                    continue
                comp_iv.append((a, b))
                # the display name carries jax.named_scope tokens when
                # the runtime surfaces them; hlo_op never does
                phase = _scope_phase(display) or _scope_phase(name)
                if phase is None and op_index:
                    info = op_index.get(name)
                    if info:
                        scope = str(info.get("scope") or "")
                        if BUCKET_SCOPE_RE.search(scope):
                            # pack/unpack (concat/slice) fusions of a
                            # bucket: exchange machinery, charged to
                            # bucket_reduce, not forward compute —
                            # they stay compute intervals for the
                            # overlap measurement (local work that CAN
                            # hide under another bucket's wire time)
                            phase = "bucket_reduce"
                        else:
                            phase = _phase_from_jax_scope(scope)
                if phase is None:
                    if bwd_start is not None:
                        if b <= bwd_start:
                            phase = "forward"
                        elif last_comm is not None and a >= last_comm:
                            phase = "optimizer"
                        else:
                            phase = "backward"
                    elif first_comm is None or b <= first_comm:
                        phase = "forward"
                    elif a >= last_comm:
                        phase = "optimizer"
                    else:
                        phase = "backward"
                ph[phase] += dur
            per_lane.append(ph)
            per_lane_b.append(bk)
            comm_u = _union([(a, b) for a, b, _n in comm])
            comp_u = _union(comp_iv)
            per_lane_ovl.append((
                sum(b - a for a, b in comm_u),
                sum(b - a for a, b in comp_u),
                _intersect_total(comm_u, comp_u)))
        if not per_lane:
            continue
        n_lanes = len(per_lane)
        us = 1e-6 / k  # µs -> s, normalized per micro-step
        for p in PHASES:
            phase_steps[p].append(
                sum(ph[p] for ph in per_lane) / n_lanes * us)
        for j in set().union(*per_lane_b) if per_lane_b else set():
            bucket_steps.setdefault(j, []).append(
                sum(bk.get(j, 0.0) for bk in per_lane_b) / n_lanes * us)
        wall_s.append((t1 - t0) * 1e-6 / k)
        comm_ps.append(sum(o[0] for o in per_lane_ovl) / n_lanes * us)
        comp_ps.append(sum(o[1] for o in per_lane_ovl) / n_lanes * us)
        ovl_ps.append(sum(o[2] for o in per_lane_ovl) / n_lanes * us)

    n_steps = len(wall_s)
    mean_wall = sum(wall_s) / n_steps if n_steps else None

    phases_out = {}
    for p in PHASES:
        vals = phase_steps[p]
        tot = sum(vals)
        phases_out[p] = {
            "total_s": tot,
            "per_step_s": vals,
            "mean_s": tot / len(vals) if vals else None,
            "pct_of_step": (tot / sum(wall_s) * 100.0)
            if wall_s and sum(wall_s) else None,
            "p50_s": _percentile(vals, 0.50),
            "p99_s": _percentile(vals, 0.99),
        }

    buckets_out = []
    injected_buckets = set()
    inj_kinds: List[str] = []
    n_inj = 0
    for e in flight_entries or ():
        if e.get("injected"):
            n_inj += 1
            kind = str(e.get("injected_kind") or "unknown")
            if kind not in inj_kinds:
                inj_kinds.append(kind)
            if e.get("bucket") is not None:
                injected_buckets.add(int(e["bucket"]))
    for j in sorted(bucket_steps):
        vals = bucket_steps[j]
        dps = sum(vals) / len(vals)
        row = {"bucket": j, "device_s_per_step": dps,
               "occupancy": dps / mean_wall if mean_wall else None,
               "injected_stall": j in injected_buckets}
        if j < n_plan:
            nbytes = int(plan_buckets[j].get("bytes") or 0)
            row["bytes"] = nbytes
            row["dtype"] = plan_buckets[j].get("dtype")
            if nbytes and dps > 0:
                row["measured_GBps"] = nbytes / dps / 1e9
        buckets_out.append(row)

    comm_mean = sum(comm_ps) / n_steps if n_steps else 0.0
    comp_mean = sum(comp_ps) / n_steps if n_steps else 0.0
    ovl_mean = sum(ovl_ps) / n_steps if n_steps else 0.0

    # flight cross-check: the recorder's bucket_reduce seq order must
    # walk buckets 0..B-1 ascending (the issue schedule the trace's
    # distinct-op order was matched against)
    flight_check: dict = {"checked": False}
    br = [e for e in (flight_entries or ())
          if e.get("op") == "bucket_reduce" and e.get("bucket") is not None]
    if br:
        br.sort(key=lambda e: e.get("seq", 0))
        first_cycle = [int(e["bucket"]) for e in br[:max(n_plan, 1)]]
        flight_check = {
            "checked": True,
            "n_entries": len(br),
            "issue_order_ascending": first_cycle ==
            sorted(first_cycle),
            "trace_order_matches_plan": plan_match,
        }

    return {
        "format": SUMMARY_FORMAT, "version": SUMMARY_VERSION,
        "workload": workload,
        "bucket_plan": dict(plan_meta) if plan_meta else None,
        "plan_match": plan_match,
        "bucket_map": bucket_map,
        "steps": {"n": n_steps, "wall_s": wall_s, "mean_s": mean_wall,
                  "p50_s": _percentile(wall_s, 0.50),
                  "p99_s": _percentile(wall_s, 0.99)},
        "phases": phases_out,
        "buckets": buckets_out,
        "overlap": {"comm_s_per_step": comm_mean,
                    "compute_s_per_step": comp_mean,
                    "overlapped_s_per_step": ovl_mean,
                    "overlap_frac": (ovl_mean / comm_mean)
                    if comm_mean > 0 else None,
                    "source": "trace"},
        "injected": {"events": n_inj, "kinds": inj_kinds},
        "flight_cross_check": flight_check,
        "n_device_events": sum(len(v) for v in lanes.values()),
        "n_lanes": len(lanes),
    }
