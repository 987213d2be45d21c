"""The one command: run one cell of ``BENCHMARK.json`` once.

    python -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Load, warm up (``setup_s`` ends here), bring a server to its steady
occupancy where the driver has a ``warm``, measure for ``--seconds``,
check what the timed path produced against the plain reference, print
one JSON line
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` when traced, ``compared`` last), exit 0.  Without a TPU,
or with fewer chips than the cell asks for, it names what it found and
exits 2 with no result line: there is no CPU fallback.

Nothing here names a cell, a configuration or a metric.  A cell is
``workloads/<cell>.json`` and its configuration's file, which names its
driver (``drivers/<driver>.py``); a per-layer metric is
``metrics/<metric>.py`` (or, for ``<quantity>.<split>``, the
quantity's file) with one function ``read(ctx)`` that returns a number,
or None where it finds nothing to read.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse                      # noqa: E402
import importlib                     # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
from typing import Dict, Optional    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reader(name: str):
    from .validate import reader_path

    path = reader_path(name)
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_cell(manifest: Dict, name: str, data_root: str):
    """``(cell, config)``: the cell's file (with its name and chips from
    the manifest) and its configuration's file; None where the manifest
    has no such cell."""
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        return None
    cell = dict(_load_json(os.path.join(data_root, "workloads",
                                        name + ".json")),
                name=name, chips=entry["chips"])
    config_file = next(c["file"] for c in manifest["configs"]
                       if c["name"] == entry["config"])
    return cell, _load_json(os.path.join(ROOT, config_file))


def _reports(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    # the Python tracer records every call of the interpreter: it slows
    # the host several times over and is not read
    opts.python_tracer_level = 0
    return opts


def main(argv=None, *, manifest_path: Optional[str] = None,
         data_root: Optional[str] = None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from . import spans as _spans
    from . import trace_reduce, validate

    manifest_path = manifest_path or os.path.join(ROOT, "BENCHMARK.json")
    data_root = data_root or HERE
    manifest = _load_json(manifest_path)
    faults = validate.check(manifest, ROOT, data_root)
    if faults:
        for line in faults:
            print("BENCHMARK.json: " + line, file=sys.stderr)
        return 2
    found = load_cell(manifest, args.workload, data_root)
    if found is None:
        print("no workload %r; there are %s" % (
            args.workload, sorted(w["name"] for w in manifest["workloads"])),
            file=sys.stderr)
        return 2
    cell, config = found

    # the program's own dumps (flight recorder, request traces) go
    # inside the checkout, never to the working directory or /tmp
    os.environ.setdefault("MXNET_DUMP_DIR",
                          os.path.join(ROOT, ".perfbench_out", "dumps"))
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peaks = _load_json(os.path.join(HERE, "peaks.json")).get(device["kind"])
    if require_chip:
        if device["platform"] != "tpu" or len(devices) < cell["chips"]:
            print("perfbench: cell %s needs %d TPU chip(s) and found "
                  "platform %r (%s x%d); there is no CPU fallback"
                  % (cell["name"], cell["chips"], device["platform"],
                     device["kind"], len(devices)), file=sys.stderr)
            return 2
        if peaks is None:
            print("perfbench: no peaks for device kind %r in peaks.json"
                  % device["kind"], file=sys.stderr)
            return 2
    from mxnet_tpu import compile_cache

    compile_cache.enable()

    spans = _spans.Spans()
    driver = importlib.import_module(
        "perfbench.drivers." + config["driver"]).Driver(
        cell, config, args.seed, devices[:cell["chips"]], spans)
    driver.setup()
    setup_s = time.perf_counter() - T_PROCESS

    seconds = args.seconds
    trace_dir = os.path.join(ROOT, ".perfbench_out", "trace")
    if args.trace:
        # a trace of the whole window would be large and is not needed:
        # the cell says how long a traced window has to be
        seconds = min(seconds, float(cell["trace_seconds"]))
    # a driver whose window has to open on a system in its steady state
    # (a server at its occupancy) brings it there now: after set-up,
    # before the window and before any trace.  It plans for the whole
    # run, so that a traced window is the timed window's beginning
    if hasattr(driver, "warm"):
        driver.warm(args.seconds)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans.annotate = jax.profiler.TraceAnnotation
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=_trace_options())
    spans.clear()
    with spans(trace_reduce.WINDOW):
        result = driver.window(seconds)
    trace = None
    if args.trace:
        jax.profiler.stop_trace()
        spans.annotate = None
        trace = _read_trace(trace_dir, trace_reduce)
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(result["info"], flush=True)

    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    device["memory_peak_bytes"] = max(
        int(s.get("peak_bytes_in_use", 0)) for s in stats)
    driver.release()
    t_check = time.perf_counter()
    compared = driver.check()
    print("check against the reference took %.1f s"
          % (time.perf_counter() - t_check), flush=True)
    correct = all(c["value"] == c["value"] and c["value"] <= c["limit"]
                  for c in compared.values())

    cell_name = cell["name"]
    metrics: Dict[str, Dict] = {}
    line: Dict = {"correct": bool(correct),
                  "attempted": int(result["attempted"]),
                  "failed": int(result["failed"]), "metrics": metrics,
                  "device": device}
    if not args.trace:
        values = dict(result["metrics"], setup_s=setup_s)
        for m in manifest["end_to_end"]:
            if _reports(m, cell_name):
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    else:
        busy = trace_reduce.busy_seconds(trace)
        if busy is not None:
            device.update(busy)
        ctx = {"trace": trace, "spans": spans, "cell": cell,
               "config": config, "peaks": peaks, "chips": cell["chips"],
               "counters": result["counters"], "busy": busy,
               "window_s": result["elapsed_s"]}
        for m in manifest["per_layer"]:
            if _reports(m, cell_name):
                value = _reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.idle_gaps(trace)}
    line["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print("compared %s = %.6g (limit %.6g)%s"
              % (name, c["value"], c["limit"],
                 "" if c["value"] <= c["limit"] else "  FAILS"),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line, allow_nan=False), flush=True)
    return 0


def _read_trace(trace_dir: str, trace_reduce) -> Dict:
    import glob

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError("expected one xplane file under %s, found %s"
                           % (trace_dir, files))
    return trace_reduce.load_xplane(files[0])


if __name__ == "__main__":
    sys.exit(main())
