"""Readings for the limits of a cell's comparison.

    python -m perfbench.calibrate --workload <cell> --seeds 1,2,3 \
        [--control 1] [--faults 1] [--out chiprun_out/<file>.jsonl]

For each seed, in one process: what the program's timed path produced
against the reference (the lower readings); with ``--control`` the
control the cell names against the same reference (``control.program``:
the program's own lower-precision path, given as configuration keys to
override; ``control.reference``: the reference with that quantiser on
its operands); with ``--faults`` a training cell's reference with half
of each batch left out, and with its state left unchanged, or a serving
cell's own ``faults`` planted under the server one after another
(``drivers/serve_lm.py``).  One JSON
line a reading; the file named by ``--out`` also gets both sides' norms
leaf by leaf.  The benchmark's own runs never run this; ``PERF.md``
records what it read on the chip.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

from . import lowprec, run, spans as _spans

QUANTISERS = {"fp8": lowprec.fake_fp8}


def main(argv=None, *, manifest_path=None, data_root=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    manifest = run._load_json(manifest_path
                              or os.path.join(run.ROOT, "BENCHMARK.json"))
    data_root = data_root or run.HERE
    cell, config = run.load_cell(manifest, args.workload, data_root)
    import jax
    from mxnet_tpu import compile_cache

    compile_cache.enable()
    devices = jax.devices()[:cell["chips"]]
    module = importlib.import_module("perfbench.drivers."
                                     + config["driver"])
    out = open(args.out, "a") if args.out else None

    def emit(seed, what, numbers, extra=None):
        line = dict({"cell": cell["name"], "seed": seed, "what": what,
                     "platform": devices[0].platform}, **numbers,
                    **(extra or {}))
        # both sides leaf by leaf go to the file alone
        print(json.dumps({k: v for k, v in line.items()
                          if k != "readings"}), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    def built(seed, cfg):
        """A driver set up (and, where the cell says for how long, run
        through a short window at the cell's own load), then released."""
        t0 = time.perf_counter()
        drv = module.Driver(cell, cfg, seed, devices, _spans.Spans())
        drv.setup()
        if "calibrate_seconds" in cell:
            if hasattr(drv, "warm"):
                drv.warm(cell["calibrate_seconds"])
            print(drv.window(cell["calibrate_seconds"])["info"], flush=True)
            if args.faults and hasattr(drv, "fault_windows"):
                drv.fault_windows(cell["calibrate_seconds"])
        drv.release()
        return drv, time.perf_counter() - t0

    for seed in (int(s) for s in args.seeds.split(",")):
        drv, t_prog = built(seed, config)
        t0 = time.perf_counter()
        for what, numbers, extra in drv.calibration(
                cell.get("control", {}) if args.control else {},
                bool(args.faults), QUANTISERS,
                lambda overrides: built(seed, dict(config, **overrides))[0]):
            emit(seed, what, numbers,
                 dict(extra, program_s=t_prog,
                      check_s=time.perf_counter() - t0))
            t0 = time.perf_counter()
        del drv
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
