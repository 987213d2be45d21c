"""Find a serving cell's knee, once: the highest rate it sustains.

    python -m perfbench.sweep --workload <cell> --rates 1,2,3 --seconds 20 --seed 1

One process: the server is built once and the cell's traffic offered
at each rate in turn, every other parameter as the cell has it.  A rate
is sustained where the time to first token of the window's second half
is no worse than its first half's and few requests wait at the close.
The cell's fixed rate (four fifths of the knee) is then written into
its file by hand; the benchmark's own runs never search.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys

from . import run, spans as _spans


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    manifest = run._load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config = run.load_cell(manifest, args.workload, run.HERE)
    import jax
    from mxnet_tpu import compile_cache

    compile_cache.enable()
    module = importlib.import_module("perfbench.drivers." + config["driver"])
    drv = module.Driver(cell, config, args.seed, jax.devices()[:1],
                        _spans.Spans())
    drv.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        drv.cell = dict(cell, rate=rate)
        res = drv.window(args.seconds)
        plan = [r for r in drv.plan if r.due_s < args.seconds]
        ttft = [(r.token_s[0] - r.due_s) for r in plan if r.token_s]
        half = len(ttft) // 2
        print(json.dumps({
            "rate": rate, "attempted": res["attempted"],
            "failed": res["failed"], **res["metrics"],
            "ttft_p90_ms": res["counters"]["ttft_p90_ms"],
            "ttft_mean_first_half_ms": 1e3 * statistics.mean(ttft[:half]),
            "ttft_mean_second_half_ms": 1e3 * statistics.mean(ttft[half:]),
            "no_first_token_at_close": sum(
                1 for r in plan if not r.token_s
                or r.token_s[0] > args.seconds),
            "platform": jax.devices()[0].platform}), flush=True)
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
