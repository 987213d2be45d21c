"""Find a serving cell's knee, once: the highest rate it sustains.

    python -m perfbench.sweep --workload <cell> --rates 1,2,3 --seconds 50 --seed 1

One process: the server is built once and the cell's traffic offered
at each rate in turn, every other parameter as the cell has it, its
warm-up too, for the length of the cell's own runs.  Each rate's line
says whether it was ``sustained`` (``sustained()`` below is the rule)
and, from what the server streamed, the rate it would saturate at
(``saturates_at``: tokens a second over the schedule's mean output; it
means something only where the slots were full all through the window,
as the line's ``info`` says: there the server streamed all it can).
A window holds some fifty requests, each rate its own arrangement of
them, so one rate's flag can err; the knee is the highest rate of the
grid that was sustained, and has to agree with ``saturates_at``.
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

from . import loadgen, run, spans as _spans


# a request that finds a slot waits a prefill and a tick or two for its
# first token, a tenth of a second; one that queues behind a full server
# waits for a whole answer to finish, seconds
STANDING_BACKLOG_MS = 1000.0


def sustained(first_half_ms: float, second_half_ms: float,
              waiting_at_close: int, median_ms: float) -> bool:
    """No backlog grows and none stands.  The mean time to first token
    of the window's second half stays under twice the first half's plus
    50 ms (under the knee the order of the lengths moves them by that
    much; over it the second half reads seconds), at most one request
    due in the window is still without its first token as it closes,
    and the median request did not queue (a backlog that the warm-up
    built and the window only carries grows in neither half)."""
    return second_half_ms < 2.0 * first_half_ms + 50.0 \
        and waiting_at_close <= 1 and median_ms < STANDING_BACKLOG_MS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
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
        drv.warm(args.seconds)
        res = drv.window(args.seconds)
        plan = [r for r in drv.plan if loadgen.in_window(r, args.seconds)]
        ttft = [1e3 * (r.token_s[0] - r.due_s) for r in plan if r.token_s]
        half = len(ttft) // 2
        first, second = (statistics.mean(ttft[:half]),
                         statistics.mean(ttft[half:]))
        waiting = sum(1 for r in plan
                      if not r.token_s or r.token_s[0] > args.seconds)
        mean_output = statistics.mean(r.max_new for r in drv.plan)
        print(json.dumps({
            "rate": rate,
            "sustained": sustained(first, second, waiting,
                                   res["counters"]["ttft_p50_ms"]),
            "attempted": res["attempted"], "failed": res["failed"],
            **res["metrics"],
            "ttft_p50_ms": res["counters"]["ttft_p50_ms"],
            "ttft_p90_ms": res["counters"]["ttft_p90_ms"],
            "ttft_mean_first_half_ms": first,
            "ttft_mean_second_half_ms": second,
            "no_first_token_at_close": waiting,
            "mean_output_tokens": mean_output,
            "saturates_at": res["metrics"]["serve_tokens_per_s"]
            / mean_output,
            "platform": jax.devices()[0].platform,
            "info": res["info"]}), flush=True)
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
