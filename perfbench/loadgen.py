"""Open-loop load for a generation server: one general generator.

A traffic mix is data (the cell's file): an arrival ``rate`` a second,
and for prompts and outputs a lognormal ``median`` and ``sigma`` with a
clip.  The generator turns it into a schedule that is the same work for
every seed: the inter-arrival gaps are the exact quantiles of the
exponential distribution and the lengths the exact quantiles of the
clipped lognormal, each shuffled once in an order that never changes.
``--seed`` draws the prompts' token ids (and the weights), not the
order: with some 70 requests a window, which request meets which moved
tokens/s by 6 % and the 95th-percentile token gap by 25 % from seed to
seed, where two runs of one order agree within 0.7 % and 2 % (my chip
runs, PR 25).

A cell may ask for ``warm_seconds`` of the same schedule before its
window opens, so that the window opens on a server at its steady
occupancy (PR 25's review: a window from an empty server measures the
ramp).  The schedule is then one, ``warm_seconds + seconds`` long, with
times counted from the window's opening: a warm-up request is due at a
negative time.

It began as a corrected copy of ``run_generation_load`` (in
``serving/loadgen.py`` until PR 31 deleted it): a request's clock starts
when it was DUE, not when it was sent; how late the generator ran is
reported; lengths have a tail; rates are taken over the window.
"""
from __future__ import annotations

import math
import random
import time
from statistics import NormalDist
from typing import Callable, Dict, List, Optional

import numpy as np


# every mix's sizes and gaps are shuffled in this one order
SCHEDULE_ORDER = 203


class Planned:
    """One request of the schedule and, once sent, what became of it."""

    __slots__ = ("index", "due_s", "prompt", "max_new", "sent_s",
                 "token_s", "tokens", "outcome", "handle")

    def __init__(self, index: int, due_s: float, prompt, max_new: int):
        self.index, self.due_s = index, due_s
        self.prompt, self.max_new = prompt, max_new
        self.sent_s: Optional[float] = None
        self.token_s: List[float] = []      # seconds from window start
        self.tokens: List[int] = []
        self.outcome: Optional[str] = None  # ok | shed:<why> | error
        self.handle = None


def _lognormal_lengths(n: int, spec: Dict) -> List[int]:
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    inv = NormalDist().inv_cdf
    return [int(min(max(round(math.exp(mu + sigma * inv((i + 0.5) / n))),
                        spec["min"]), spec["max"])) for i in range(n)]


def _exponential_gaps(n: int, rate: float) -> List[float]:
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def schedule(traffic: Dict, seed: int, seconds: float, vocab: int
             ) -> List[Planned]:
    """``round(rate * (warm_seconds + seconds))`` requests, due from
    ``-warm_seconds`` to ``seconds``: time 0 is the window's opening."""
    warm = float(traffic.get("warm_seconds", 0.0))
    n = max(int(round(traffic["rate"] * (warm + seconds))), 1)
    rng = random.Random(SCHEDULE_ORDER)
    gaps = _exponential_gaps(n, traffic["rate"])
    prompts = _lognormal_lengths(n, traffic["prompt"])
    outputs = _lognormal_lengths(n, traffic["output"])
    for values in (gaps, prompts, outputs):
        rng.shuffle(values)
    ids = np.random.default_rng(int(seed)).integers(
        1, vocab, size=sum(prompts), dtype=np.int32)
    plan, due, at = [], -warm, 0
    for i in range(n):
        due += gaps[i]
        plan.append(Planned(i, due, ids[at:at + prompts[i]], outputs[i]))
        at += prompts[i]
    return plan


def offer(plan: List[Planned], submit: Callable[[Planned], None],
          seconds: float, t0: float, spans) -> None:
    """Send every request not yet sent that is due before ``seconds``
    (from ``t0``, the window's opening) at its time, whatever became of
    the earlier ones; returns at ``t0 + seconds``.  With ``seconds`` 0
    that is the warm-up, which returns as the window opens.  ``submit``
    sets ``handle`` or ``outcome``."""
    for req in plan:
        if req.due_s >= seconds:
            break
        if req.sent_s is not None:
            continue
        with spans("bench.wait"):
            delay = t0 + req.due_s - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        with spans("bench.submit"):
            req.sent_s = time.perf_counter() - t0
            submit(req)
    with spans("bench.wait"):
        delay = t0 + seconds - time.perf_counter()
        if delay > 0:
            time.sleep(delay)


def percentile(values: List[float], q: float) -> float:
    """The smallest value with at least ``q`` of the sample at or below
    it."""
    ordered = sorted(values)
    return ordered[min(int(math.ceil(q * len(ordered))) - 1,
                       len(ordered) - 1)]


def in_window(r: Planned, seconds: float) -> bool:
    return 0.0 <= r.due_s < seconds


def summarize(plan: List[Planned], seconds: float,
              gave_up_s: float) -> Dict:
    """The window's numbers.  Tokens and the gaps between them are
    every request's that were streamed inside the window, a warm-up
    request's too: they are the steady state's, and stand for what the
    window's last requests stream after it closes.  A gap counts where
    both of its tokens lie inside.  Time to first token, ``attempted``
    and how late the generator ran are of the requests DUE in the
    window.  One that got no first token (shed or failed) counts as
    missing: its time to first token is taken as the whole wait until
    ``gave_up_s`` (seconds from the window's opening), when the harness
    stopped waiting for it.  A warm-up request that was shed or failed
    is a failed operation like any other, and is counted as attempted
    with it."""
    due = [r for r in plan if in_window(r, seconds)]
    ttft = [(r.token_s[0] if r.token_s else gave_up_s) - r.due_s
            for r in due]
    gaps, tokens_in = [], 0
    for r in plan:
        inside = [t for t in r.token_s if 0.0 <= t <= seconds]
        tokens_in += len(inside)
        gaps += [b - a for a, b in zip(inside, inside[1:])]
    late = [r.sent_s - r.due_s for r in due if r.sent_s is not None]
    bad = [r for r in plan if r.outcome not in (None, "ok")]
    return {
        "attempted": len(due) + sum(1 for r in bad if r.due_s < 0.0),
        "failed": sum(1 for r in bad if r.due_s < seconds),
        "tokens_in_window": tokens_in, "token_gaps": len(gaps),
        "serve_tokens_per_s": tokens_in / seconds,
        "ttft_p90_ms": 1e3 * percentile(ttft, 0.90),
        "tpot_p95_ms": 1e3 * (percentile(gaps, 0.95) if gaps
                              else gave_up_s),
        "ttft_p50_ms": 1e3 * percentile(ttft, 0.50),
        "late_ms": [1e3 * v for v in late],
    }
