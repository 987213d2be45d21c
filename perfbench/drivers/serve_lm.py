"""Driver: generation serving through ``ModelServer``.

One process: ``GenerationRuntime`` + ``ModelServer.add_generator`` +
``submit_generation(..., on_token=...)`` — the entry behind HTTP
``:generate`` — through the continuous-batching engine, the paged
cache and the compiled prefill and decode programs (the call shapes of
``chip_smoke.py``'s serving leg, without the HTTP front end).  Load is
open loop at the cell's fixed rate (``perfbench/loadgen.py``).

Before the window opens, ``warm`` offers the same schedule for the
cell's ``warm_seconds`` and the window's requests follow at once, so
the timed and the traced window alike open on a server at its steady
occupancy.  That is neither set-up (``run.py`` stamps ``setup_s``
before it) nor measured (a trace starts after it).

After the window closes the driver waits for the first token of every
request already sent, cancels what is still decoding, and keeps a
sample of the requests the window FINISHED for the check: the
reference runs once over each prompt with its served tokens, and the
number compared is the widest gap by which a served token's logit lies
below the reference's best at its position.

While the window is open a clock reads, ten times a second, how many
cache blocks live sequences hold (``PagedKVCache.stats()``): what the
traffic fills of the pool that ``memory_peak_bytes`` pays for.

``perfbench.calibrate --faults 1`` plants the cell's ``faults`` one
after another under the same server (``FAULTS``: what the engine hands
a compiled decode step is altered on its way in) and reads what each
does to the number compared.  The benchmark's own runs plant nothing.
"""
from __future__ import annotations

import importlib
import random
import threading
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops, loadgen, program_trace, weights

MODEL = "bench_gen"
FIRST_TOKEN_WAIT_S = 60.0
KV_SAMPLE_S = 0.1


def _position_before(tokens, positions, tables):
    """Every rider decodes one position early: its new K and V land on
    the token before, which is lost, and its rotary angle is off by
    one against the prompt's."""
    return tokens, np.maximum(positions - 1, 0), tables


def _first_block_stale(tokens, positions, tables):
    """Every rider's first 128 tokens are read from block 0, the pool's
    scratch block, which holds whatever was written there last."""
    tables = tables.copy()
    tables[:, 0] = 0
    return tokens, positions, tables


def _tables_rolled(tokens, positions, tables):
    """Every rider reads and writes the block table of the rider in the
    slot before it."""
    return tokens, positions, np.roll(tables, 1, axis=0)


# what ``perfbench.calibrate --faults 1`` can plant in a decode step
FAULTS = {"decode_position_before": _position_before,
          "decode_first_block_stale": _first_block_stale,
          "decode_tables_rolled": _tables_rolled}


def _planted(step, alter):
    def call(params, tokens, positions, pages, tables):
        tokens, positions, tables = alter(tokens, positions, tables)
        return step(params, tokens, positions, pages, tables)
    return call


class Driver:
    def __init__(self, cell: Dict, config: Dict, seed: int, devices,
                 spans):
        self.cell, self.config, self.seed = cell, config, int(seed)
        self.devices, self.spans = devices, spans
        self.sample: List = []

    # -- set-up -------------------------------------------------------
    def setup(self) -> None:
        from mxnet_tpu import diagnostics, serving
        from mxnet_tpu.serving import reqtrace
        from mxnet_tpu.transformer import TransformerConfig

        diagnostics.reset_recompile_stats()
        reqtrace.reset()
        cfg, cell = self.config, self.cell
        self.ref = importlib.import_module(
            "perfbench.reference." + cfg["reference"])
        self.specs = self.ref.leaves(cfg)
        lm = TransformerConfig(
            vocab_size=cfg["vocab_size"],
            n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            d_ff=cfg["intermediate_size"], rope_base=cfg["rope_base"],
            dtype=cfg["dtype"], param_dtype=cfg["param_dtype"],
            eps=cfg["rms_norm_eps"])
        with jax.default_device(self.devices[0]):
            params = weights.make_all(self.seed, self.specs,
                                      cfg["param_dtype"])
            self.rt = serving.GenerationRuntime(
                MODEL, params, lm, slots=cell["slots"],
                block_tokens=cell["block_tokens"],
                max_prompt=cell["prompt"]["max"],
                max_context=cell["prompt"]["max"] + cell["output"]["max"],
                max_new=cell["output"]["max"], prefill_batch=1)
            del params
        # outside ``default_device``: it is part of jit's cache key, and
        # the engine's thread, which runs without it, would compile
        # every plan cell a second time under traffic
        self.srv = serving.ModelServer(
            queue_max=cell["queue_max"],
            default_deadline_ms=cell["deadline_ms"])
        self.srv.add_generator(self.rt)       # compiles every plan cell
        self.compiled = self._compiles()

    @staticmethod
    def _compiles() -> Dict[str, int]:
        from mxnet_tpu import diagnostics

        return {name: st["count"]
                for name, st in diagnostics.recompile_stats().items()
                if name.startswith("gen_")}

    def _submit(self, req) -> None:
        """Send one request; its tokens are stamped on the engine's
        thread with ``perf_counter()`` as it reads, and moved onto the
        window's clock once the window has closed."""
        from mxnet_tpu.serving.errors import Rejected

        def on_token(tok):
            if tok is not None:
                req.token_s.append(time.perf_counter())
                req.tokens.append(tok)

        try:
            req.handle = self.srv.submit_generation(
                MODEL, req.prompt, max_new=req.max_new,
                on_token=on_token, request_id="bench-%d" % req.index)
        except Rejected as e:
            req.outcome = "shed:%s" % e.reason

    # -- before the window: a server at its steady occupancy ----------
    def warm(self, seconds: float) -> None:
        """Offer the part of the schedule that is due before the window
        opens; return as it opens.  ``seconds`` is the length the one
        schedule is made for: the run's, so that a shorter (traced)
        window sees the beginning of the same traffic."""
        self.plan = loadgen.schedule(self.cell, self.seed, seconds,
                                     self.config["vocab_size"])
        self.opens = time.perf_counter() + float(
            self.cell.get("warm_seconds", 0.0))
        loadgen.offer(self.plan, self._submit, 0.0, self.opens, self.spans)

    # -- the measured window -----------------------------------------
    def window(self, seconds: float) -> Dict:
        from mxnet_tpu.serving.errors import ServeError

        plan, kv = self.plan, self.rt.kv
        block_bytes = self.cell["block_tokens"] * flops.kv_bytes_per_token(
            self.config, jnp.dtype(self.config["dtype"]).itemsize)
        held: List[float] = []
        closed = threading.Event()

        def sample():
            # a clock of its own, so that an idle server is read too
            while not closed.wait(KV_SAMPLE_S):
                held.append(kv.stats()["blocks_live"] * block_bytes)

        clock = threading.Thread(target=sample, name="bench-kv-clock")
        t0 = time.perf_counter()
        clock.start()
        try:
            loadgen.offer(plan, self._submit, seconds, t0, self.spans)
        finally:
            closed.set()
            clock.join()
        elapsed = time.perf_counter() - t0
        sent = [r for r in plan if r.handle is not None]
        with self.spans("bench.drain"):
            # a request sent just before the close still gets its first
            # token: late is late, not missing
            limit = time.perf_counter() + FIRST_TOKEN_WAIT_S
            for r in sent:
                while not r.token_s and not r.handle.done() \
                        and time.perf_counter() < limit:
                    time.sleep(0.002)
            for r in sent:
                if not r.handle.done():
                    r.handle.cancel()
            for r in sent:
                try:
                    r.handle.wait(30.0)
                except ServeError:
                    pass       # read from ``handle.error`` just below
        for r in sent:
            # every stream has ended: onto the window's clock
            r.token_s = [t - t0 for t in r.token_s]
            err = r.handle.error
            if err is None or type(err).__name__ == "Cancelled":
                r.outcome = "ok"
            else:
                r.outcome = "error:%s" % type(err).__name__
        compiled = self._compiles()
        if compiled != self.compiled:
            # a run that compiled under traffic measured the compiler
            raise RuntimeError("plan cells compiled under traffic: %s" % {
                k: (self.compiled.get(k), v) for k, v in compiled.items()
                if v != self.compiled.get(k)})
        numbers = loadgen.summarize(plan, seconds,
                                    seconds + FIRST_TOKEN_WAIT_S)
        gaps = [b - a for r in plan for a, b in zip(r.token_s, r.token_s[1:])
                if 0.0 <= a and b <= seconds]
        finished = [r for r in plan if r.outcome == "ok"
                    and len(r.tokens) == r.max_new
                    and 0.0 <= r.token_s[-1] <= seconds]
        self.sample = self._draw_sample(finished)
        self.counters = dict(
            self._count(plan, seconds, numbers, len(finished)),
            kv_live_bytes=held)
        return {
            "t_start": t0, "elapsed_s": elapsed,
            "attempted": numbers["attempted"], "failed": numbers["failed"],
            "metrics": {k: numbers[k] for k in
                        ("serve_tokens_per_s", "tpot_p95_ms")},
            "counters": self.counters,
            "info": "%d requests due (%d more in %g s of warm-up, the "
                    "window opened %.3f s after it), %d failed, %d tokens "
                    "and %d gaps in %.1f s (p50/p90/p95/p99 %s ms); ttft "
                    "p50/p90 %.3f/%.3f ms; generator late p90 %.2f ms; %d "
                    "finished in the window; %s; live sequences held "
                    "p50/max %d/%d bytes of cache (%d readings)" % (
                        numbers["attempted"],
                        sum(1 for r in plan if r.due_s < 0.0),
                        self.cell.get("warm_seconds", 0.0),
                        t0 - self.opens, numbers["failed"],
                        numbers["tokens_in_window"], numbers["token_gaps"],
                        seconds, "/".join(
                            "%.3f" % (1e3 * loadgen.percentile(gaps, q))
                            for q in (0.5, 0.9, 0.95, 0.99)) if gaps
                        else "-",
                        numbers["ttft_p50_ms"], numbers["ttft_p90_ms"],
                        loadgen.percentile(numbers["late_ms"], 0.9)
                        if numbers["late_ms"] else float("nan"),
                        self.counters["finished_in_window"],
                        self._occupancy(t0, seconds),
                        loadgen.percentile(held, 0.5) if held else 0,
                        max(held, default=0), len(held)),
        }

    @staticmethod
    def _occupancy(t0: float, seconds: float) -> str:
        """The decode slots' occupancy in the window's first fifth
        beside the rest's: the proof that the window opened on a warmed
        server."""
        from mxnet_tpu.profiler import spans_between

        spans = spans_between(t0, t0 + seconds)
        fifth = t0 + seconds / 5.0
        first, rest = (program_trace.slot_occupancy_pct(spans, lo, hi)
                       for lo, hi in ((t0, fifth), (fifth, t0 + seconds)))
        return "slots occupied %.1f %% in the first fifth, %.1f %% in " \
            "the rest" % (float("nan") if first is None else first,
                          float("nan") if rest is None else rest)

    def _draw_sample(self, done) -> List:
        """Of the requests the window finished, a sample drawn from the
        seed, the longest among them."""
        if not done:
            return []
        longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
        rest = [r for r in done if r is not longest]
        random.Random(self.seed).shuffle(rest)
        picked = [longest] + rest[:self.cell["check_requests"] - 1]
        return [(np.asarray(r.prompt), np.asarray(r.tokens, np.int32))
                for r in picked]

    def _count(self, plan, seconds, numbers, finished: int) -> Dict:
        """Work done inside the window, from the tokens served."""
        from mxnet_tpu.serving import reqtrace

        cfg = self.config
        prefilled = [len(r.prompt) for r in plan
                     if r.token_s and 0.0 <= r.token_s[0] <= seconds]
        # a decode tick emits token i (i >= 1) of a request after
        # reading the prompt and the i tokens before it
        decode_reads = [len(r.prompt) + i for r in plan
                        for i, t in enumerate(r.token_s)
                        if i >= 1 and 0.0 <= t <= seconds]
        due = {"bench-%d" % r.index for r in plan
               if loadgen.in_window(r, seconds)}
        queue_ms = [1e3 * rec["phases"]["queue"]
                    for rec in reqtrace.snapshot()["recent"]
                    if rec.get("id") in due
                    and "queue" in rec.get("phases", {})]
        prefill_flops = sum(flops.prefill_flops(cfg, n) for n in prefilled)
        decode_flops = sum(flops.transformer_forward_flops(cfg, 1, n)
                           for n in decode_reads)
        return {
            "finished_in_window": finished,
            "prefill_requests": len(prefilled),
            "prefill_tokens": sum(prefilled),
            "prefill_flops_each": [flops.prefill_flops(cfg, n)
                                   for n in prefilled],
            "prefill_flops": prefill_flops,
            "decode_tokens": len(decode_reads),
            "decode_kv_token_reads": sum(decode_reads),
            "decode_flops": decode_flops,
            "flops_done": prefill_flops + decode_flops,
            "queue_ms": queue_ms,
            "late_ms": numbers["late_ms"],
            "ttft_p90_ms": numbers["ttft_p90_ms"],
            "ttft_p50_ms": numbers["ttft_p50_ms"],
        }

    # -- planted faults (``perfbench.calibrate --faults 1`` only) ------
    def fault_windows(self, seconds: float) -> None:
        """On the server the program's window has just left: each of
        the cell's ``faults`` planted in turn under every compiled
        decode step, the cell's warm-up and a window of ``seconds``,
        and the window's sample kept for ``calibration``."""
        sound, self.faulty = dict(self.rt._decode), {}
        served = self.sample
        for name in self.cell.get("faults", ()):
            for key, step in sound.items():
                self.rt._decode[key] = _planted(step, FAULTS[name])
            self.warm(seconds)
            print("%s: %s" % (name, self.window(seconds)["info"]),
                  flush=True)
            self.faulty[name] = self.sample
        self.sample = served
        self.rt._decode.update(sound)

    def release(self) -> None:
        report = self.srv.drain(timeout_s=30)
        if not report["drained"]:
            # the engine's thread would keep the weights and the cache
            raise RuntimeError("the server did not drain: %s" % report)
        self.srv = self.rt = None
        import gc

        gc.collect()

    # -- the check ----------------------------------------------------
    def check(self) -> Dict:
        value = self.token_gaps()["served"]
        return {"token_gap": {"value": value,
                              "limit": self.cell["limits"]["token_gap"]}}

    def calibration(self, control: Dict, faults: bool, quantisers: Dict,
                    rebuilt):
        """``(what, numbers, extra)`` for ``perfbench.calibrate``."""
        def extra(sample):
            served = [t for _, tokens in sample for t in tokens]
            return {"requests": len(sample), "tokens": len(served),
                    "distinct_tokens": len(set(served)),
                    "repeats_of_the_token_before": sum(
                        1 for _, tokens in sample
                        for a, b in zip(tokens, tokens[1:]) if a == b)}

        gaps = self.token_gaps(quantisers.get(control.get("reference")))
        yield "program", {"token_gap": gaps["served"]}, extra(self.sample)
        if "reference" in control:
            yield "control", {"token_gap": gaps["control"]}, \
                extra(self.sample)
        if faults:
            sound = self.sample
            for name, sample in getattr(self, "faulty", {}).items():
                self.sample = sample
                yield name, {"token_gap": self.token_gaps()["served"]}, \
                    extra(sample)
            self.sample = sound

    def token_gaps(self, quantise=None) -> Dict[str, float]:
        """``served``: the widest gap, over the sample's served tokens,
        between the reference's best logit at the token's position and
        the served token's.  With ``quantise`` also ``control``: the
        same for the token that the reference computed with that
        quantiser puts first at each position."""
        if not self.sample:
            return {"served": float("nan"), "control": float("nan")}
        cfg = self.config
        context = self.cell["prompt"]["max"] + self.cell["output"]["max"]
        p = weights.make_all(self.seed, self.specs, "float32")

        def gaps(p, ids, first, count, served):
            logits = self.ref.forward(p, ids, cfg)
            at = first + jnp.arange(served.shape[0])
            rows = logits[at]
            live = jnp.arange(served.shape[0]) < count
            best = jnp.max(rows, axis=-1)

            def gap(tokens):
                g = best - jnp.take_along_axis(rows, tokens[:, None],
                                               1)[:, 0]
                return jnp.max(jnp.where(live, g, 0.0))

            out = {"served": gap(served)}
            if quantise is not None:
                low = self.ref.forward(p, ids, cfg, quantise)[at]
                out["control"] = gap(jnp.argmax(low, axis=-1)
                                     .astype(jnp.int32))
            return out

        run = jax.jit(gaps)
        worst = {"served": 0.0, "control": 0.0}
        n_out = self.cell["output"]["max"]
        with jax.default_matmul_precision("highest"):
            for prompt, tokens in self.sample:
                ids = np.zeros((context,), np.int32)
                ids[:len(prompt)] = prompt
                ids[len(prompt):len(prompt) + len(tokens)] = tokens
                served = np.zeros((n_out,), np.int32)
                served[:len(tokens)] = tokens
                got = run(p, ids, len(prompt) - 1, len(tokens), served)
                for k, v in got.items():
                    worst[k] = max(worst[k], float(v))
        del p
        return worst
