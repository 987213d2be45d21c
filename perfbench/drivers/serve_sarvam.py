"""Driver: one chip's share of sarvam-105b behind ``ModelServer``.

``serve_lm.Driver`` with the four methods of another block: the
program's configuration (latent attention without a query bottleneck,
one dense SwiGLU layer then expert layers of which this chip holds a
share, an untied head), a cache block of latent rows, the work counted
in ``perfbench/flops_sarvam.py``, and a reference
(``perfbench/reference/sarvam.py``) that holds one layer's float32
weights at a time and attends by blocks of query rows.

Beside the four it hands the program's routing counters to the count:
what the expert layers routed is added up on the device by every
compiled step and read here twice, as the window opens (``warm``) and
once it has closed and drained (``_work``), never inside it
(``GenerationRuntime.routing_counters``).  And it holds a second number
of the served tokens' gaps (``token_gaps``): the 90th percentile over
the checked positions beside the worst of them.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.transformer import model as _model

from .. import flops_sarvam, weights
from . import serve_lm

if not hasattr(_model, "cache_rows"):
    # a program from before the latent pool: its cache is sized from
    # heads x head width and its generation forwards raise for this block
    raise ImportError("this program's generation forwards do not spell a "
                      "latent block (transformer.model.cache_rows)")

# of the checked positions' gaps, the share under ``token_gap_p90``
GAP_QUANTILE = 90.0
# the reference runs over a sequence's real length rounded up to this
LENGTH_STEP = 1024


class Driver(serve_lm.Driver):
    routed = None               # the window's routing counters
    disagree = None             # routing flips, while the check counts

    # -- the four -----------------------------------------------------
    def _lm_config(self):
        from mxnet_tpu.transformer import RopeYarn, TransformerConfig

        c, sc = self.config, self.config["rope_scaling"]
        dense = c["first_k_dense_replace"]
        return TransformerConfig(
            vocab_size=c["vocab_size"], n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            d_ff=c["intermediate_size"], rope_base=float(c["rope_theta"]),
            dtype=c["dtype"], param_dtype=c["param_dtype"],
            eps=c["rms_norm_eps"], attn_kind="latent", q_lora_rank=0,
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"],
            rope_yarn=RopeYarn(
                float(sc["factor"]), sc["original_max_position_embeddings"],
                float(sc["beta_fast"]), float(sc["beta_slow"]),
                float(sc["mscale"]), float(sc["mscale_all_dim"])),
            ffn_act="swiglu", tied_head=c["tie_word_embeddings"],
            layer_kinds=("dense_ffn",) * dense
            + ("experts",) * (c["num_hidden_layers"] - dense),
            n_experts=c["router_width"],
            experts_per_token=c["num_experts_per_tok"],
            n_shared_experts=c["num_shared_experts"],
            expert_ff=c["moe_intermediate_size"],
            held_experts=tuple(c["held_experts"]),
            routed_scaling=c["routed_scaling_factor"])

    def _block_bytes(self) -> float:
        return self.cell["block_tokens"] * flops_sarvam.cache_bytes_per_token(
            self.config, jnp.dtype(self.config["dtype"]).itemsize)

    def _work(self, prefilled: List[int], decode_reads: List[int]) -> Dict:
        """The count of ``flops_sarvam.work`` over what the program
        says it routed since ``warm`` read last.  The window has
        drained by now: beside the window's own ticks the counters
        hold those of the drain, the ticks until every request sent
        has its first token and is cancelled, a handful among some two
        thousand."""
        self.routed = self.rt.routing_counters()
        if self.routed is None:
            raise RuntimeError("the program kept no routing counters: "
                               "the window's work cannot be counted")
        return flops_sarvam.work(
            self.config, prefilled, decode_reads, self.routed,
            jnp.dtype(self.config["dtype"]).itemsize)

    def _reference_rows(self, ids, at, quantise=None):
        """Rows ``at`` of the reference's logits over ``ids``: a layer's
        float32 weights at a time, made again on every call (the cut's
        are 18 GB), over the sequence's real length rounded up to
        ``LENGTH_STEP`` (the positions behind it are padding that no
        real row attends to; its rows are not compared)."""
        cfg, ref = self.config, self.ref
        if self.kept is None:
            self.kept = {}
            _say_what_the_reference_finds(self.devices[0])

        def leaves(pre, names):
            made = weights.make_all(self.seed, self.specs, "float32",
                                    only=set(names))
            return {k[len(pre):]: v for k, v in made.items()}

        def compiled(what, fn):
            # a layer's leaves go in under their short names: the four
            # expert layers are one program
            if (what, quantise) not in self.kept:
                self.kept[what, quantise] = jax.jit(fn)
            return self.kept[what, quantise]

        real = max((len(p) + len(t) for p, t in self.sample
                    if len(p) == at[0] + 1
                    and np.array_equal(ids[:len(p)], p)), default=len(ids))
        n = min(-(-real // LENGTH_STEP) * LENGTH_STEP, len(ids))
        h = leaves("", ["embed"])["embed"][jnp.asarray(ids[:n])]
        for i, kind in enumerate(ref.layer_kinds(cfg)):
            pre = "blk%d." % i
            p = leaves(pre, (name for name, _, _ in self.specs
                             if name.startswith(pre)))
            h, routed = compiled(kind, lambda p, h, kind=kind: ref.layer(
                p, "", kind, h, cfg, quantise))(p, h)
            if routed is not None and quantise is None \
                    and self.disagree is not None:
                moved = _flips(p["router"], p["router_bias"], real,
                               **routed)
                self.disagree[0] += int(moved)
                self.disagree[1] += real * routed["choice"].shape[1]
        rows = h[jnp.minimum(jnp.asarray(at), n - 1)]
        return compiled("head", lambda p, rows: ref.head(
            p, rows, cfg, quantise))(leaves("", ["final_norm", "head"]),
                                     rows)

    def token_gaps(self, quantise=None) -> Dict[str, float]:
        """``serve_lm``'s ``served`` and ``control``, the WORST checked
        position's gap, and beside each its ``_p90``: the gap that nine
        checked positions in ten lie under.  One routing choice that
        flips where two scores lie within bfloat16 of each other moves
        a position's logits by about their spread, so the worst of
        30,000 positions reads what a flip does and guards against a
        blow-up; a flip reaches few positions, so the percentile reads
        what is lost at EVERY position: precision, a wrong angle, a
        wrong history."""
        nan = float("nan")
        if not self.sample:
            return {"served": nan, "control": nan, "served_p90": nan,
                    "control_p90": nan}
        context = self.cell["prompt"]["max"] + self.cell["output"]["max"]
        n_out = self.cell["output"]["max"]

        @jax.jit
        def gaps(rows, low, served):
            best = jnp.max(rows, axis=-1)

            def gap(tokens):
                return best - jnp.take_along_axis(rows, tokens[:, None],
                                                  1)[:, 0]

            out = {"served": gap(served)}
            if low is not None:
                out["control"] = gap(jnp.argmax(low, axis=-1)
                                     .astype(jnp.int32))
            return out

        every: Dict[str, List] = {"served": [], "control": []}
        try:
            with jax.default_matmul_precision("highest"):
                for prompt, tokens in self.sample:
                    ids = np.zeros((context,), np.int32)
                    ids[:len(prompt)] = prompt
                    ids[len(prompt):len(prompt) + len(tokens)] = tokens
                    served = np.zeros((n_out,), np.int32)
                    served[:len(tokens)] = tokens
                    at = len(prompt) - 1 + np.arange(n_out, dtype=np.int32)
                    rows = self._reference_rows(ids, at)
                    low = None if quantise is None else \
                        self._reference_rows(ids, at, quantise)
                    for k, g in gaps(rows, low, served).items():
                        every[k].append(np.asarray(g)[:len(tokens)])
        finally:
            self.kept = None
        out = {}
        for k, per in every.items():
            g = np.concatenate(per) if per else np.zeros((1,))
            out[k] = float(g.max())
            out[k + "_p90"] = float(np.percentile(g, GAP_QUANTILE))
            if per:
                print("info: %s tokens' gaps over %d positions: mean %.5f, "
                      "p50/p75/p90/p95/p99/p99.9 %s, worst %.5f" % (
                          k, g.size, g.mean(), "/".join(
                              "%.5f" % np.percentile(g, q) for q in
                              (50, 75, 90, 95, 99, 99.9)), g.max()),
                      flush=True)
        return out

    def _compared(self, gaps: Dict, which: str) -> Dict[str, float]:
        return {"token_gap": gaps[which],
                "token_gap_p90": gaps[which + "_p90"]}

    def check(self) -> Dict:
        """Both numbers of ``token_gaps`` under their limits, and a
        line of ``info`` on the routing flips that the limit of
        ``token_gap`` leaves room for: how many of the reference's own
        assignments over the checked sequences move when its router
        reads its input rounded to bfloat16, as the program's does."""
        self.disagree = [0, 0]
        got = self._compared(self.token_gaps(), "served")
        (moved, of), self.disagree = self.disagree, None
        print("info: route_disagree_pct %.4f (%d of %d assignments of the "
              "checked sequences move when the reference's router reads "
              "its input rounded to bfloat16)"
              % (100.0 * moved / max(of, 1), moved, of), flush=True)
        return {k: {"value": v, "limit": self.cell["limits"][k]}
                for k, v in got.items()}

    def calibration(self, control: Dict, faults: bool, quantisers: Dict,
                    rebuilt):
        """``serve_lm``'s readings, each with both numbers."""
        def extra(sample):
            served = [t for _, tokens in sample for t in tokens]
            return {"requests": len(sample), "tokens": len(served),
                    "distinct_tokens": len(set(served)),
                    "repeats_of_the_token_before": sum(
                        1 for _, tokens in sample
                        for a, b in zip(tokens, tokens[1:]) if a == b)}

        gaps = self.token_gaps(quantisers.get(control.get("reference")))
        yield "program", self._compared(gaps, "served"), extra(self.sample)
        if "reference" in control:
            yield "control", self._compared(gaps, "control"), \
                extra(self.sample)
        if faults:
            sound = self.sample
            for name, sample in getattr(self, "faulty", {}).items():
                self.sample = sample
                yield name, self._compared(self.token_gaps(), "served"), \
                    extra(sample)
            self.sample = sound

    def release(self) -> None:
        """``serve_lm``'s, and the weights and the pools deleted by hand:
        the reference needs their 11 GB, and the runtime and its engine
        name each other, so dropping the last name of them frees nothing
        until a collection of cycles finds them (one that runs while the
        engine's thread is leaving its last tick does not)."""
        rt = self.rt
        super().release()
        for a in list(rt._params.values()) + list(rt.kv.pages.values()):
            if hasattr(a, "delete") and not a.is_deleted():
                a.delete()

    # -- the routing counters: read as the window opens, and in _work ----
    def warm(self, seconds: float) -> None:
        super().warm(seconds)
        self.rt.routing_counters()      # what the warm-up routed: dropped

    def window(self, seconds: float) -> Dict:
        result = super().window(seconds)
        r = self.routed
        if r["dropped"]:
            # nothing may be dropped: a window that did has failed
            result["failed"] += 1
        held = r["counts"][:, list(self.config["held_experts"])]
        result["info"] += (
            "; routed %d assignments, %d here (%d by decoded tokens), "
            "%d dropped, held experts' load max over mean %.3f, %.2f "
            "of %d held experts reached a decode tick a layer over %d "
            "ticks; by layer here %s" % (
                r["assignments_total"], r["assignments_here"],
                r["decode_assignments_here"], r["dropped"],
                r["load_max_over_mean"], r["experts_reached"],
                held.shape[1], r["decode_ticks"],
                held.sum(axis=1).tolist()))
        return result


def _say_what_the_reference_finds(device) -> None:
    """The reference needs the memory that ``release`` gave up: say how
    much the device still holds as it starts."""
    used = (device.memory_stats() or {}).get("bytes_in_use", 0)
    print("info: %d bytes in use on the device as the reference starts"
          % used, flush=True)


@jax.jit
def _flips(router, bias, real, choice, router_input):
    """Assignments of the first ``real`` tokens that are chosen no more
    once the router's input is rounded to bfloat16."""
    from jax import lax

    low = router_input.astype(jnp.bfloat16).astype(jnp.float32)
    s = jax.nn.sigmoid(jnp.matmul(low, router,
                                  precision=lax.Precision.HIGHEST))
    _, again = lax.top_k(s + bias, choice.shape[1])
    stays = jnp.any(choice[:, :, None] == again[:, None, :], axis=2)
    live = jnp.arange(choice.shape[0]) < real
    return jnp.sum(jnp.where(live[:, None], ~stays, False))
