"""Driver: training a language model whose block has expert layers
(and whatever else ``TransformerConfig`` spells) through
``TransformerTrainStep``.

``train_lm``'s driver (``step(tokens, labels)`` on NDArrays a step, a
block on the loss, tokens round-robin from a device-resident pool made
from the seed) with another build, work and check: the step is built
ON the seed's weights (``params=``: one copy of the state, not two), a
row of the pool holds ``seq_len`` + 1 + the multi-token modules' ids,
the routing counters are read after the window, and the check also
compares which experts the first step chose (``route_disagree_pct``)
and holds kinds of leaf to limits of their own.
"""
from __future__ import annotations

import importlib
import re
from typing import Dict

import jax
import jax.numpy as jnp

from .. import flops_moe, train_check, weights
from . import train_lm

_KIND = re.compile(r"^hc_(attn|mlp)_")


def lm_config(cfg: Dict):
    """The configuration file's (published) keys as a
    ``TransformerConfig``."""
    try:
        from mxnet_tpu.transformer import RopeYarn, TransformerConfig
    except ImportError as e:
        # a program from before the block was data: fail at once
        raise SystemExit("perfbench: this program cannot spell the "
                         "configuration (%s)" % e)

    sc = cfg["rope_scaling"]
    dense = cfg["first_k_dense_replace"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], rope_base=float(cfg["rope_theta"]),
        dtype=cfg["dtype"], param_dtype=cfg["param_dtype"],
        eps=cfg["rms_norm_eps"], attn_kind="latent",
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_yarn=RopeYarn(
            factor=float(sc["factor"]),
            original_positions=sc["original_max_position_embeddings"],
            beta_fast=float(sc["beta_fast"]),
            beta_slow=float(sc["beta_slow"]), mscale=float(sc["mscale"]),
            mscale_all_dim=float(sc["mscale_all_dim"])),
        ffn_act="swiglu", tied_head=bool(cfg["tie_word_embeddings"]),
        layer_kinds=tuple("dense_ffn" if i < dense else "experts"
                          for i in range(cfg["num_hidden_layers"])),
        n_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        expert_ff=cfg["moe_intermediate_size"],
        held_experts=tuple(cfg["held_experts"]),
        routed_scaling=float(cfg["routed_scaling_factor"]),
        router_bias_rate=cfg["router_bias_rate"],
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=cfg["hc_eps"], hc_clamp=float(cfg["mhc_h_res_clamp_max"]),
        mtp_layers=cfg["num_nextn_predict_layers"],
        mtp_loss_weight=cfg["mtp_loss_weight"])


class Driver(train_lm.Driver):
    def build(self) -> None:
        import mxnet_tpu as mx
        from mxnet_tpu import diagnostics
        from mxnet_tpu.parallel.mesh import make_mesh
        from mxnet_tpu.transformer import TransformerTrainStep

        diagnostics.reset_recompile_stats()
        cfg, cell = self.config, self.cell
        if len(cfg["held_experts"]) != cfg["n_routed_experts"] or \
                -cfg["mhc_h_res_clamp_min"] != cfg["mhc_h_res_clamp_max"]:
            raise ValueError("n_routed_experts counts the experts held "
                             "here; the clamp is symmetric")
        self.ref = importlib.import_module(
            "perfbench.reference." + cfg["reference"])
        opt = cfg["optimizer"]
        self.lr = float(opt["learning_rate"])
        self.specs = self.ref.leaves(cfg)
        self.biased = self.ref.frozen(cfg)
        self.trainable = {n for n, _, _ in self.specs} - set(self.biased)
        lm = lm_config(cfg)
        with jax.default_device(self.devices[0]):
            params = weights.make_all(self.seed, self.specs,
                                      cfg["param_dtype"])
            tokens = train_lm._make_pool(
                weights.root_key(self.seed), cell["pool"], cell["batch"],
                cell["seq_len"] + lm.mtp_layers, cfg["vocab_size"])
        self.step = step = TransformerTrainStep(
            lm, mesh=make_mesh((1,), ("dp",), self.devices[:1]),
            learning_rate=self.lr, momentum=float(opt["momentum"]),
            attn_impl=cell["attention"], remat=cell["remat"], seed=0,
            # in the program's own order, which the step checks
            params={n: params[n] for n, _, _ in self.specs})
        del params
        step._build()
        seq = cell["seq_len"]
        self.pool = [(mx.nd.NDArray(t[:, :seq]), mx.nd.NDArray(t[:, 1:]))
                     for t in tokens]
        self.cursor = 0
        self.routing = None

    def leaf_kinds(self) -> Dict[str, str]:
        """A leaf's kind is its name without the layer, the two
        sublayers' stream maps together: ``wq_a``, ``we_gate``,
        ``router``, ``hc_w``, ``hc_alpha``, ``hc_b_res``, ...  The worst
        leaf of all is a stream map's three scalars (PERF.md), so the
        cell holds kinds to limits of their own."""
        return {name: _KIND.sub("hc_", name.split(".", 1)[-1])
                for name in self.trainable}

    def params(self) -> Dict:
        return {k: self.step._params[k] for k in self.trainable}

    def setup(self) -> None:
        super().setup()
        # the first step's choices, for the comparison; the log is
        # empty again when the window starts
        self.routing = self.step.routing_counters()

    def window(self, seconds: float) -> Dict:
        result = super().window(seconds)
        moe = self.step.routing_counters()
        result["counters"].update(
            {"moe_" + k: moe[k] for k in (
                "assignments_here", "assignments_total", "dropped",
                "load_max_over_mean")})
        result["failed"] += int(moe["dropped"] > 0)
        return result

    def work(self) -> Dict:
        batch, seq = self.cell["batch"], self.cell["seq_len"]
        return {"flops_per_step":
                flops_moe.train_step_flops(self.config, batch, seq),
                "samples_per_step": batch * seq, "sample_unit": "tokens"}

    # -- the reference ------------------------------------------------
    def reference_readings(self, quantise=None, half=False, frozen=False,
                           identity_res=False) -> Dict:
        """The reference's three steps from the seed's weights over the
        program's own first batches; ``route_disagree_pct`` rides along
        (the share of the first step's assignments that the program
        chose otherwise).  Planted faults: ``half`` leaves half of each
        batch out (its later rows; of a batch of one, the later half of
        the row's tokens), ``frozen`` steps at a learning rate of
        nought, ``identity_res`` mixes no stream into another."""
        cfg, opt = self.config, self.config["optimizer"]
        lr = 0.0 if frozen else self.lr
        step = self.ref.make_step(cfg, lr, float(opt["momentum"]),
                                  quantise, identity_res)
        p = weights.make_all(self.seed, self.specs, "float32",
                             only=self.trainable)
        b = weights.make_all(self.seed, self.specs, "float32",
                             only=set(self.biased))
        b = {k: b[k] for k in self.biased}
        m = {k: jnp.zeros_like(v) for k, v in p.items()}
        losses, first, disagree = [], None, None
        with jax.default_matmul_precision("highest"):
            for i, batch in enumerate(self.first_batches):
                x, y = batch[0]._data, batch[1]._data
                if half and x.shape[0] > 1:
                    x, y = x[:x.shape[0] // 2], y[:x.shape[0] // 2]
                elif half:
                    cut = x.shape[1] // 2
                    x, y = x[:, :cut], y[:, :cut + y.shape[1] - x.shape[1]]
                p, m, b, loss, choices = step(p, m, b, x, y)
                losses.append(float(loss))
                if i == 0:
                    first = jax.device_get(weights.norms(m))
                    disagree = _disagree_pct(
                        self.routing["choice"], jax.device_get(choices),
                        batch[0].shape[0])
        changes = jax.device_get(weights.change_norms(
            self.seed, self.specs, p))
        del p, m
        out = train_check.readings(losses, first, self.lr, changes)
        out["route_disagree_pct"] = disagree
        return out

    def _numbers(self, side: Dict, ref: Dict) -> Dict:
        values = train_check.numbers(side, ref, self.leaf_kinds())
        # a side that is itself a run of the reference (a control, a
        # planted fault) brings its own; the program's is the
        # reference's count against it
        values["route_disagree_pct"] = side.get(
            "route_disagree_pct", ref["route_disagree_pct"])
        return values

    def check(self) -> Dict:
        values = self._numbers(self.program_readings,
                               self.reference_readings())
        return train_check.judge(values, self.cell["limits"])

    def calibration(self, control: Dict, faults: bool, quantisers: Dict,
                    rebuilt):
        """As the template's, with this model's own planted fault: the
        reference with ``H_res`` replaced by the identity."""
        ref = self.reference_readings()

        def against(side: Dict):
            return self._numbers(side, ref), \
                {"readings": {"side": side, "reference": ref}}

        yield ("program",) + against(self.program_readings)
        if "reference" in control:
            yield ("control",) + against(self.reference_readings(
                quantise=quantisers[control["reference"]]))
        if faults:
            yield ("fault_half_batch",) + against(
                self.reference_readings(half=True))
            yield ("fault_state_unchanged",) + against(
                self.reference_readings(frozen=True))
            yield ("fault_identity_h_res",) + against(
                self.reference_readings(identity_res=True))


def _disagree_pct(program, reference, batch: int) -> float:
    """``program`` (L, batch * T, k) and ``reference`` (b, L, t, k)
    chosen experts, the reference's over the first ``b`` rows and ``t``
    tokens a row: the share of those assignments, in percent, that one
    side made and the other did not (a token's choices compared as a
    set)."""
    import numpy as np

    ref = np.asarray(reference).transpose(1, 0, 2, 3)       # L, b, t, k
    prog = np.asarray(program)
    prog = prog.reshape(prog.shape[0], batch, -1, prog.shape[-1])
    prog = prog[:, :ref.shape[1], :ref.shape[2]]
    same = (prog[..., :, None] == ref[..., None, :]).any(-1).sum()
    return 100.0 * (1.0 - same / prog.size)
