"""Driver: language-model training through ``TransformerTrainStep``.

The calls are the ones ``TransformerTrainStep.fit`` makes for each
batch — ``step(tokens, labels)`` on NDArrays, then a block on the loss —
driven from here so that the window ends on the clock and not on a
count.  Tokens come round-robin from a small device-resident pool made
from the seed; the weights are the seed's, handed over through
``load_state``.
"""
from __future__ import annotations

import importlib
from typing import Dict

import jax
import jax.numpy as jnp

from .. import flops, weights
from ..train_common import TrainDriver


class Driver(TrainDriver):
    def build(self) -> None:
        import mxnet_tpu as mx
        from mxnet_tpu import diagnostics
        from mxnet_tpu.parallel.mesh import make_mesh
        from mxnet_tpu.transformer import (TransformerConfig,
                                           TransformerTrainStep)

        diagnostics.reset_recompile_stats()
        cfg, cell = self.config, self.cell
        self.ref = importlib.import_module(
            "perfbench.reference." + cfg["reference"])
        opt = cfg["optimizer"]
        self.lr = float(opt["learning_rate"])
        self.specs = self.ref.leaves(cfg)
        self.trainable = {name for name, _, _ in self.specs}
        lm = TransformerConfig(
            vocab_size=cfg["vocab_size"],
            n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            d_ff=cfg["intermediate_size"], rope_base=cfg["rope_base"],
            dtype=cfg["dtype"], param_dtype=cfg["param_dtype"],
            eps=cfg["rms_norm_eps"])
        self.step = step = TransformerTrainStep(
            lm, mesh=make_mesh((1,), ("dp",), self.devices[:1]),
            learning_rate=self.lr, momentum=float(opt["momentum"]),
            attn_impl=cell["attention"], remat=cell["remat"], seed=0)
        step._build()
        # the program names its leaves as the reference lists them, in
        # the same order; checked, not assumed
        theirs = [(k, tuple(v.shape)) for k, v in step._params.items()]
        ours = [(n, tuple(s)) for n, s, _ in self.specs]
        if theirs != ours:
            raise RuntimeError("the program's parameters %s are not the "
                               "reference's leaves %s"
                               % (theirs[:3], ours[:3]))
        with jax.default_device(self.devices[0]):
            step.load_state({"params": weights.make_all(
                self.seed, self.specs, cfg["param_dtype"])})
            tokens = _make_pool(weights.root_key(self.seed), cell["pool"],
                                cell["batch"], cell["seq_len"],
                                cfg["vocab_size"])
        self.pool = [(mx.nd.NDArray(t[:, :-1]), mx.nd.NDArray(t[:, 1:]))
                     for t in tokens]
        self.cursor = 0

    def leaf_specs(self):
        return self.specs

    def next_batch(self):
        batch = self.pool[self.cursor % len(self.pool)]
        self.cursor += 1
        return batch

    def call(self, tokens, labels):
        return self.step.step(tokens, labels)

    def momenta(self) -> Dict:
        return dict(self.step._moms)

    def params(self) -> Dict:
        return dict(self.step._params)

    def work(self) -> Dict:
        batch, seq = self.cell["batch"], self.cell["seq_len"]
        return {"flops_per_step":
                flops.transformer_train_step_flops(self.config, batch, seq),
                "samples_per_step": batch * seq, "sample_unit": "tokens"}

    def drop_program(self) -> None:
        self.step = self.pool = None

    def reference_readings(self, quantise=None, rows=None,
                           frozen=False) -> Dict:
        opt = self.config["optimizer"]

        def make_step(q, lr):
            return self.ref.make_step(self.config, lr,
                                      float(opt["momentum"]), q)

        def to_batch(batch):
            return batch[0]._data, batch[1]._data

        return self.run_reference(make_step, to_batch, quantise, rows,
                                  frozen)


def _make_pool(key, pool: int, batch: int, seq: int, vocab: int):
    """``pool`` batches of ``seq + 1`` token ids, rows that all differ."""

    @jax.jit
    def make(key):
        t = jax.random.randint(jax.random.fold_in(key, 1 << 20),
                               (pool, batch, seq + 1), 0, vocab, jnp.int32)
        return [t[i] for i in range(pool)]

    return make(key)
