"""Driver: image-classification training through ``FusedTrainStep``.

The call shape is the one ``chip_smoke.py`` proved on the chip (PR 21):
a zoo ResNet v1 built by the constructor ``resnet50_v1`` calls, the
gluon softmax cross entropy, ``FusedTrainStep`` on a one-chip ``dp``
mesh with a whole-model cast to the configuration's dtype, one call a
step.  The input pipeline is bypassed: batches come round-robin from a
small pool made on the device from the seed.
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
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.model_zoo.vision import resnet
        from mxnet_tpu.parallel.dp import FusedTrainStep
        from mxnet_tpu.parallel.mesh import make_mesh

        from mxnet_tpu import diagnostics

        # the recompile registry counts by step NAME: start this
        # object's count clean (as chip_smoke.py does)
        diagnostics.reset_recompile_stats()
        cfg, cell = self.config, self.cell
        self.ref = importlib.import_module(
            "perfbench.reference." + cfg["reference"])
        opt = cfg["optimizer"]
        self.lr = float(opt["learning_rate"])
        dev = self.devices[0]
        ctx = mx.tpu(0) if dev.platform == "tpu" else mx.cpu()
        self.net = net = resnet.ResNetV1(
            resnet.resnet_block_versions[0][cfg["block"]], cfg["layers"],
            cfg["channels"], classes=cfg["classes"])
        net.initialize(mx.init.Zero(), ctx=ctx)
        # the seed's weights, handed over by position: the program
        # defines its parameters in the order ``leaves`` lists them
        self.specs = [(n, s, _init(kind, s)) for n, s, kind in
                      self.ref.leaves(cfg)]
        self.trainable = {n for n, _, kind in self.ref.leaves(cfg)
                          if kind in self.ref.TRAINABLE}
        values = weights.make_all(self.seed, self.specs, "float32")
        self.leaf_of: Dict[str, str] = {}
        with jax.default_device(dev):
            for (leaf, shape, _), p in zip(self.specs,
                                           net.collect_params().values(),
                                           strict=True):
                if any(a and a != b for a, b in zip(p.shape, shape)) \
                        or len(p.shape) != len(shape):
                    raise RuntimeError("parameter %s %s is not leaf %s %s"
                                       % (p.name, p.shape, leaf, shape))
                p.set_data(mx.nd.NDArray(values[leaf], ctx=ctx))
                self.leaf_of[p.name] = leaf
        del values
        self.step = FusedTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            mesh=make_mesh((1,), ("dp",), self.devices[:1]),
            learning_rate=self.lr, momentum=float(opt["momentum"]),
            dtype=None if cfg["dtype"] == "float32" else cfg["dtype"])
        self.pool = _make_pool(
            weights.root_key(self.seed), cell["pool"], cell["batch"],
            (cfg["image_channels"], cfg["image_size"], cfg["image_size"]),
            cfg["classes"], cfg["dtype"])
        self.cursor = 0

    def leaf_specs(self):
        return self.specs

    def leaf_kinds(self) -> Dict[str, str]:
        return {n: kind for n, _, kind in self.ref.leaves(self.config)}

    def next_batch(self):
        batch = self.pool[self.cursor % len(self.pool)]
        self.cursor += 1
        return batch

    def call(self, data, label):
        return self.step(data, label)[0]._data

    def momenta(self) -> Dict:
        return {self.leaf_of[c.name]: m for c, m in
                zip(self.step._cells, self.step._moms)
                if self.leaf_of[c.name] in self.trainable}

    def params(self) -> Dict:
        return {self.leaf_of[p.name]: p.data()._data for p in
                self.net.collect_params().values()
                if self.leaf_of[p.name] in self.trainable}

    def work(self) -> Dict:
        batch = self.cell["batch"]
        return {"flops_per_step":
                flops.resnet_train_step_flops(self.config, batch),
                "samples_per_step": batch, "sample_unit": "images"}

    def drop_program(self) -> None:
        self.net = self.step = self.pool = None

    def reference_readings(self, quantise=None, rows=None,
                           frozen=False) -> Dict:
        opt = self.config["optimizer"]

        def make_step(q, lr):
            return self.ref.make_step(self.config, lr,
                                      float(opt["momentum"]), q)

        def to_batch(batch):
            return batch[0].astype(jnp.float32), batch[1]

        return self.run_reference(make_step, to_batch, quantise, rows,
                                  frozen)


def _init(kind: str, shape):
    if kind in ("conv_w", "fc_w"):
        fan_in = 1
        for n in shape[1:]:
            fan_in *= n
        return ("normal", (2.0 / fan_in) ** 0.5)
    return ("const", 1.0 if kind in ("bn_gamma", "bn_var") else 0.0)


def _make_pool(key, pool: int, batch: int, image, classes: int, dtype):
    """``pool`` batches that all differ, on the device, in one call."""

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(jax.random.fold_in(key, 1 << 20))
        x = jax.random.uniform(kx, (pool, batch) + tuple(image),
                               jnp.float32).astype(dtype)
        y = jax.random.randint(ky, (pool, batch), 0, classes) \
            .astype(jnp.float32)
        return [x[i] for i in range(pool)], [y[i] for i in range(pool)]

    xs, ys = make(key)
    return list(zip(xs, ys))
