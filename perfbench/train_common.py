"""What the training drivers share: the first steps, the window, the
check.  A driver supplies the program (built, weights loaded from the
seed), the feed and the plain reference."""
from __future__ import annotations

import gc
import math
import time
from typing import Callable, Dict, List, Optional

import jax

from . import train_check, weights


class TrainDriver:
    """Template.  Subclasses implement ``build``, ``next_batch``,
    ``call``, ``momenta``, ``params``, ``leaf_specs``, ``work`` and
    ``reference_readings``; ``self.lr`` is the learning rate.
    ``leaf_kinds`` may tell the kinds of leaf apart."""

    def __init__(self, cell: Dict, config: Dict, seed: int, devices,
                 spans):
        self.cell, self.config, self.seed = cell, config, int(seed)
        self.devices, self.spans = devices, spans
        self.program_readings: Optional[Dict] = None
        self.first_batches: List = []

    def leaf_kinds(self) -> Optional[Dict[str, str]]:
        return None

    # -- set-up: build, then the first steps through the window's own
    # call and feed; they compile every program the window uses -------
    def setup(self) -> None:
        self.build()
        losses, first_momenta = [], None
        for i in range(train_check.STEPS):
            batch = self.next_batch()
            self.first_batches.append(batch)
            loss = self.call(*batch)
            jax.block_until_ready(loss)
            losses.append(float(loss))
            if i == 0:
                first_momenta = weights.norms(self.momenta())
        changes = weights.change_norms(self.seed, self.leaf_specs(),
                                       self.params())
        self.program_readings = train_check.readings(
            losses, jax.device_get(first_momenta), self.lr,
            jax.device_get(changes))

    # -- the measured window -----------------------------------------
    def window(self, seconds: float) -> Dict:
        spans, losses = self.spans, []
        t0 = time.perf_counter()
        while True:
            with spans("bench.next_batch"):
                batch = self.next_batch()
            with spans("bench.step"):
                loss = self.call(*batch)
            with spans("bench.block"):
                jax.block_until_ready(loss)
            losses.append(loss)
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
        elapsed = now - t0
        values = [float(v) for v in jax.device_get(losses)]
        steps = len(values)
        work = self.work()
        return {
            "t_start": t0, "elapsed_s": elapsed, "attempted": steps,
            "failed": sum(1 for v in values if not math.isfinite(v)),
            "metrics": {"train_step_ms": 1e3 * elapsed / steps},
            "counters": dict(work, steps=steps, last_loss=values[-1],
                             flops_done=work["flops_per_step"] * steps),
            "info": "%d steps in %.3f s: %.1f %s/s, last loss %.4f" % (
                steps, elapsed, steps * work["samples_per_step"] / elapsed,
                work["sample_unit"], values[-1]),
        }

    def release(self) -> None:
        """Drop everything the program holds on the device."""
        self.drop_program()
        gc.collect()

    # -- the check ----------------------------------------------------
    def check(self) -> Dict:
        ref = self.reference_readings()
        values = train_check.numbers(self.program_readings, ref,
                                     self.leaf_kinds())
        return train_check.judge(values, self.cell["limits"])

    def calibration(self, control: Dict, faults: bool, quantisers: Dict,
                    rebuilt: Callable):
        """``(what, numbers, extra)`` for ``perfbench.calibrate``."""
        ref = self.reference_readings()
        kinds = self.leaf_kinds()

        def against(side: Dict):
            # ``readings`` holds both sides leaf by leaf, for a look
            # that the numbers alone do not allow
            return train_check.numbers(side, ref, kinds), \
                {"readings": {"side": side, "reference": ref}}

        yield ("program",) + against(self.program_readings)
        if "program" in control:
            yield ("control",) + against(
                rebuilt(control["program"]).program_readings)
        if "reference" in control:
            yield ("control",) + against(self.reference_readings(
                quantise=quantisers[control["reference"]]))
        if faults:
            yield ("fault_half_batch",) + against(
                self.reference_readings(rows=self.cell["batch"] // 2))
            yield ("fault_state_unchanged",) + against(
                self.reference_readings(frozen=True))

    def run_reference(self, make_step: Callable, to_batch: Callable,
                      quantise=None, rows: Optional[int] = None,
                      frozen: bool = False) -> Dict:
        """The reference's three steps from the seed's weights over the
        program's own first batches.  Two planted faults: ``rows`` keeps
        only the first so many rows of each batch; ``frozen`` steps at a
        learning rate of nought, so the state comes back unchanged."""
        p = weights.make_all(self.seed, self.leaf_specs(), "float32",
                             only=self.trainable)
        m = {k: jax.numpy.zeros_like(v) for k, v in p.items()}
        step = make_step(quantise, 0.0 if frozen else self.lr)
        losses, first = [], None
        with jax.default_matmul_precision("highest"):
            for i, batch in enumerate(self.first_batches):
                x, y = to_batch(batch)
                if rows is not None:
                    x, y = x[:rows], y[:rows]
                p, m, loss = step(p, m, x, y)
                losses.append(float(loss))
                if i == 0:
                    first = jax.device_get(weights.norms(m))
        changes = jax.device_get(weights.change_norms(
            self.seed, self.leaf_specs(), p))
        del p, m
        return train_check.readings(losses, first, self.lr, changes)
