#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

    python3 chip_smoke.py

drives the program's main paths once on a TPU, through the entry points
a user calls, at the full width of the models the repo supports (depth,
slots and context are cut; weights are random, from a seed):

  1. ResNet-50 training: ``FusedTrainStep`` on a one-chip ``dp`` mesh,
     ``mx.mod.Module(sym, context=mx.tpu(0)).fit``, and batches fed
     through ``io_pipeline.InputPipeline(device=True)``;
  2. transformer training: ``TransformerTrainStep.fit`` over
     ``LMTokenIter`` (hidden 2048, 16 heads of 128, vocab 50,304,
     sequence 2048, bf16 compute);
  3. generation serving: ``GenerationRuntime`` + ``ModelServer`` +
     ``HttpFrontend`` answering real HTTP requests, in this process;
  4. the Pallas flash-attention kernel, compiled by Mosaic;
  5. with more than one chip: legs 1 and 2 again on a ``dp`` mesh over
     every chip, against the one-chip loss.

One process, the only one that touches JAX: a chip belongs to one
process.  No leg is wrapped in try/except and nothing here exits 0 on a
failure — any leg failing is a traceback and a non-zero exit code.
Without a TPU (``jax.devices()[0].platform != "tpu"``) the script names
what it found and exits 2; there is no CPU fallback.

``--rehearsal`` runs the same control flow at toy sizes on whatever
backend is there (Pallas in interpret mode) so tier-1 covers the script.
Its output is labelled "rehearsal — not a chip result" and carries no
``"ok"``.

Every time printed is INFORMATIONAL — a smoke test, not a benchmark.
The last line of stdout of a passing chip run is the JSON object

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and the full record goes to ``chiprun_out/chip_smoke.json``.
"""
import argparse
import functools
import json
import math
import os
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# sizes: width is never cut on the chip; the rehearsal cuts everything.
# ``resnet`` is a (block, layers, channels) row of the model zoo's
# resnet_spec: 50 is resnet50_v1, built by the same constructor call
# vision.resnet50_v1 makes; the rehearsal keeps the block, two stages.
CHIP = dict(
    resnet=50, classes=1000, img=224, batch=32, fit_batches=4,
    io_batches=3, io_workers=2,
    d_model=2048, n_heads=16, vocab=50304, seq=2048, n_layers=2,
    lm_batch=4, lm_steps=6, lm_lr=0.05,
    gen_slots=2, gen_block=64, gen_prompt=64, gen_context=128, gen_new=8,
    attn=(2, 2048, 16, 128), attn_block=256,
)
REHEARSAL = dict(
    resnet=("bottle_neck", [1, 1], [8, 16, 32]), classes=10, img=32,
    batch=8, fit_batches=2,
    io_batches=2, io_workers=2,
    d_model=64, n_heads=4, vocab=256, seq=64, n_layers=1,
    lm_batch=4, lm_steps=6, lm_lr=0.05,
    gen_slots=2, gen_block=16, gen_prompt=16, gen_context=32, gen_new=6,
    attn=(1, 256, 2, 8), attn_block=128,
)
ALL_LEGS = ("resnet", "transformer", "serving", "pallas", "multichip")


class SmokeFailure(RuntimeError):
    """A leg produced something wrong."""


def check(cond, msg, *args):
    if not cond:
        raise SmokeFailure(msg % args if args else msg)


def say(msg, *args):
    print(msg % args if args else msg, flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from jax's own
    monitoring events — so compile time is reported apart from steps."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.compile_s += float(duration)
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.cache_misses += 1

    def snapshot(self):
        return (self.compile_s, self.compiles, self.cache_hits,
                self.cache_misses)

    def since(self, snap):
        now = self.snapshot()
        return {"compile_s": round(now[0] - snap[0], 2),
                "programs": now[1] - snap[1],
                "cache_hits": now[2] - snap[2],
                "cache_misses": now[3] - snap[3]}


def on_devices(arr, devices, what):
    """Assert a jax array (or NDArray) lives on exactly these devices —
    not merely that such devices are listed."""
    raw = getattr(arr, "_data", arr)
    got = set(raw.devices())
    check(got == set(devices), "%s lives on %s, expected %s", what,
          sorted(map(str, got)), sorted(map(str, devices)))


def _new_program():
    """The recompile registry counts compilations per step NAME, and the
    legs build several step objects of one name on purpose (one chip,
    then every chip); start each from a clean count so the storm
    detector keeps meaning "this object recompiled"."""
    from mxnet_tpu import diagnostics

    diagnostics.reset_recompile_stats()


def finite(x, what):
    import numpy as np

    a = np.asarray(x, dtype=np.float64)
    check(bool(np.isfinite(a).all()), "%s is not finite: %s", what, a)
    return a


# ---------------------------------------------------------------------
# leg 1: ResNet training — fused step, Module.fit, input pipeline
# ---------------------------------------------------------------------
def _resnet_batch(cfg, batch, seed=0):
    import numpy as np

    rng = np.random.RandomState(seed)
    X = rng.uniform(size=(batch, 3, cfg["img"], cfg["img"])) \
        .astype(np.float32)
    y = rng.randint(0, cfg["classes"], batch).astype(np.float32)
    return X, y


def _fresh_resnet(cfg):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision import resnet

    np.random.seed(0)
    mx.random.seed(0)
    spec = cfg["resnet"]
    block, layers, channels = resnet.resnet_spec[spec] \
        if isinstance(spec, int) else spec
    net = resnet.ResNetV1(resnet.resnet_block_versions[0][block], layers,
                          channels, classes=cfg["classes"])
    net.initialize(mx.init.Xavier())
    return net


def _fused_step(cfg, net, devices):
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh

    _new_program()
    mesh = make_mesh((len(devices),), ("dp",), devices)
    # lr 0.01: momentum 0.9 at 0.05 overshoots within a few steps on a
    # repeated batch of random labels
    return FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, learning_rate=0.01, momentum=0.9,
                          dtype="bfloat16")


def leg_resnet(cfg, devices, meter, record):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import io as mio
    from mxnet_tpu import io_pipeline as iop
    from mxnet_tpu import nd

    chip = devices[:1]
    ctx = mx.tpu(0)
    check(ctx.jax_device() == chip[0], "mx.tpu(0) resolved to %s, not %s",
          ctx.jax_device(), chip[0])
    X, y = _resnet_batch(cfg, cfg["batch"])

    # -- (a) FusedTrainStep on a one-chip dp mesh ----------------------
    net = _fresh_resnet(cfg)
    step = _fused_step(cfg, net, chip)
    Xd, yd = nd.array(X, ctx=ctx), nd.array(y, ctx=ctx)
    on_devices(Xd, chip, "the input batch")
    # the shape-settling eager forward of _build, looked at on its own:
    # it runs wherever the freshly initialised parameters live
    t0 = time.perf_counter()
    snap = meter.snapshot()
    step._build(Xd)
    where = [d.platform for p in net.collect_params().values()
             for d in p.data()._data.devices()]
    settle = {"wall_s": round(time.perf_counter() - t0, 2),
              "parameters_on": {p: where.count(p) for p in set(where)},
              **meter.since(snap)}
    say("  settle forward (FusedTrainStep._build): %s", settle)
    t0 = time.perf_counter()
    losses = []
    for _ in range(3):
        loss, logits = step(Xd, yd)
        losses.append(float(loss.asnumpy()))
    t_steps = time.perf_counter() - t0
    check(logits.shape == (cfg["batch"], cfg["classes"]),
          "logits shape %s", logits.shape)
    on_devices(loss, chip, "the loss")
    on_devices(logits, chip, "the logits")
    finite(logits.asnumpy().astype(np.float32), "logits")
    t0 = time.perf_counter()
    window = step.run_steps(Xd, yd, steps=4).asnumpy()
    t_window = time.perf_counter() - t0
    losses += [float(v) for v in window]
    finite(losses, "fused-step losses")
    check(losses[-1] < losses[0], "fused-step loss did not fall on a "
          "repeated batch: %s", losses)
    for p in net.collect_params().values():
        on_devices(p.data(), chip, "parameter %s" % p.name)
    for m in step._moms:
        on_devices(m, chip, "a momentum buffer")
    t0 = time.perf_counter()
    float(step(Xd, yd)[0].asnumpy())
    record["fused"] = {
        "losses": [round(v, 4) for v in losses], "settle": settle,
        "first_3_steps_wall_s": round(t_steps, 2),
        "run_steps_4_wall_s": round(t_window, 2),
        "warm_step_s": round(time.perf_counter() - t0, 4)}
    say("  fused step: losses %s", record["fused"]["losses"])

    # -- (b) the user-facing spelling: Module.fit on mx.tpu(0) ---------
    n = cfg["fit_batches"]
    Xf, yf = _resnet_batch(cfg, cfg["batch"] * n, seed=1)
    sym = mx.sym.SoftmaxOutput(
        _fresh_resnet(cfg)(mx.sym.Variable("data")), name="softmax")
    it = mio.NDArrayIter(Xf, yf, batch_size=cfg["batch"],
                         label_name="softmax_label")
    mod = mx.mod.Module(sym, context=ctx)
    metric = mx.metric.create("ce")
    per_epoch = []
    t0 = time.perf_counter()
    mod.fit(it, num_epoch=3, optimizer="sgd", eval_metric=metric,
            optimizer_params=(("learning_rate", 0.01), ("momentum", 0.9)),
            initializer=mx.init.Xavier(),
            batch_end_callback=lambda p: per_epoch.append(
                (p.epoch, p.eval_metric.get()[1])))
    t_fit = time.perf_counter() - t0
    ce = [v for _, v in sorted(dict(per_epoch).items())]
    finite(ce, "Module.fit cross-entropy")
    check(len(ce) == 3 and ce[-1] < ce[0],
          "Module.fit cross-entropy did not fall over epochs: %s", ce)
    for name, arr in mod._exec.arg_dict.items():
        on_devices(arr, chip, "Module argument %s" % name)
    arg_params, _ = mod.get_params()
    finite(arg_params[sorted(arg_params)[0]].asnumpy(), "a fitted weight")
    record["module_fit"] = {"epoch_ce": [round(v, 4) for v in ce],
                            "wall_s": round(t_fit, 2)}
    say("  Module.fit: cross-entropy per epoch %s", record["module_fit"])

    # -- (c) batches through the decode pool + device prefetch ---------
    nb = cfg["io_batches"]
    Xp, yp = _resnet_batch(cfg, cfg["batch"] * nb * cfg["io_workers"],
                           seed=2)
    pipe = iop.InputPipeline(
        iop.make_ndarray_iter_fn(Xp, yp, batch_size=cfg["batch"],
                                 last_batch_handle="discard"),
        num_workers=cfg["io_workers"], device=True)
    method = pipe._pool._method
    t0 = time.perf_counter()
    io_losses = []
    for _ in range(nb):
        b = pipe.next()
        on_devices(b.data[0], chip, "a prefetched batch")
        loss, _ = step(b.data[0], b.label[0])
        io_losses.append(float(loss.asnumpy()))
    pipe.close()
    finite(io_losses, "pipeline-fed losses")
    record["input_pipeline"] = {
        "start_method": method, "batches": nb,
        "losses": [round(v, 4) for v in io_losses],
        "wall_s": round(time.perf_counter() - t0, 2)}
    say("  InputPipeline(device=True), %s workers: %s",
        method, record["input_pipeline"])


# ---------------------------------------------------------------------
# leg 2: transformer training
# ---------------------------------------------------------------------
def _lm_cfg(cfg):
    from mxnet_tpu.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=cfg["vocab"], n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"], dtype="bfloat16")


def _lm_iter(cfg, batch):
    from mxnet_tpu.transformer import LMTokenIter

    # as many sequences as one batch holds: every step sees the same
    # batch, so a few steps are enough for the loss to fall
    return LMTokenIter(batch_size=batch, seq_len=cfg["seq"],
                       vocab_size=cfg["vocab"], num_sequences=batch,
                       seed=0)


def leg_transformer(cfg, devices, record):
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.transformer import TransformerTrainStep

    chip = devices[:1]
    _new_program()
    step = TransformerTrainStep(
        _lm_cfg(cfg), mesh=make_mesh((1,), ("dp",), chip),
        learning_rate=cfg["lm_lr"], seed=0)
    t0 = time.perf_counter()
    losses = step.fit(_lm_iter(cfg, cfg["lm_batch"]), cfg["lm_steps"])
    wall = time.perf_counter() - t0
    finite(losses, "transformer losses")
    check(losses[-1] < losses[0], "transformer loss did not fall: %s",
          losses)
    check(losses[-1] < math.log(cfg["vocab"]),
          "transformer loss %.4f not below log(vocab)=%.4f: %s",
          losses[-1], math.log(cfg["vocab"]), losses)
    for k, v in step._params.items():
        on_devices(v, chip, "transformer parameter %s" % k)
    for k, v in step._moms.items():
        on_devices(v, chip, "transformer momentum %s" % k)
    record.update({"losses": [round(v, 4) for v in losses],
                   "log_vocab": round(math.log(cfg["vocab"]), 4),
                   "fit_wall_s": round(wall, 2)})
    say("  TransformerTrainStep.fit: losses %s (log vocab %.3f)",
        record["losses"], math.log(cfg["vocab"]))
    return losses


# ---------------------------------------------------------------------
# leg 3: generation serving over HTTP, in this process
# ---------------------------------------------------------------------
def _http(base, path, body=None):
    req = urllib.request.Request(
        base + path,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    resp = urllib.request.urlopen(req, timeout=300)
    check(resp.status == 200, "%s answered %d", path, resp.status)
    return resp, resp.read().decode()


def leg_serving(cfg, devices, record):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import diagnostics as diag
    from mxnet_tpu import serving
    from mxnet_tpu.transformer import TransformerConfig, init_params
    from mxnet_tpu.transformer import model as tm

    chip = devices[:1]
    lm = TransformerConfig(
        vocab_size=cfg["vocab"], n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        dtype="bfloat16", param_dtype="bfloat16")
    with jax.default_device(chip[0]):
        params = init_params(jax.random.PRNGKey(0), lm)
        rt = serving.GenerationRuntime(
            "smoke_gen", params, lm, slots=cfg["gen_slots"],
            block_tokens=cfg["gen_block"], max_prompt=cfg["gen_prompt"],
            max_context=cfg["gen_context"], max_new=cfg["gen_new"],
            prefill_batch=1)
    srv = serving.ModelServer(queue_max=8, default_deadline_ms=300000)
    t0 = time.perf_counter()
    srv.add_generator(rt)  # AOT-compiles every plan cell
    t_load = time.perf_counter() - t0
    for k, v in rt._params.items():
        on_devices(v, chip, "served parameter %s" % k)
    for k, v in rt.kv.pages.items():
        on_devices(v, chip, "KV pool %s" % k)
    fe = serving.HttpFrontend(srv, port=0)
    host, port = fe.start()
    base = "http://%s:%d" % (host, port)
    reference = jax.jit(functools.partial(
        tm.apply, cfg=lm, attn_fn=tm.dense_causal_attn))
    rng = np.random.RandomState(3)
    n_new = cfg["gen_new"]
    prompts = [rng.randint(1, cfg["vocab"], size=n).tolist()
               for n in (5, cfg["gen_prompt"] - 3)]
    _http(base, "/readyz")
    t0 = time.perf_counter()
    for prompt in prompts:
        _, body = _http(base, "/v1/models/smoke_gen:generate",
                        {"prompt": prompt, "max_new": n_new})
        blocking = json.loads(body)
        check(len(blocking["tokens"]) == n_new
              and blocking["prompt_len"] == len(prompt),
              "blocking reply %s", blocking)
        resp, body = _http(base, "/v1/models/smoke_gen:generate",
                           {"prompt": prompt, "max_new": n_new,
                            "stream": True})
        check(resp.headers.get("Transfer-Encoding") == "chunked",
              "stream reply was not chunked")
        lines = [json.loads(ln) for ln in body.splitlines() if ln]
        check(lines[-1] == {"done": True, "tokens": n_new,
                            "prompt_len": len(prompt)},
              "stream ended with %s", lines[-1])
        streamed = [ln["token"] for ln in lines[:-1]]
        check(streamed == blocking["tokens"],
              "streamed tokens %s != blocking tokens %s", streamed,
              blocking["tokens"])
        # the first token against the training-path forward on the same
        # prompt: bf16 may reorder a near-tie, so the served token must
        # sit within tolerance of the reference maximum
        ref = np.asarray(reference(
            rt._params, jnp.asarray(np.asarray(prompt, np.int32)[None])
        )[0, -1], dtype=np.float32)
        finite(ref, "reference logits")
        tok = streamed[0]
        check(ref[tok] >= ref.max() - 0.05 * max(1.0, abs(ref.max())),
              "first token %d (ref logit %.4f) disagrees with "
              "transformer.model.apply (argmax %d, logit %.4f)",
              tok, ref[tok], int(ref.argmax()), ref.max())
    t_req = time.perf_counter() - t0
    _http(base, "/readyz")
    _, metrics = _http(base, "/metrics")
    check("mxnet_serve_gen_tokens_total" in metrics,
          "/metrics carries no generation counter")
    counts = {k: v["count"] for k, v in diag.recompile_stats().items()
              if k.startswith(("gen_prefill:smoke_gen:",
                               "gen_decode:smoke_gen:"))}
    check(len(counts) == len(rt.prefill_plan) + len(rt.decode_plan),
          "plan cells %d+%d, compiled %s", len(rt.prefill_plan),
          len(rt.decode_plan), sorted(counts))
    check(set(counts.values()) == {1},
          "a plan cell recompiled under traffic: %s", counts)
    kv = rt.kv.stats()
    check(kv["blocks_live"] == 0, "leaked KV blocks: %s", kv)
    srv.drain(timeout_s=30)
    fe.stop()
    record.update({"plan_cells": len(counts),
                   "load_and_compile_wall_s": round(t_load, 2),
                   "requests": 2 * len(prompts) + 3,
                   "requests_wall_s": round(t_req, 2),
                   "kv": {k: kv[k] for k in ("blocks_live",
                                             "blocks_free")
                          if k in kv}})
    say("  serving: %s", record)


# ---------------------------------------------------------------------
# leg 4: the Pallas kernel, compiled by Mosaic
# ---------------------------------------------------------------------
def leg_pallas(cfg, devices, record, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel.attention import (attention_reference,
                                              pallas_flash_attention)

    chip = devices[:1]
    B, T, H, D = cfg["attn"]
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.device_put(
        jax.random.normal(kk, (B, T, H, D), jnp.float32)
        .astype(jnp.bfloat16), chip[0]) for kk in keys)
    fn = jax.jit(functools.partial(
        pallas_flash_attention, causal=True, block_q=cfg["attn_block"],
        block_k=cfg["attn_block"], interpret=interpret))
    lowered = fn.lower(q, k, v).as_text()
    mosaic = "tpu_custom_call" in lowered
    check(mosaic != interpret,
          "the kernel's lowering %s a Mosaic custom call (interpret=%s)",
          "carries" if mosaic else "does not carry", interpret)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(q, k, v))
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn(q, k, v))
    t_warm = time.perf_counter() - t0
    on_devices(out, chip, "the kernel output")
    check(out.shape == (B, T, H, D) and out.dtype == jnp.bfloat16,
          "kernel output %s %s", out.shape, out.dtype)
    ref = attention_reference(q, k, v, causal=True)
    got = finite(np.asarray(out, np.float32), "kernel output")
    want = np.asarray(ref, np.float32)
    err = float(np.abs(got - want).max())
    check(bool(np.allclose(got, want, atol=3e-2, rtol=3e-2)),
          "kernel vs attention_reference: max abs err %.4g", err)
    record.update({"shape": [B, T, H, D], "block": cfg["attn_block"],
                   "lowering": "interpret" if interpret else "mosaic",
                   "max_abs_err": round(err, 5),
                   "first_call_s": round(t_first, 3),
                   "warm_call_s": round(t_warm, 5)})
    say("  pallas_flash_attention: %s", record)


# ---------------------------------------------------------------------
# leg 5: every chip this process can see
# ---------------------------------------------------------------------
def _spread(arr, devices, what, replicated):
    """Replicated over, or sharded over, exactly these DISTINCT devices."""
    raw = getattr(arr, "_data", arr)
    on_devices(raw, devices, what)
    shard_devs = [s.device for s in raw.addressable_shards]
    check(len(set(shard_devs)) == len(devices),
          "%s: %d shards on %d distinct devices", what, len(shard_devs),
          len(set(shard_devs)))
    check(raw.sharding.is_fully_replicated == replicated,
          "%s: replicated=%s", what, raw.sharding.is_fully_replicated)
    if not replicated:
        check(raw.addressable_shards[0].data.shape[0] * len(devices)
              == raw.shape[0], "%s is not split %d ways on axis 0",
              what, len(devices))


def leg_multichip(cfg, devices, record, lm_first_loss):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.transformer import TransformerTrainStep

    n = len(devices)
    check(len(set(devices)) == n and n > 1, "devices %s", devices)
    mesh = make_mesh((n,), ("dp",), devices)
    batch_sh = NamedSharding(mesh, P("dp"))

    # -- ResNet: global batch = leg 1's batch per chip -----------------
    X, y = _resnet_batch(cfg, cfg["batch"] * n, seed=5)
    one = _fused_step(cfg, _fresh_resnet(cfg), devices[:1])
    loss_one = float(one(X, y)[0].asnumpy())
    net = _fresh_resnet(cfg)
    step = _fused_step(cfg, net, devices)
    Xs, ys = jax.device_put(X, batch_sh), jax.device_put(y, batch_sh)
    _spread(Xs, devices, "the sharded batch", replicated=False)
    loss, logits = step(Xs, ys)
    loss_all = float(loss.asnumpy())
    _spread(logits, devices, "the logits", replicated=False)
    for p in net.collect_params().values():
        _spread(p.data(), devices, "parameter %s" % p.name,
                replicated=True)
    finite([loss_one, loss_all], "first-step losses")
    check(abs(loss_all - loss_one) <= 2e-2 * max(1.0, abs(loss_one)),
          "ResNet first-step loss on %d chips %.5f vs one chip %.5f",
          n, loss_all, loss_one)
    loss_next = float(step(Xs, ys)[0].asnumpy())
    finite([loss_next], "second multi-chip loss")
    check(loss_next < loss_all, "loss on %d chips did not fall: %.5f "
          "then %.5f", n, loss_all, loss_next)
    record["resnet"] = {"loss_one_chip": round(loss_one, 5),
                        "loss_all_chips": round(loss_all, 5),
                        "loss_second_step": round(loss_next, 5),
                        "bucketed": step.bucketed}
    say("  ResNet dp=%d: %s", n, record["resnet"])

    # -- transformer: leg 2's batch, split over the chips --------------
    check(cfg["lm_batch"] % n == 0, "lm_batch %d over %d chips",
          cfg["lm_batch"], n)
    _new_program()
    tstep = TransformerTrainStep(_lm_cfg(cfg), mesh=mesh,
                                 learning_rate=cfg["lm_lr"], seed=0)
    b = _lm_iter(cfg, cfg["lm_batch"]).next()
    t_loss = float(tstep.step(b.data[0], b.label[0]))
    for k, v in tstep._params.items():
        _spread(v, devices, "transformer parameter %s" % k,
                replicated=True)
    finite([t_loss], "multi-chip transformer loss")
    check(abs(t_loss - lm_first_loss)
          <= 2e-2 * max(1.0, abs(lm_first_loss)),
          "transformer first-step loss on %d chips %.5f vs one chip "
          "%.5f", n, t_loss, lm_first_loss)
    record["transformer"] = {"loss_one_chip": round(lm_first_loss, 5),
                             "loss_all_chips": round(t_loss, 5)}
    say("  transformer dp=%d: %s", n, record["transformer"])

    in_use = {}
    for d in devices:
        stats = d.memory_stats()
        if stats is None and d.platform != "tpu":
            in_use[str(d)] = "not reported by this backend"
            continue
        check(stats is not None and stats.get("bytes_in_use", 0) > 0,
              "%s reports no memory in use: %s", d, stats)
        in_use[str(d)] = int(stats["bytes_in_use"])
    record["bytes_in_use"] = in_use
    say("  memory in use per device: %s", in_use)


# ---------------------------------------------------------------------
def where_default_context_lands(record):
    """Informational: on a machine with both backends, where do arrays
    made WITHOUT a context go?  (context.py documents the answer.)"""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    def plat(a):
        return sorted({d.platform for d in a._data.devices()})

    a = nd.array(np.ones((2, 2), np.float32))
    b = nd.zeros((2, 2))
    c = nd.random.uniform(shape=(2, 2))
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=2, name="fc"), name="softmax"))
    mod.bind(data_shapes=[("data", (2, 2))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params()
    record.update({
        "default_context": str(mx.current_context()),
        "nd.array(numpy)": plat(a), "nd.zeros": plat(b),
        "nd.random.uniform": plat(c), "array+zeros": plat(a + b),
        "zeros+1": plat(b + 1),
        "Module(sym) weight": plat(mod._exec.arg_dict["fc_weight"]),
        "nd.zeros(ctx=mx.cpu())": plat(nd.zeros((2, 2), ctx=mx.cpu())),
        "nd.zeros(ctx=mx.tpu(0))": plat(nd.zeros((2, 2),
                                                 ctx=mx.tpu(0)))})
    say("default-context placement: %s", record)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes on any backend; not a chip result")
    ap.add_argument("--legs", default=",".join(ALL_LEGS),
                    help="comma-separated subset of: %s"
                    % ", ".join(ALL_LEGS))
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out"),
                    help="directory for chip_smoke.json and dumps")
    args = ap.parse_args(argv)
    legs = [s for s in args.legs.split(",") if s]
    unknown = sorted(set(legs) - set(ALL_LEGS))
    if unknown:
        ap.error("unknown legs %s" % unknown)
    os.makedirs(args.out, exist_ok=True)
    os.environ.setdefault("MXNET_DUMP_DIR",
                          os.path.join(args.out, "smoke_dumps"))

    t_start = time.perf_counter()
    import jax

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    if not args.rehearsal and dev0.platform != "tpu":
        print("chip_smoke: needs a TPU and found platform %r (%s x%d); "
              "there is no CPU fallback — use --rehearsal for the "
              "control-flow run" % (dev0.platform, dev0.device_kind,
                                    len(jax.devices())),
              file=sys.stderr)
        return 2
    cfg = dict(REHEARSAL if args.rehearsal else CHIP)
    if args.rehearsal:
        say("REHEARSAL — NOT A CHIP RESULT: toy sizes, Pallas in "
            "interpret mode, whatever backend is here")
    say("device: platform=%s kind=%s count=%d", device["platform"],
        device["kind"], device["count"])
    say("x64=%s", jax.config.jax_enable_x64)

    meter = CompileMeter()
    from mxnet_tpu import compile_cache

    cache_dir = compile_cache.enable()
    check(cache_dir is not None or args.rehearsal,
          "the compile cache is disabled on the chip path")
    entries_before = compile_cache.entry_count()
    say("compile cache: %s (%d entries)", cache_dir, entries_before)

    devices = list(jax.devices())
    record = {"device": device, "rehearsal": args.rehearsal,
              "cache_dir": cache_dir, "legs": {}}
    where_default_context_lands(record.setdefault("placement", {}))

    lm_losses = None
    for leg in legs:
        if leg == "multichip" and len(devices) < 2:
            say("leg multichip: skipped, one device")
            record["legs"][leg] = {"skipped": "one device"}
            continue
        say("leg %s ...", leg)
        rec = record["legs"].setdefault(leg, {})
        snap, t0 = meter.snapshot(), time.perf_counter()
        if leg == "resnet":
            leg_resnet(cfg, devices, meter, rec)
        elif leg == "transformer":
            lm_losses = leg_transformer(cfg, devices, rec)
        elif leg == "serving":
            leg_serving(cfg, devices, rec)
        elif leg == "pallas":
            leg_pallas(cfg, devices, rec,
                       interpret=args.rehearsal
                       and dev0.platform != "tpu")
        elif leg == "multichip":
            check(lm_losses is not None,
                  "leg multichip needs leg transformer's one-chip loss")
            leg_multichip(cfg, devices, rec, lm_losses[0])
        rec["wall_s"] = round(time.perf_counter() - t0, 2)
        rec["compile"] = meter.since(snap)
        say("leg %s passed: wall %.1fs, of which compile %s "
            "(informational)", leg, rec["wall_s"], rec["compile"])

    entries_after = compile_cache.entry_count()
    total = meter.since((0.0, 0, 0, 0))
    record.update({"cache_entries_before": entries_before,
                   "cache_entries_after": entries_after,
                   "compile_total": total,
                   "wall_s": round(time.perf_counter() - t_start, 2)})
    say("compile cache: %s (%d -> %d entries); compile total %s; wall "
        "%.1fs (informational, not a benchmark)", cache_dir,
        entries_before, entries_after, total, record["wall_s"])
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")
    if args.rehearsal:
        print(json.dumps({"rehearsal": "not a chip result",
                          "legs": legs, "device": device}))
    elif sorted(legs) == sorted(ALL_LEGS):
        print(json.dumps({"ok": True, "device": device}))
    else:
        print(json.dumps({"partial": legs, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
