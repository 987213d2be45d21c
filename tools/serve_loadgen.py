#!/usr/bin/env python
"""serve_loadgen.py — drive open-loop load against the serving tier
and print the outcome accounting as JSON.

Two modes over an in-process demo server (the serving layer is what's
being measured; swap in a real checkpoint with --ckpt-dir):

  # fixed-rate window: offered/admitted/ok/shed/p50/p99
  python tools/serve_loadgen.py --qps 500 --duration 3

  # SLO ramp: QPS sustained at a fixed p99 SLO (qps_at_slo, ramp)
  python tools/serve_loadgen.py --slo-p99-ms 50

Chaos composes exactly like training: MXNET_CHAOS="slow_request:
model=demo,ms=5,count=1000000" reproduces the overload e2e from the
command line.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="open-loop load generator for mxnet_tpu.serving")
    ap.add_argument("--qps", type=float, default=200.0,
                    help="offered request rate (fixed-rate mode)")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="window seconds (fixed-rate mode)")
    ap.add_argument("--deadline-ms", type=float, default=1000.0,
                    help="per-request deadline")
    ap.add_argument("--slo-p99-ms", type=float, default=None,
                    help="run the qps_at_slo ramp instead of one window")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--queue-max", type=int, default=128)
    ap.add_argument("--batch-deadline-ms", type=float, default=2.0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="serve this elastic checkpoint's params "
                         "through the demo MLP apply_fn (dims must "
                         "match) instead of the fixed-seed weights")
    args = ap.parse_args(argv)

    from mxnet_tpu import serving

    if args.ckpt_dir:
        rt = serving.ModelRuntime.from_checkpoint(
            "demo", args.ckpt_dir, _demo_apply(),
            sample_shape=(16,), max_batch=args.max_batch)
    else:
        rt = serving.demo_runtime(max_batch=args.max_batch)
    srv = serving.ModelServer(max_batch=args.max_batch,
                              queue_max=args.queue_max,
                              batch_deadline_ms=args.batch_deadline_ms,
                              default_deadline_ms=args.deadline_ms)
    srv.add_model(rt)
    if args.slo_p99_ms is not None:
        out = serving.qps_at_slo(srv, rt.name,
                                 slo_p99_ms=args.slo_p99_ms)
    else:
        out = serving.run_load(srv, rt.name, qps=args.qps,
                               duration_s=args.duration)
    srv.drain()
    print(json.dumps(out, indent=2))
    return 0


def _demo_apply():
    def apply_fn(p, aux, x):
        import jax.numpy as jnp

        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return jnp.argmax(h @ p["w2"] + p["b2"], axis=-1)

    return apply_fn


if __name__ == "__main__":
    sys.exit(main())
