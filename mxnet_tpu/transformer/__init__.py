"""mxnet_tpu.transformer — the transformer-LM workload tier.

Decoder-only LM training with pluggable attention (single-chip flash /
ring / Ulysses sequence parallelism), ZeRO-1 sharded optimizer state
over the dp mesh axis, per-block remat policies, and a synthetic
tokenized stream on the io.py iterator contract so the checkpoint /
chaos / flight-recorder stack applies unchanged.  See README
"Transformer workload" and ROADMAP item 4.
"""
from .data import LMTokenIter, make_corpus
from .model import (ATTENTION_IMPLS, RopeYarn, TransformerConfig, apply,
                    apply_decode, apply_prefill, attention_impl,
                    dense_causal_attn, frozen_names, gather_kv,
                    init_params, lm_loss, loss_and_aux, make_attn_fn,
                    param_shapes)
from .train import TransformerTrainStep

__all__ = [
    "ATTENTION_IMPLS", "RopeYarn", "TransformerConfig",
    "TransformerTrainStep", "frozen_names", "loss_and_aux",
    "LMTokenIter", "make_corpus", "apply", "apply_decode",
    "apply_prefill", "attention_impl", "dense_causal_attn",
    "gather_kv", "init_params", "lm_loss", "make_attn_fn",
    "param_shapes",
]
