"""Operations and bytes a cell's work needs, from its shapes alone.

One multiply-add counts as TWO floating-point operations (the
convention of the peaks in ``peaks.json``).  Recomputation is never
counted, and neither is what an implementation happens to read or
compute beyond what the algorithm needs.  ``tests/perfbench/
test_counts.py`` pins each function against a hand count.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple


# ---------------------------------------------------------------------
# ResNet v1 (bottleneck), as the configuration file describes it
# ---------------------------------------------------------------------
def _out(size: int, kernel: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - kernel) // stride + 1


def resnet_convs(cfg: Dict) -> Iterator[Tuple[int, int, int, int, int]]:
    """Every convolution of the network as ``(c_in, c_out, kernel,
    h_out, w_out)``, in forward order.  The stride of a bottleneck sits
    on its first 1x1 convolution (the reference zoo's v1 layout)."""
    size = _out(cfg["image_size"], 7, 2, 3)
    c_in = cfg["channels"][0]
    yield cfg["image_channels"], c_in, 7, size, size
    size = _out(size, 3, 2, 1)                       # max pool
    for stage, (units, c_out) in enumerate(zip(cfg["layers"],
                                               cfg["channels"][1:])):
        for unit in range(units):
            stride = 2 if (stage > 0 and unit == 0) else 1
            mid = c_out // 4
            size = _out(size, 1, stride, 0)
            yield c_in, mid, 1, size, size
            yield mid, mid, 3, size, size
            yield mid, c_out, 1, size, size
            if unit == 0:
                yield c_in, c_out, 1, size, size     # downsample path
            c_in = c_out


def resnet_forward_flops(cfg: Dict) -> float:
    """Forward FLOPs of ONE image: convolutions and the classifier.
    BatchNorm, ReLU, pooling and the loss are left out (under 1 %)."""
    total = 0.0
    for c_in, c_out, k, h, w in resnet_convs(cfg):
        total += 2.0 * c_in * c_out * k * k * h * w
    total += 2.0 * cfg["channels"][-1] * cfg["classes"]
    return total


def resnet_train_step_flops(cfg: Dict, batch: int) -> float:
    """Forward + backward of one step: the backward pass computes a
    gradient for the input and one for the weights of every layer, two
    products the size of the forward one."""
    return 3.0 * batch * resnet_forward_flops(cfg)


# ---------------------------------------------------------------------
# dense decoder-only transformer (tied head, no biases)
# ---------------------------------------------------------------------
def transformer_params(cfg: Dict) -> float:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4.0 * d * d + 2.0 * d * f + 2.0 * d
    return (cfg["vocab_size"] * d + cfg["num_hidden_layers"] * per_layer
            + d)


def transformer_layer_forward_flops(cfg: Dict, tokens: int,
                                    attended: float) -> float:
    """One layer over ``tokens`` new tokens whose queries attend, on
    average, to ``attended`` keys each (causal training at length T:
    (T + 1) / 2)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    matmuls = 2.0 * tokens * (3 * d * d + d * d + 2 * d * f)
    attention = 2.0 * 2.0 * tokens * attended * d    # QK^T and PV
    return matmuls + attention


def transformer_forward_flops(cfg: Dict, tokens: int, attended: float,
                              head_tokens: int = None) -> float:
    """Forward FLOPs for ``tokens`` tokens; the vocabulary head is
    applied to ``head_tokens`` of them (all, in training; the last of
    each prompt, in a prefill; one a sequence, in a decode tick)."""
    if head_tokens is None:
        head_tokens = tokens
    layers = cfg["num_hidden_layers"] * transformer_layer_forward_flops(
        cfg, tokens, attended)
    head = 2.0 * head_tokens * cfg["hidden_size"] * cfg["vocab_size"]
    return layers + head


def transformer_train_step_flops(cfg: Dict, batch: int, seq: int) -> float:
    return 3.0 * transformer_forward_flops(cfg, batch * seq,
                                           (seq + 1) / 2.0)


def kv_bytes_per_token(cfg: Dict, itemsize: int = 2) -> float:
    """Keys and values of one token over all layers."""
    return 2.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * itemsize


def prefill_flops(cfg: Dict, prompt_len: int) -> float:
    """One prompt's prefill: causal over itself, head on its last
    token."""
    return transformer_forward_flops(cfg, prompt_len,
                                     (prompt_len + 1) / 2.0,
                                     head_tokens=1)
