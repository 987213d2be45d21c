"""Operations and bytes of a block with latent attention, expert
layers, hyper-connected streams and a multi-token module, from the
configuration file's (published) keys alone.

``flops.py``'s conventions: one multiply-add is TWO operations, nothing
recomputed is counted, and only the work DONE HERE is: the routed
experts at the share of assignments that the held experts expect
(``held / router_width``), or, for the expert kernel's roofline, at the
assignments a run actually served.  ``tests/perfbench/
test_counts_moe.py`` pins each function against a hand count.
"""
from __future__ import annotations

from typing import Dict


def attention_proj_macs(cfg: Dict) -> float:
    """One token through one layer's five latent projections."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rp, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return float(d * rq + rq * h * (nope + rp) + d * (rkv + rp)
                 + rkv * h * (nope + dv) + h * dv * d)


def attention_core_macs(cfg: Dict, attended: float) -> float:
    """One token's scores and weighted values over ``attended`` keys."""
    return float(cfg["num_attention_heads"] * attended
                 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                    + cfg["v_head_dim"]))


def stream_macs(cfg: Dict) -> float:
    """One token through ONE sublayer's maps and mixing: the map
    projection, the weighted sum in, the stream-to-stream product and
    the weighted write out (Sinkhorn's 4x4 arithmetic is left out)."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    return float(n * d * (2 * n + n * n) + n * d + n * n * d + n * d)


def expert_macs(cfg: Dict) -> float:
    """ONE assignment through one routed expert's three products."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layer_macs(cfg: Dict) -> float:
    """One token through an expert layer's feed-forward as computed
    here: the router over all experts, the shared expert, and the held
    experts at their expected share of the token's assignments."""
    d = cfg["hidden_size"]
    share = len(cfg["held_experts"]) / cfg["router_width"]
    return (d * cfg["router_width"]
            + cfg["n_shared_experts"] * expert_macs(cfg)
            + cfg["num_experts_per_tok"] * share * expert_macs(cfg))


def forward_macs_per_token(cfg: Dict, attended: float) -> float:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    layer = attention_proj_macs(cfg) + attention_core_macs(cfg, attended) \
        + 2.0 * stream_macs(cfg)
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    mtp = cfg["num_nextn_predict_layers"]
    total = cfg["num_hidden_layers"] * layer \
        + dense * 3.0 * d * cfg["intermediate_size"] \
        + sparse * expert_layer_macs(cfg) + d * v
    total += mtp * (2.0 * d * d + layer + expert_layer_macs(cfg) + d * v)
    return total


def train_step_flops(cfg: Dict, batch: int, seq: int) -> float:
    """Forward and backward (two products the size of the forward one)
    of one step, causal at length ``seq``."""
    return 3.0 * 2.0 * batch * seq * forward_macs_per_token(
        cfg, (seq + 1) / 2.0)


def expert_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] \
        + cfg["num_nextn_predict_layers"]


def expert_step_flops(cfg: Dict, assignments: float) -> float:
    """The grouped products of a step's ``assignments`` actually served
    by held experts (all expert layers together): forward, and twice
    that backward."""
    return 3.0 * 2.0 * assignments * expert_macs(cfg)


def expert_step_bytes(cfg: Dict, assignments: float, steps: int = 1,
                      weight_bytes: int = 4, act_bytes: int = 2) -> float:
    """The least the grouped products move over ``steps`` steps: every
    held expert's three matrices read forward and backward and their
    gradient written (``weight_bytes`` each, in every expert layer),
    and a hidden-size row read and written forward, two read and one
    written backward, twice (input and output side), for each
    assignment; the expert-width intermediates are not counted."""
    weights = expert_layers(cfg) * len(cfg["held_experts"]) \
        * expert_macs(cfg) * weight_bytes * 3.0 * steps
    rows = assignments * cfg["hidden_size"] * act_bytes * 6.0
    return weights + rows
