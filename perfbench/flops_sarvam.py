"""Operations and bytes of SERVING a block with latent attention and
expert layers, from the configuration file's (published) keys alone.

``flops.py``'s conventions: one multiply-add is TWO operations, nothing
recomputed is counted, and neither is what an implementation happens to
read or compute beyond what the algorithm needs.  Only the work DONE
HERE is counted: the routed experts at the assignments the held experts
actually served and the matrices of the held experts a decode tick
actually reached (the program's own counter, never an assumed share).
``tests/perfbench/test_counts_sarvam.py`` pins each function against a
hand count.
"""
from __future__ import annotations

from typing import Dict, List


def cache_row_width(cfg: Dict) -> int:
    """What a token caches a layer: the latent and the one rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def cache_bytes_per_token(cfg: Dict, itemsize: int = 2) -> float:
    return float(cfg["num_hidden_layers"] * cache_row_width(cfg) * itemsize)


def attention_params(cfg: Dict) -> float:
    """One layer's four attention matrices (no query bottleneck)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rp, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    r = cfg["kv_lora_rank"]
    return float(d * h * (nope + rp) + d * (r + rp) + r * h * (nope + dv)
                 + h * dv * d)


def expert_params(cfg: Dict) -> float:
    """One expert's three matrices; also ONE assignment's multiply-adds
    through them."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def every_tick_params(cfg: Dict) -> float:
    """What EVERY decode run reads whatever it routes: attention, the
    norms, the dense feed-forward, the shared experts, the routers and
    their selection biases, the final norm and the head.  Not the
    embedding (a row a rider) and not the routed experts."""
    d = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    norms = layers * (2 * d + cfg["kv_lora_rank"]) + d
    return (layers * attention_params(cfg) + norms
            + dense * 3.0 * d * cfg["intermediate_size"]
            + expert_layers(cfg) * (
                cfg["num_shared_experts"] * expert_params(cfg)
                + d * cfg["router_width"] + cfg["router_width"])
            + cfg["vocab_size"] * d)


def token_macs(cfg: Dict) -> float:
    """One token through every layer's projections, dense or shared
    feed-forward and router: everything but the attention core, the
    routed experts and the head.  Absorbed or not, the latent's second
    matrix costs a new token the same (``q_nope W_uk^T`` and ``u W_uv``
    against ``c W_kvb``)."""
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + cfg["first_k_dense_replace"] * 3.0 * d
            * cfg["intermediate_size"]
            + expert_layers(cfg) * (
                cfg["num_shared_experts"] * expert_params(cfg)
                + d * cfg["router_width"]))


def absorbed_core_macs(cfg: Dict) -> float:
    """One new token against ONE cached token in one layer, absorbed:
    a score over the row's ``kv_lora_rank + rope`` and a sum over its
    ``kv_lora_rank``, for every head."""
    return float(cfg["num_attention_heads"]
                 * (cache_row_width(cfg) + cfg["kv_lora_rank"]))


def expanded_core_macs(cfg: Dict) -> float:
    """The same in the expanded form a prefill runs: scores over ``nope
    + rope`` and a sum over ``v_head_dim``, for every head."""
    return float(cfg["num_attention_heads"]
                 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                    + cfg["v_head_dim"]))


def head_macs(cfg: Dict) -> float:
    return float(cfg["hidden_size"] * cfg["vocab_size"])


def prefill_flops(cfg: Dict, prompt_len: int, served: float) -> float:
    """One prompt's prefill: causal over itself in the expanded form,
    ``served`` assignments through held experts, the head on its last
    token."""
    n = float(prompt_len)
    core = cfg["num_hidden_layers"] * expanded_core_macs(cfg) \
        * n * (n + 1.0) / 2.0
    return 2.0 * (n * token_macs(cfg) + core
                  + served * expert_params(cfg) + head_macs(cfg))


def decode_flops(cfg: Dict, decode_reads: List[int],
                 served: float) -> float:
    """The window's decoded tokens, each over the tokens behind it in
    the absorbed form, ``served`` assignments through held experts in
    all, the head on every one."""
    tokens = float(len(decode_reads))
    core = cfg["num_hidden_layers"] * absorbed_core_macs(cfg) \
        * float(sum(decode_reads))
    return 2.0 * (tokens * (token_macs(cfg) + head_macs(cfg)) + core
                  + served * expert_params(cfg))


def latent_attn_bytes(cfg: Dict, decode_reads: List[int],
                      itemsize: int = 2) -> float:
    """The live latent rows, read once a tick a layer."""
    return float(sum(decode_reads)) * cache_bytes_per_token(cfg, itemsize)


def latent_attn_flops(cfg: Dict, decode_reads: List[int]) -> float:
    return 2.0 * cfg["num_hidden_layers"] * absorbed_core_macs(cfg) \
        * float(sum(decode_reads))


def expert_bytes(cfg: Dict, reached: float, itemsize: int = 2) -> float:
    """Three matrices for each held expert a tick reached, summed over
    ticks and layers (``reached``)."""
    return reached * expert_params(cfg) * itemsize


def work(cfg: Dict, prefilled: List[int], decode_reads: List[int],
         routed: Dict, itemsize: int = 2) -> Dict:
    """The counters ``drivers/serve_lm.py`` and the readers ask for.
    ``routed`` is the program's count over the same window
    (``GenerationRuntime.routing_counters``): assignments served here
    by prompt and by decoded tokens, and the held experts the decode
    ticks reached.  Nothing is assumed in its place: a window whose
    routing was not read has no count."""
    held, layers = len(cfg["held_experts"]), expert_layers(cfg)
    prefill_served = float(routed["prefill_assignments_here"])
    decode_served = float(routed["decode_assignments_here"])
    reached = float(routed["experts_reached_sum"])
    tokens = max(sum(prefilled), 1)
    each = [prefill_flops(cfg, n, prefill_served * n / tokens)
            for n in prefilled]
    decode = decode_flops(cfg, decode_reads, decode_served)
    tick = every_tick_params(cfg) * itemsize
    attn_bytes = latent_attn_bytes(cfg, decode_reads, itemsize)
    moe_bytes = expert_bytes(cfg, reached, itemsize)
    return {
        "prefill_flops_each": each,
        "prefill_flops": sum(each),
        "decode_flops": decode,
        "flops_done": sum(each) + decode,
        "tick_weight_bytes": tick,
        "decode_extra_bytes": attn_bytes + moe_bytes,
        # a prompt of 512 tokens or more leaves 4,096 assignments a
        # layer: it reaches every held expert
        "prefill_weight_bytes": tick + layers * held
        * expert_params(cfg) * itemsize,
        "latent_attn_bytes": attn_bytes,
        "latent_attn_flops": latent_attn_flops(cfg, decode_reads),
        "moe_expert_bytes": moe_bytes,
        "moe_expert_flops": 2.0 * decode_served * expert_params(cfg),
        "moe_experts_reached_pct": 100.0 * routed["experts_reached"] / held,
    }
