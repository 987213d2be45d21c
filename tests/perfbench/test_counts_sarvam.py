"""``perfbench/flops_sarvam.py`` against a hand count at the toy's
sizes (hidden 32, 2 heads of 16 + 8 over 16, latent 32, dense 64,
experts of 16, 4 held of 8, 2 a token, 1 shared, 1 dense + 2 expert
layers, vocabulary 512), and at the published widths against the
figures of the issue that added the configuration."""
import json
import os

import pytest

from perfbench import flops_sarvam as fs

import toy_sarvam_manifest

with open(os.path.join(toy_sarvam_manifest.TOY, "configs",
                       "sarvam_toy.json")) as f:
    TOY = json.load(f)
with open(os.path.join(toy_sarvam_manifest.ROOT, "perfbench", "configs",
                       "sarvam-105b-ep4.json")) as f:
    REAL = json.load(f)


@pytest.mark.parametrize("got, want", [
    (fs.cache_row_width(TOY), 32 + 8),
    (fs.cache_bytes_per_token(TOY), 3 * 40 * 2),
    # wq 32 x 48, wkv_a 32 x 40, wkv_b 32 x 64, wo 32 x 32
    (fs.attention_params(TOY), 1536 + 1280 + 2048 + 1024),
    (fs.expert_params(TOY), 3 * 32 * 16),
    (fs.expert_layers(TOY), 2),
    # attention 3 x 5,888; gains 3 x (32 + 32 + 32) + 32; the dense layer
    # 3 x 32 x 64; 2 x (shared 1,536 + router 256 + bias 8); head 512 x 32
    (fs.every_tick_params(TOY), 17664 + 320 + 6144 + 3600 + 16384),
    (fs.token_macs(TOY), 17664 + 6144 + 2 * (1536 + 256)),
    (fs.absorbed_core_macs(TOY), 2 * (40 + 32)),
    (fs.expanded_core_macs(TOY), 2 * (16 + 8 + 16)),
    (fs.head_macs(TOY), 512 * 32),
    # 10 tokens through the layers, 55 causal pairs in 3 layers, 7
    # assignments served, the head once
    (fs.prefill_flops(TOY, 10, 7.0),
     2 * (10 * 27392 + 3 * 80 * 55 + 7 * 1536 + 16384)),
    # 3 tokens with 6 + 7 + 40 tokens behind them, 5 assignments served
    (fs.decode_flops(TOY, [6, 7, 40], 5.0),
     2 * (3 * (27392 + 16384) + 3 * 144 * 53 + 5 * 1536)),
    (fs.latent_attn_bytes(TOY, [6, 7, 40]), 53 * 240),
    (fs.latent_attn_flops(TOY, [6, 7, 40]), 2 * 3 * 144 * 53),
    (fs.expert_bytes(TOY, 9.0), 9 * 1536 * 2),
])
def test_toy_counts_by_hand(got, want):
    assert got == want


def test_work_follows_the_programs_counter():
    routed = {"prefill_assignments_here": 30, "decode_assignments_here": 5,
              "experts_reached_sum": 9, "experts_reached": 1.5}
    w = fs.work(TOY, [10, 20], [6, 7, 40], routed)
    # the prompts share the served assignments by their lengths
    assert w["prefill_flops_each"] == [fs.prefill_flops(TOY, 10, 10.0),
                                       fs.prefill_flops(TOY, 20, 20.0)]
    assert w["decode_flops"] == fs.decode_flops(TOY, [6, 7, 40], 5.0)
    assert w["flops_done"] == sum(w["prefill_flops_each"]) \
        + w["decode_flops"] == w["prefill_flops"] + w["decode_flops"]
    assert w["tick_weight_bytes"] == 44112 * 2
    assert w["moe_expert_bytes"] == 9 * 1536 * 2
    assert w["moe_expert_flops"] == 2 * 5 * 1536
    assert w["latent_attn_bytes"] == 53 * 240
    assert w["decode_extra_bytes"] == 53 * 240 + 9 * 1536 * 2
    assert w["prefill_weight_bytes"] == (44112 + 2 * 4 * 1536) * 2
    assert w["moe_experts_reached_pct"] == 100.0 * 1.5 / 4
    # the cache's float32 in the toy's rehearsal doubles the bytes
    assert fs.work(TOY, [10], [6], routed, 4)["tick_weight_bytes"] \
        == 44112 * 4


def test_work_assumes_nothing_in_the_counters_place():
    # no routing read, no count: the expected share is nobody's fallback
    with pytest.raises(TypeError):
        fs.work(TOY, [10], [6, 7])
    with pytest.raises(KeyError):
        fs.work(TOY, [10], [6, 7], {"prefill_assignments_here": 20})
    idle = {"prefill_assignments_here": 0, "decode_assignments_here": 0,
            "experts_reached_sum": 0, "experts_reached": 0.0}
    w = fs.work(TOY, [], [], idle)
    assert w["flops_done"] == 0 and w["decode_extra_bytes"] == 0
    assert w["moe_experts_reached_pct"] == 0.0


def test_the_published_widths_give_the_issues_figures():
    assert fs.attention_params(REAL) == pytest.approx(94.64e6, rel=1e-3)
    assert fs.expert_params(REAL) == pytest.approx(25.17e6, rel=1e-3)
    # what every decode run reads: about 2.09 GB
    assert fs.every_tick_params(REAL) * 2 == pytest.approx(2.09e9, rel=5e-3)
    # three matrices of one expert: 50.3 MB
    assert fs.expert_bytes(REAL, 1.0) == pytest.approx(50.3e6, rel=1e-3)
    # a cached token: 1,152 B a layer, 5,760 B over five
    assert fs.cache_bytes_per_token(REAL) == 5760
    assert 128 * fs.cache_bytes_per_token(REAL) == 737280
    assert fs.absorbed_core_macs(REAL) == 64 * (576 + 512)
    # all the cut's parameters, embedding and routed experts with them
    every = fs.every_tick_params(REAL) + 65536 * 4096 \
        + 4 * 32 * fs.expert_params(REAL)
    assert every == pytest.approx(4535.4e6, rel=1e-4)
