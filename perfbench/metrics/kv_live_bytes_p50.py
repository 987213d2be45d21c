"""Median of the bytes of cache blocks held by live sequences
(``PagedKVCache.stats()["blocks_live"]`` times a block's bytes over all
layers), read by the driver's clock ten times a second while the window
is open: how much cache the traffic really fills, beside
``memory_peak_bytes``, which only says how large the pools are."""
from perfbench.loadgen import percentile


def read(ctx):
    held = ctx["counters"].get("kv_live_bytes")
    return percentile(held, 0.50) if held else None
