"""Scheduled-HLO overlap measurement (parallel/overlap.py).  The parser
must handle both schedule shapes:

* async ``all-reduce-start``/``done`` pairs with compute in flight —
  overlap credited for the flops scheduled between them;
* the sync combined all-reduce this toolchain's TPU schedule actually
  emits — overlap 0, bytes still accounted.
"""
from mxnet_tpu.parallel.overlap import schedule_overlap_from_text

_ASYNC_HLO = """
HloModule test

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%fused_matmul (p0: f32[128,128], p1: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128] parameter(0)
  %p1 = f32[128,128] parameter(1)
  ROOT %d = f32[128,128] dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main (x: f32[128,128], g: f32[1000000]) -> f32[128,128] {
  %x = f32[128,128] parameter(0)
  %g = f32[1000000] parameter(1)
  %ar = f32[1000000] all-reduce-start(%g), to_apply=%add.1
  %mm = f32[128,128] fusion(%x, %x), kind=kOutput, calls=%fused_matmul
  %done = f32[1000000] all-reduce-done(%ar)
  ROOT %out = f32[128,128] add(%mm, %mm)
}
"""

_SYNC_HLO = """
HloModule test

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (g: f32[1000000]) -> f32[1000000] {
  %g = f32[1000000] parameter(0)
  ROOT %ar = f32[1000000] all-reduce(%g), to_apply=%add.1
}
"""


def test_async_pair_credits_inflight_flops():
    # 4 MB at 45 GB/s ring (n=8): t_comm = 2*(7/8)*4e6/45e9 = 155.6 us.
    # dot flops = 2*128^3 = 4.19 MFLOP; at 1 GFLOP/s rate that is
    # 4.19 ms of hiding -> fully hidden, overlap 1.0.
    out = schedule_overlap_from_text(_ASYNC_HLO, achieved_flops=1e9,
                                     ici_GBps=45.0, n_devices=8)
    assert out["n_async_pairs"] == 1
    assert out["async_bytes"] == 4000000
    assert abs(out["hidden_flops"] - 2 * 128 ** 3) < 1
    assert out["overlap_measured"] == 1.0

    # at an enormous achieved rate the same flops hide almost nothing
    out2 = schedule_overlap_from_text(_ASYNC_HLO, achieved_flops=1e18,
                                      ici_GBps=45.0, n_devices=8)
    assert out2["overlap_measured"] < 0.01


def test_sync_allreduce_hides_nothing():
    out = schedule_overlap_from_text(_SYNC_HLO, achieved_flops=1e12)
    assert out["n_async_pairs"] == 0
    assert out["n_sync_allreduce_bytes"] == 4000000
    assert out["overlap_measured"] == 0.0
