"""Unit tests for the HLO-parsing roofline machinery (trip-count multipliers,
collective byte accounting, dot FLOP counting)."""
import pytest

from repro.launch import hlo_stats as H

SYNTH = """HloModule test

%body.1 (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %w = f32[16,32]{1,0} parameter(1)
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %dot.1 = f32[8,32]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[8,64]{1,0} all-gather(%x), replica_groups={}, dimensions={1}
  ROOT %t = (s32[], f32[8,16]) tuple(%p)
}

%cond.1 (p: (s32[], f32[8,16])) -> pred[] {
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %b = f32[16,8]{1,0} parameter(1)
  %dot.0 = f32[8,8]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%a), to_apply=%add
  %while.1 = (s32[], f32[8,16]) while(%t0), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%while.1), index=1
}
"""


def test_collective_trip_count_multiplier():
    stats = H.collective_stats(SYNTH)
    # all-reduce in entry: 8*16*4 = 512 B; all-gather in body x5 trips:
    # 8*64*4 * 5 = 10240 B
    assert stats.bytes_by_kind["all-reduce"] == 512
    assert stats.bytes_by_kind["all-gather"] == 8 * 64 * 4 * 5
    assert stats.count_by_kind["all-gather"] == 5


def test_dot_flops_trip_count():
    s = H.hlo_compute_stats(SYNTH)
    # entry dot: 2*8*8*16 = 2048; body dot x5: 2*8*32*16*5 = 40960
    assert s["dot_flops"] == 2048 + 40960


def test_async_collectives_counted_once():
    text = """HloModule m
ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4]{1,0} parameter(0)
  %ag-s = f32[4,8]{1,0} all-gather-start(%a), dimensions={1}
  %ag-d = f32[4,8]{1,0} all-gather-done(%ag-s)
  ROOT %r = f32[4,4]{1,0} copy(%a)
}
"""
    stats = H.collective_stats(text)
    assert stats.count_by_kind.get("all-gather", 0) == 1
    assert stats.bytes_by_kind["all-gather"] == 4 * 8 * 4


def test_decode_per_token_stats_divides_by_batch():
    # SYNTH totals: dot flops 2048 + 40960; dot bytes 1280 + 3584*5 = 19200;
    # collective bytes 512 + 10240 = 10752.  A decode step advances every
    # batch row by one token, so per-token = total / batch.
    pt = H.decode_per_token_stats(SYNTH, 4)
    assert pt["dot_flops_per_token"] == pytest.approx((2048 + 40960) / 4)
    assert pt["dot_bytes_per_token"] == pytest.approx(19200 / 4)
    assert pt["collective_bytes_per_token"] == pytest.approx(10752 / 4)


def test_decode_per_token_stats_batch_one_is_totals():
    pt = H.decode_per_token_stats(SYNTH, 1)
    s = H.hlo_compute_stats(SYNTH)
    assert pt["dot_flops_per_token"] == s["dot_flops"]
    assert pt["dot_bytes_per_token"] == s["dot_bytes"]


def test_decode_per_token_stats_rejects_bad_batch():
    with pytest.raises(ValueError, match="batch must be >= 1"):
        H.decode_per_token_stats(SYNTH, 0)


def test_roofline_terms_dominance():
    t = H.roofline_terms(
        flops=197e12, bytes_accessed=819e9, collective_bytes=100e9, chips=1,
        device_kind="TPU v5 lite",
    )
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(2.0)
    assert t["dominant"] == "collective"


def test_shape_bytes_dtype_table():
    assert H._shape_bytes("bf16[2,3]") == 12
    assert H._shape_bytes("f32[10] s8[4]") == 44
    assert H._shape_bytes("pred[8]") == 8


def test_peaks_unknown_device_kind_raises():
    assert H.peaks("TPU v5 lite")["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        H.peaks("cpu")
    with pytest.raises(KeyError):
        H.roofline_terms(flops=1.0, bytes_accessed=1.0, collective_bytes=0.0,
                         chips=1, device_kind="TPU v9000")
