"""Counts from shapes, against numbers worked by hand at the cells' shapes."""
import math

import pytest

from bench import counts

V5E = counts.load_peaks("TPU v5 lite")


def test_peaks_table_is_keyed_by_device_kind():
    assert V5E == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                   "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError, match="not in"):
        counts.load_peaks("TPU v9 imaginary")


def test_phase1_polyeval_of_the_up_projection():
    # W's shares: [21, 6] @ [8, 6, 3317760]; 3317760 = 2304 * 5760 / 4
    call = counts.call_from_operands((21, 6), (8, 6, 3317760))
    assert call == counts.MatmulCall(8, 21, 6, 3317760, a_batched=False, b_batched=True)
    assert call.field_macs == 8 * 21 * 6 * 3317760 == 3_344_302_080
    # a once (126) + b (8*6*3317760 = 159_252_480) + out (8*21*3317760 =
    # 557_383_680) = 716_636_286 elements at 2 bytes
    assert call.least_bytes == 1_433_272_572
    t, bound = counts.least_time_s(call, V5E)
    assert bound == "memory"
    assert t == pytest.approx(1_433_272_572 / 819e9)  # 1.75 ms
    assert 2 * 3_344_302_080 / 197e12 < t  # compute bound 34 us


def test_worker_multiply_unpads_to_the_logical_product():
    # up: launched as [168, 8, 1152] @ [168, 1152, 2944]; N = 2880 in 128s
    logical = counts.worker_product(2304, 5760, 16, 2, 2)
    assert logical == (8, 1152, 2880)
    launched = counts.call_from_operands((168, 8, 1152), (168, 1152, 2944))
    call = counts.unpad(launched, [(21, 6, 9216), logical])
    assert call == counts.MatmulCall(168, 8, 1152, 2880)
    assert call.field_macs == 4_459_069_440
    # 168*8*1152 + 168*1152*2880 + 168*8*2880 = 562_802_688 elements
    assert call.least_bytes == 2 * 562_802_688
    assert counts.least_time_s(call, V5E)[1] == "memory"
    # a call no logical product explains is counted as launched
    assert counts.unpad(launched, []) == launched
    assert counts.unpad(launched, [(8, 1152, 2304)]) == launched


def test_down_projection_worker_multiply():
    # 4 rows tall; K = 2880 padded to 256s, N = 576 to 128s
    logical = counts.worker_product(5760, 2304, 16, 2, 4)
    assert logical == (4, 2880, 576)
    launched = counts.call_from_operands((416, 4, 3072), (416, 3072, 640))
    assert counts.unpad(launched, [logical]) == counts.MatmulCall(416, 4, 2880, 576)
    # the polyevals' short contraction is no padded worker multiply
    polyeval = counts.call_from_operands((52, 10), (8, 10, 11520))
    assert counts.unpad(polyeval, [logical]) == polyeval


def test_degree_reduce_is_unbatched():
    call = counts.call_from_operands((21, 17), (17, 184320))
    assert call == counts.MatmulCall(1, 21, 17, 184320, False, False)
    assert call.least_bytes == 2 * (21 * 17 + 17 * 184320 + 21 * 184320)


def test_shapes_that_do_not_form_a_product():
    assert counts.call_from_operands((4, 5), (6, 7)) is None
    assert counts.call_from_operands((2, 4, 5), (3, 5, 7)) is None
    assert counts.call_from_operands((5,), (5, 7)) is None


def test_request_flops_of_one_up_projection_request():
    assert counts.request_flops(16, 2304, 5760) == 2 * 16 * 2304 * 5760 == 424_673_280
    # one row of rows_per_s at 197 TFLOP/s
    assert counts.request_flops(1, 2304, 5760) / 197e12 == pytest.approx(1.3473e-7, rel=1e-4)


def test_percentile_matches_linear_interpolation():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert counts.percentile(xs, 50) == 3.0
    assert counts.percentile(xs, 90) == pytest.approx(4.6)
    assert math.isnan(counts.percentile([], 50))
