"""The operation and byte counts against hand counts at small sizes."""
import pytest

import counts
from reference import rwkv6

PEAK = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}


def test_smw_cost_by_hand():
    # d = 4: F v is 2*16, v.u is 2*4, g F + c u u^T is 3*16 -> 88 FLOPs;
    # F read and written in bf16 (2 * 16 * 2) plus v in float32 (16) -> 80
    assert counts.smw_cost(4, 1, 2) == (88.0, 80.0)
    assert counts.smw_cost(4, 3, 2) == (264.0, 240.0)


def test_precond_cost_by_hand():
    # R (2x2) G (2x3) L (3x3): 2*2*2*3 + 2*2*3*3 = 60 FLOPs; bytes of R, L
    # (4 + 9 bf16 values) and of G and the output (6 + 6 bf16 values)
    assert counts.precond_cost(2, 3, 1, 2, 2) == (60.0, 50.0)


def test_least_time_names_the_bound():
    assert counts.least_time(1000.0, 10.0, PEAK) == (10.0, "compute")
    assert counts.least_time(100.0, 100.0, PEAK) == (10.0, "memory")


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("TPU v0")
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_rwkv6_flops_by_hand():
    sz = {"d_model": 64, "d_ff": 128, "vocab": 10, "n_layers": 1}
    # r, k, v, g, o and receptance: 6 * 64^2; key, value: 2 * 64 * 128;
    # LoRAs: 64 * (2 * 5 * 32 + 2 * 64); head 640; wkv 5 * 64 * 64 * 3
    per_layer = 6 * 64 * 64 + 2 * 64 * 128 + 64 * (320 + 128)
    assert rwkv6.flops_per_token(sz, 7) == \
        6.0 * (per_layer + 640) + 3.0 * 5 * 64 * 64
