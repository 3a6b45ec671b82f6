"""The cost functions against hand counts."""
import pytest

from benchmarks.harness import costs


def test_rk4_flops_per_twin_step():
    # 4 field evaluations x 2 operations x the multiply-adds of each layer
    assert costs.rk4_step_flops((6, 64, 64, 6)) == 4 * 2 * (
        6 * 64 + 64 * 64 + 64 * 6) == 38_912
    assert costs.rk4_step_flops((2, 14, 14, 1)) == 4 * 2 * (
        2 * 14 + 14 * 14 + 14 * 1) == 1_904


def test_fused_byte_model():
    # L96 serving window, 1024 rows x 600 steps at bf16 storage: y0 f32 in,
    # a (2T+1, 1) f32 zero drive column, bf16 weights in, bf16 slab out
    flops, nbytes = costs.fused_fwd_cost((6, 64, 64, 6), steps=600,
                                         rows=1024, precision="bf16_f32acc")
    weights = 6 * 64 + 64 + 64 * 64 + 64 + 64 * 6 + 6
    assert nbytes == 1024 * 6 * 4 + 1201 * 4 + weights * 2 + 600 * 1024 * 6 * 2
    assert flops == 1024 * 600 * 38_912
    bflops, bbytes = costs.fused_bwd_cost((6, 64, 64, 6), steps=600,
                                          rows=1024, precision="bf16_f32acc")
    assert bflops == 2 * flops
    assert bbytes == nbytes + 600 * 1024 * 6 * 2 + weights * 4 + 1024 * 6 * 4
    # per-twin drives carry one column per row
    _, hp = costs.fused_fwd_cost((2, 14, 14, 1), steps=64, rows=256,
                                 precision="bf16_f32acc", per_twin_drive=True)
    assert hp == (256 * 4 + 129 * 256 * 4 + (2 * 14 + 14 + 14 * 14 + 14
                  + 14 + 1) * 2 + 64 * 256 * 2)


def test_least_time_and_peaks():
    peak = costs.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = costs.least_time_s(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = costs.least_time_s(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
