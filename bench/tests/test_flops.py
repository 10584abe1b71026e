"""Operation and byte counts against hand counts, and the peaks table."""
import pytest

from bench import flops, peaks

MAMBA2_1P3B = {"d_model": 2048, "n_layers": 48, "d_state": 128,
               "ssm_headdim": 64, "ssm_expand": 2, "ssm_n_groups": 1,
               "conv_kernel": 4}


def test_mamba2_matmul_params_by_hand():
    # one layer: in-projections 2048 x (2*4096 + 2*128 + 64) = 17,432,576
    # and out-projection 4096 x 2048 = 8,388,608
    per_layer = 17_432_576 + 8_388_608
    head = 2048 * 50304 + 2048          # LM head and value head
    assert flops.mamba2_matmul_params(MAMBA2_1P3B, 50304) == \
        48 * per_layer + head
    cut = dict(MAMBA2_1P3B, n_layers=16)
    assert flops.mamba2_matmul_params(cut, 50304) == 16 * per_layer + head


def test_mamba2_token_and_step_flops_by_hand():
    n = flops.mamba2_matmul_params(MAMBA2_1P3B, 50304)
    conv = 2 * 4 * (4096 + 256)          # depthwise conv over x, B, C
    scan = 5 * 64 * 64 * 128             # decay, outer product, read-out
    per_token = 2 * n + 48 * (conv + scan)
    assert flops.mamba2_token_flops(MAMBA2_1P3B, 50304) == per_token
    assert flops.lm_ppo_step_flops(MAMBA2_1P3B, 50304, 16, 64) == \
        4 * per_token * 16 * 64


def test_ssd_op_by_hand():
    f, b = flops.ssd_op(2, 3, 4, 5, 1, 6)
    assert f == 5 * 2 * 3 * 4 * 5 * 6
    # bf16 x, y (2*3*4*5 each) and B, C (2*3*1*6 each);
    # f32 dt (2*3*4), A (4) and final state (2*4*5*6)
    assert b == 2 * (2 * 120 + 2 * 36) + 4 * (24 + 4 + 240)


def test_peaks_and_roofline():
    p = peaks.peak("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    t, bound = peaks.roofline_seconds(197e12, 1.0, "TPU v5 lite")
    assert (t, bound) == (1.0, "compute")
    t, bound = peaks.roofline_seconds(1.0, 819e9, "TPU v5 lite")
    assert (t, bound) == (1.0, "memory")
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
