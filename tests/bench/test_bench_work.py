"""Work functions against hand counts."""
import pytest

from bench.work import decode_step, fused_adapter, least_seconds, \
    mask_aggregate

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_mask_aggregate_counts():
    # one admission of 2 profiles at qwen1.5-0.5b: 2 x 24 rows of [1024, 64]
    # each summing 50 bank rows
    f, b = mask_aggregate.work(48, 50, 1024, 64)
    assert f == 2 * 48 * 50 * 1024 * 64
    assert b == 48 * 50 * 1024 * 64 * 2 + 48 * 1024 * 64 * 4 + 48 * 50 * 8
    # memory bound by far: ~0.4 FLOP per byte
    assert least_seconds(f, b, PEAKS) == pytest.approx(b / 819e9)


def test_fused_adapter_counts():
    f, b = fused_adapter.work(16, 1, 1024, 64)
    assert f == 4 * 16 * 1024 * 64 + 16 * 1024
    assert b == 2 * 16 * 1024 * 2 + 2 * 16 * 1024 * 64 * 2 + 2 * 16 * 64 * 4
    # at T=1 the per-row adapters dominate the bytes
    assert 2 * 16 * 1024 * 64 * 2 / b > 0.9


def test_decode_step_counts_qwen():
    s = {"L": 24, "d": 1024, "H": 16, "KV": 16, "hd": 64, "ff": 2816,
         "V": 151936, "b": 64}
    layer = 4 * 1024 * 1024 + 3 * 1024 * 2816 + 2 * 1024 * 64
    params = 24 * layer + 1024 * 151936
    assert decode_step.matmul_params(s) == params
    # ~464M matmul parameters: 0.93 GFLOP per token before attention
    assert 0.92e9 < 2 * params < 0.94e9
    att = 4 * 24 * 16 * 64 * 500
    assert decode_step.flops_per_token(s, 500) == 2 * params + att


def test_least_seconds_takes_the_larger_bound():
    assert least_seconds(197e12, 1.0, PEAKS) == pytest.approx(1.0)
    assert least_seconds(1.0, 819e9, PEAKS) == pytest.approx(1.0)


def test_gang_step_counts_qwen():
    from bench.work import gang_step

    s = {"L": 24, "d": 1024, "H": 16, "KV": 16, "hd": 64, "ff": 2816,
         "V": 151936, "N": 256, "b": 64}
    M = decode_step.matmul_params(s)
    T = 256
    attn = 2 * 24 * 16 * 64 * T
    per_token = (2 * M + attn) + (2 * M + 2 * attn + 4 * 24 * 1024 * 64)
    per_example = 8 * 24 * 256 * 1024 * 64
    assert gang_step.flops(s, 8, T) == 8 * (T * per_token + per_example)
    # ~1.9 GFLOP per token: forward and the backward to the activations
    assert 1.9e9 < gang_step.flops_per_token(s, T) < 1.92e9
