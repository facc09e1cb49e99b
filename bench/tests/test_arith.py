"""The benchmark's operation and byte counts, and its peak table."""

import json
import os

import pytest

from bench import arith

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*path):
    with open(os.path.join(ROOT, "bench", *path)) as f:
        return json.load(f)


def test_mistral_chip_flops_per_token():
    cfg = load("configs", "mistral-7b-chip.json")
    # 2 layers x (41,943,040 attention + 176,160,768 SwiGLU) + the
    # 4096 x 4000 head
    assert arith.dense_lm_matmul_params(cfg) == 452_591_616
    # 6 x 452.6 M + 2 layers x 3 x 2 x 4096 x 32 x 128 = 2.92 GFLOP a token
    assert arith.dense_lm_train_flops_per_token(cfg, 4096) == 2_916_876_288


def test_tiny_dense_flops_per_token():
    cfg = load("tests", "fixtures", "tiny-dense.json")
    # 2 layers x (12,288 attention + 24,576 SwiGLU) + the 64 x 256 head
    assert arith.dense_lm_matmul_params(cfg) == 90_112
    assert arith.dense_lm_train_flops_per_token(cfg, 64) == 589_824


def test_round_bytes_and_flops():
    # m=50 reports of d=100 f32 read once, the (d,) aggregate written once
    assert arith.gd_round_bytes(50, 100) == (50 * 100 + 100) * 4 == 20_400
    assert arith.gd_round_kernel_flops(50, 100) == 10_000
    assert arith.linreg_round_flops(50_000, 100) == 20_000_000


def test_roofline_names_its_bound():
    peak = arith.peaks("TPU v5 lite")
    t, bound = arith.roofline_seconds(10_000, 20_400, peak)
    assert bound == "memory" and t == pytest.approx(20_400 / 819e9)
    t, bound = arith.roofline_seconds(1e15, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in"):
        arith.peaks("TPU v9 imaginary")
