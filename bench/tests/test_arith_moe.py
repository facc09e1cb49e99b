"""The operation and byte counts of the DeepSeek-V3-style cells."""

import json
import os

import pytest

from bench import arith_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*path):
    with open(os.path.join(ROOT, "bench", *path)) as f:
        return json.load(f)


def test_moonlight_chip_counts():
    cfg = load("configs", "moonlight-16b-a3b-chip.json")
    # q 2048 x 16 x 192, latent and rotary key 2048 x 576, keys and values
    # 512 x 16 x 256, output 2048 x 2048
    assert arith_moe.latent_attention_matmul_params(cfg) == 13_762_560
    # top-6 x 8 held / 64 routed over
    assert arith_moe.routed_assignments_per_token(cfg) == 0.75
    # 5 x 13,762,560 attention + 69,206,016 dense SwiGLU + 4 x (131,072
    # router + 17,301,504 shared + 0.75 x 8,650,752 routed) + the
    # 2048 x 20480 head
    assert arith_moe.moe_lm_matmul_params(cfg) == 275_644_416
    # 6 x 275.6 M + 5 layers x 3 x 8192 x 16 x (192 + 128)
    assert arith_moe.moe_lm_train_flops_per_token(cfg, 8192) == 2_283_012_096
    # about 74.8 TFLOP a step of 4 x 8192 tokens
    assert 4 * 8192 * 2_283_012_096 == pytest.approx(74.81e12, rel=1e-4)


def test_expert_matmul_work_at_the_cut():
    cfg = load("configs", "moonlight-16b-a3b-chip.json")
    rows = 6144            # 8192 tokens x 6 x 8 / 64, one layer of a group
    flops = arith_moe.expert_matmul_flops(cfg, rows)
    assert flops == 18 * 2048 * 1408 * rows == 318_901_321_728
    nbytes = arith_moe.expert_matmul_bytes(cfg, rows, calls=1)
    # rows: 2 B x (6 x 2048 + 9 x 1408); weights: 8 experts' three
    # matrices read twice and their gradients written once
    assert nbytes == 2 * (6 * 2048 + 9 * 1408) * rows \
        + 18 * 8 * 2048 * 1408
    # compute-bound on a v5e: 1.62 ms of flops against 0.88 ms of bytes
    assert flops / 197e12 > nbytes / 819e9


def test_tiny_counts():
    cfg = load("tests", "fixtures", "tiny-mla-moe.json")
    attn = 64 * 4 * 24 + 64 * 24 + 16 * 4 * 24 + 4 * 8 * 64
    assert arith_moe.latent_attention_matmul_params(cfg) == attn
    expert_layer = 64 * 8 + 3 * 64 * 64 + 3 * 2 / 8 * 3 * 64 * 32
    assert arith_moe.moe_lm_matmul_params(cfg) == \
        3 * attn + 3 * 64 * 128 + 2 * expert_layer + 64 * 256
