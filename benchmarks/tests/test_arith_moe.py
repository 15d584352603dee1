"""The sparse-expert FLOP and byte functions against the hand counts of
``olmoe-1b-7b-d10``."""

import json
import os

import pytest

from benchmarks.lib import arith, arith_moe
from benchmarks.lib.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def m():
    with open(os.path.join(HERE, "..", "configs", "olmoe-1b-7b-d10.json")) as f:
        c = json.load(f)
    return {**c, **c["arith"]}


def test_layer_sizes_by_hand(m):
    # q, k, v, o of 2048 x 2048 (16 kv heads of 128: no grouping)
    assert arith_moe.layer_attn_params(m) == 4 * 2048 * 2048 == 16_777_216
    # gate, up, down of 2048 x 1024
    assert arith_moe.expert_params(m) == 3 * 2048 * 1024 == 6_291_456
    experts = 64 * arith_moe.expert_params(m)
    assert experts == 402_653_184                 # 805 MB in bf16
    assert experts * 2 == pytest.approx(805e6, rel=1e-3)
    router = 2048 * 64
    assert arith_moe.layer_params(m) == experts + 16_777_216 + router \
        == 419_561_472                            # 839 MB in bf16
    assert arith_moe.layer_params(m) * 2 == pytest.approx(839e6, rel=1e-3)
    # ten layers and the two 103 M-parameter tables: 8.80 GB
    tables = 2 * 2048 * 50304
    assert (10 * arith_moe.layer_params(m) + tables) * 2 == pytest.approx(
        8.80e9, rel=2e-3)
    # 2 (k, v) * 16 heads * 128 * 2 bytes * 10 layers = 80 KiB a token
    assert arith.kv_bytes_per_token(m) == 80 * 1024
    assert 768 * 64 * arith.kv_bytes_per_token(m) == 3.75 * 2 ** 30
    # what arith.py would count for this model: one expert a layer
    assert arith.layer_matmul_params(m) == 16_777_216 + 6_291_456


def test_step_by_hand(m):
    # one decode token of one sequence with 100 tokens seen: 101 keys
    per_token = 16_777_216 + 8 * 6_291_456 + 2048 * 64
    f = arith_moe.moe_step_flops(m, n_tokens=1, qk_pairs=101, logit_rows=1)
    assert f == 2 * 10 * per_token + 4 * 10 * 16 * 128 * 101 \
        + 2 * 2048 * 50304
    # one token touches 8 experts; 64 decode tokens touch all 64
    assert arith_moe.experts_touched(m, 1) == 8
    assert arith_moe.experts_touched(m, 7) == 56
    assert arith_moe.experts_touched(m, 64) == 64
    b = arith_moe.moe_step_bytes(m, n_tokens=1, ctx_tokens=101)
    assert b == (10 * (16_777_216 + 2048 * 64 + 8 * 6_291_456)
                 + 2048 * 50304) * 2 + 102 * 80 * 1024 + 2048 * 2
    b = arith_moe.moe_step_bytes(m, n_tokens=64, ctx_tokens=64 * 285)
    assert b == (10 * 419_561_472 + 2048 * 50304) * 2 \
        + (64 * 285 + 64) * 80 * 1024 + 64 * 2048 * 2
    # 64 decode tokens at 285 tokens of context: bound by the weights' read,
    # 0.98 ms a layer for the experts at 819 GB/s
    peaks = peaks_for("TPU v5 lite")
    f = arith_moe.moe_step_flops(m, 64, 64 * 285, 64)
    sec, which = arith.roofline_seconds(f, b, peaks)
    assert which == "memory"
    assert sec == pytest.approx(12.3e-3, rel=0.02)
    assert 402_653_184 * 2 / peaks["hbm_bytes_per_s"] == pytest.approx(
        0.983e-3, rel=1e-3)


def test_expert_gemm_by_hand(m):
    assert arith_moe.expert_gemm_flops(m, 64) == 2 * 64 * 10 * 8 * 6_291_456
    rows = 64 * 8
    assert arith_moe.expert_gemm_bytes(m, 64) == 10 * 2 * (
        402_653_184 + 3 * rows * (2048 + 1024))
    # the projections of a full 512-token step are still memory bound:
    # 4096 rows over 64 experts are 64 rows a weight
    peaks = peaks_for("TPU v5 lite")
    assert arith.roofline_seconds(arith_moe.expert_gemm_flops(m, 512),
                                  arith_moe.expert_gemm_bytes(m, 512),
                                  peaks)[1] == "memory"
