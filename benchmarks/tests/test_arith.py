"""FLOP and byte functions against hand counts, both model families."""

import json
import os

import pytest

from benchmarks.lib import arith
from benchmarks.lib.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))


def cfg(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        c = json.load(f)
    return {**c, **c["arith"]}


def test_mistral_counts_by_hand():
    m = cfg("mistral-7b-d16")
    # q 4096*4096, k and v 4096*1024 each, o 4096*4096; three 4096*14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert arith.layer_matmul_params(m) == layer == 218_103_808
    assert arith.matmul_params(m) == 16 * layer + 4096 * 32000
    # + embedding, two RMS norms a layer and the final one
    assert arith.param_count(m) == 16 * layer + 2 * 4096 * 32000 \
        + 16 * 2 * 4096 + 4096
    assert arith.param_count(m) == pytest.approx(3.75e9, rel=0.01)
    # 2 (k, v) * 8 heads * 128 * 2 bytes * 16 layers
    assert arith.kv_bytes_per_token(m) == 65536


def test_pythia_counts_by_hand():
    m = cfg("pythia-1.4b")
    layer = 4 * 2048 * 2048 + 2 * 2048 * 8192
    assert arith.layer_matmul_params(m) == layer == 50_331_648
    biases = 3 * 2048 + 2048 + 8192 + 2048
    norms = 2 * 2 * 2048
    assert arith.param_count(m) == 24 * (layer + biases + norms) \
        + 2 * 2048 * 50304 + 2 * 2048
    assert arith.param_count(m) == pytest.approx(1.4146e9, rel=1e-3)
    assert arith.param_count(cfg("pythia-1.4b-d6")) == pytest.approx(
        0.508e9, rel=1e-2)


def test_train_flops_per_token_by_hand():
    m = cfg("pythia-1.4b-d6")
    weights = 6 * 50_331_648 + 2048 * 50304
    attn = 6 * 6 * 16 * 128 * 2049      # 6 * layers * heads * head_dim * (S+1)
    assert arith.train_flops_per_token(m, 2048) == 6 * weights + attn
    assert arith.train_flops_per_token(m, 2048) == pytest.approx(2.58e9, rel=0.01)


def test_serve_step_by_hand():
    m = cfg("mistral-7b-d16")
    # one decode token of one sequence with 100 tokens seen: 101 keys
    f = arith.serve_step_flops(m, n_tokens=1, qk_pairs=101, logit_rows=1)
    assert f == 2 * 16 * 218_103_808 + 4 * 16 * 32 * 128 * 101 \
        + 2 * 4096 * 32000
    b = arith.serve_step_bytes(m, n_tokens=1, ctx_tokens=101)
    assert b == (16 * 218_103_808 + 4096 * 32000) * 2 + 102 * 65536 + 4096 * 2
    # a lone decode token is memory bound on a v5e; 512 prompt tokens are not
    peaks = peaks_for("TPU v5 lite")
    assert arith.roofline_seconds(f, b, peaks)[1] == "memory"
    f = arith.serve_step_flops(m, 512, 512 * 513 // 2, 1)
    b = arith.serve_step_bytes(m, 512, 512)
    assert arith.roofline_seconds(f, b, peaks)[1] == "compute"


def test_unknown_chip_is_an_error():
    assert peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks_for("cpu")
