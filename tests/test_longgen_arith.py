"""benchmarks/lib/arith_hybrid.py against counts made by hand at the
published sizes of ``trinity-mini-d5``, and its readers on a stubbed
trace.  Under ``tests/`` so that tier-1 counts it."""

import json
import os

import pytest

from benchmarks.lib import arith_hybrid as A
from benchmarks.lib import program_spans, trace
from benchmarks.lib.common import load_module, reader_path

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def m():
    with open(os.path.join(ROOT, "benchmarks/configs/trinity-mini-d5.json")) as f:
        return A.model(json.load(f))


ATTN = 3 * 2048 * 4096 + 2 * 2048 * 512          # q, o, gate; k, v
EXPERT = 3 * 2048 * 1024
DENSE_MLP = 3 * 2048 * 6144
HEAD = 2048 * 200192
KV = 2 * 4 * 128 * 2                             # one token, one layer


def test_layers_and_parameter_counts(m):
    assert A.layers(m) == (1, 4, 4, 1)
    assert A.attn_params(m) == ATTN == 27_262_976
    assert A.expert_params(m) == EXPERT == 6_291_456
    assert A.dense_mlp_params(m) == DENSE_MLP
    assert A.kv_token_bytes(m) == KV == 2048


def test_decode_step_bytes_equal_a_hand_count(m):
    """64 decode tokens at a mean context of 4,200: 500 of the four
    layers' 512 experts took a row, a window layer reads 1,900 tokens a
    sequence where the full one reads them all."""
    n, kv_full, kv_window, touched = 64, 64 * 4200, 64 * 1900, 500
    weights = (5 * ATTN + DENSE_MLP
               + 4 * (2048 * 128 + EXPERT) + touched * EXPERT + HEAD)
    by_hand = (2 * weights
               + (1 * kv_full + 4 * kv_window) * KV      # cached, by kind
               + 5 * n * KV                              # written
               + n * 2048 * 2)                           # embedding rows
    assert A.step_bytes(m, n, kv_full, kv_window, touched) == by_hand
    # 6.3 GB of experts, 1.2 GB of the other weights, 1.5 GB of keys and values
    assert (touched + 4) * EXPERT * 2 == pytest.approx(6.34e9, rel=1e-2)
    assert by_hand == pytest.approx(9.05e9, rel=1e-2)
    # five full layers would read 2.75 GB of keys and values
    assert A.step_bytes(m, n, kv_full, kv_full, touched) - by_hand == \
        4 * (kv_full - kv_window) * KV
    # an expert that took no row is not counted as read
    assert A.step_bytes(m, n, kv_full, kv_window, 512) - by_hand == \
        12 * EXPERT * 2


def test_decode_step_flops_equal_a_hand_count(m):
    n, kv_full, kv_window = 64, 64 * 4200, 64 * 1900
    per_token = 5 * ATTN + DENSE_MLP + 4 * (2048 * 128 + 9 * EXPERT)
    by_hand = (2.0 * n * per_token
               + 4.0 * 4096 * (kv_full + 4 * kv_window)
               + 2.0 * n * HEAD)
    assert A.step_flops(m, n, kv_full, kv_window, n) == by_hand


def test_expert_kernel_counts_four_layers_not_five(m):
    from benchmarks.lib import arith_moe
    n = 64
    assert A.expert_gemm_flops(m, n) == 2.0 * n * 4 * 8 * EXPERT
    assert A.expert_gemm_bytes(m, n, 500) == 2 * (
        500 * EXPERT + 4 * n * 8 * 3 * (2048 + 1024))
    # arith_moe counts num_hidden_layers expert layers and every expert
    # as read: 5/4 of the truth where all four layers' 512 took a row
    moe = {**m, "intermediate_size": m["moe_intermediate_size"]}
    assert arith_moe.expert_gemm_bytes(moe, n) == \
        pytest.approx(1.25 * A.expert_gemm_bytes(m, n, 4 * 128))


def test_window_kernel_bytes(m):
    n, kv_window = 64, 64 * 1900
    assert A.window_attn_bytes(m, n, kv_window) == 4 * (
        kv_window * KV + 2 * n * 4096 * 2)
    assert A.window_attn_flops(m, kv_window) == 4.0 * 4096 * 4 * kv_window


def _stub(monkeypatch, spans):
    monkeypatch.setattr(trace, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(program_spans, "read",
                        lambda path: ({(0, 0): spans}, [], {}))


def test_readers_take_the_steps_from_the_stage_spans(m, monkeypatch):
    stats = {"n_tokens": 64, "n_seqs": 64, "kv_tokens_full": 64 * 4200,
             "kv_tokens_window": 64 * 1900}
    spans = [(1.0 + 0.02 * i, 1.001 + 0.02 * i, "ds.serve.stage",
              {**stats, "sid": i}) for i in range(12)]
    # a step's readback lies a step behind its staging, the last ones'
    # behind the window's end; step 11's is not in the file
    spans += [(1.03 + 0.02 * i, 1.031 + 0.02 * i, "ds.serve.readback",
               {"sid": i, "moe_assignments": 2048, "moe_load": 4.0,
                "moe_experts_touched": 500}) for i in range(11)]
    spans.append((0.5, 0.6, "ds.serve.stage", {**stats, "sid": -1}))
    spans.append((1.05, 1.06, "ds.serve.dispatch", stats))  # another span
    _stub(monkeypatch, spans)
    with open(os.path.join(ROOT, "benchmarks/configs/trinity-mini-d5.json")) as f:
        config = json.load(f)
    rec = {"kind": "serve", "trace_dir": "d", "config": config,
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"window": (0.9, 1.5), "busy_s": 0.2,
                     "groups_s": {"moe_expert_gemm": 0.1,
                                  "window_attention": 0.02}}}
    steps = A.traced_steps(rec)
    assert len(steps) == 11                 # twelve inside, less the last
    assert all(s["moe_experts_touched"] == 500 for s in steps)
    read = {n: load_module(reader_path("layer_metrics", n), n).read
            for n in ("longgen_step_roofline", "longgen_expert_gemm_roofline",
                      "window_attn_roofline", "window_attn_share",
                      "window_kv_read_share")}
    step_s = A.step_bytes(m, 64, 64 * 4200, 64 * 1900, 500) / 819e9
    assert read["longgen_step_roofline"](rec) == \
        pytest.approx(100 * 11 * step_s / 0.2)
    assert read["longgen_expert_gemm_roofline"](rec) == pytest.approx(
        100 * 11 * A.expert_gemm_bytes(m, 64, 500) / 819e9 / 0.1)
    assert read["window_attn_roofline"](rec) == pytest.approx(
        100 * 11 * A.window_attn_bytes(m, 64, 64 * 1900) / 819e9 / 0.02)
    assert read["window_attn_share"](rec) == pytest.approx(10.0)
    assert read["window_kv_read_share"](rec) == pytest.approx(100 * 19 / 42)


def test_readers_say_nothing_of_a_program_without_the_counts(monkeypatch):
    """The parent's ``ds.serve.stage`` spans have no ``kv_tokens_*`` and
    its ``ds.serve.readback`` spans no ``moe_experts_touched``."""
    spans = [(1.0 + 0.02 * i, 1.001 + 0.02 * i, "ds.serve.stage",
              {"n_tokens": 64, "n_seqs": 64, "sid": i}) for i in range(5)]
    spans += [(1.01 + 0.02 * i, 1.011 + 0.02 * i, "ds.serve.readback",
               {"sid": i, "moe_load": 4.0}) for i in range(5)]
    _stub(monkeypatch, spans)
    rec = {"kind": "serve", "trace_dir": "d", "config": {"arith": {}},
           "peaks": {}, "trace": {"window": (0.9, 1.5), "busy_s": 0.2,
                                  "groups_s": {}}}
    for n in ("longgen_step_roofline", "longgen_expert_gemm_roofline",
              "window_attn_roofline", "window_attn_share",
              "window_kv_read_share"):
        assert load_module(reader_path("layer_metrics", n), n).read(rec) is None
