"""``benchmarks/lib/arith_ragmoe.py`` counted by hand at the published
widths, the configuration's file against the shapes the preset makes, and
what ``BENCHMARK.json`` says of the cell."""

import json
import os

import pytest

from benchmarks.lib import arith_ragmoe as A

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-small-d10.json")) as f:
        return json.load(f)


def test_the_file_holds_every_key_of_the_catalog_row(config):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == set(config["published"])
    assert all(config["published"][k] == row["config"][k] for k in differs)
    assert (config["num_hidden_layers"], config["num_local_experts"],
            config["router_outputs"], config["experts_held"]) \
        == (10, 36, 72, [0, 36])
    # a whole period: the published list's first ten
    assert config["layer_types"][:10] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert len(config["reference"]["tolerance"]) >= 4


def test_the_preset_makes_the_shapes_the_file_counts(config):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib.drivers.serve_state_share import preset_config
    from deepspeed_tpu.inference.ragged.state import (KVCacheConfig,
                                                      RecurrentConfig)
    from deepspeed_tpu.models.transformer import init_params
    cfg = preset_config(config)
    assert cfg.layer_kinds == ("mamba",) * 5 + ("full",) + ("mamba",) * 4
    assert cfg.experts_held == (0, 36) and cfg.num_experts == 72
    shapes = jax.eval_shape(lambda k: init_params(cfg, k)[0],
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 4962732672 and "4,962,732,672 parameters" in config[
        "deployment"]
    b = shapes["blocks"]
    assert b["experts"]["wi"].shape == (10, 36, 4096, 768)
    assert b["gate"]["kernel"].shape == (10, 4096, 72)
    assert b["mamba"]["w_in"].shape == (9, 4096, 16768)
    assert b["full"]["wq"].shape == (1, 4096, 32, 128)
    m = A.model(config)
    assert (m["n_mamba"], m["n_attn"]) == (9, 1)
    assert A.fixed_params(m) + 10 * 36 * A.expert_params(m) \
        + 4096 * 100352 == n - 2 * 10 * 4096 - 4096 \
        - 9 * (8448 * 4 + 8448 + 3 * 128 + 8192)
    sd = cfg.ssm_dims
    rc = RecurrentConfig(heads=sd.heads, head_dim=sd.head_dim,
                         state=sd.state, conv=sd.conv,
                         channels=sd.conv_channels, chunk=sd.chunk,
                         layers=cfg.layers_of("mamba"))
    kv = KVCacheConfig(num_layers=cfg.block_layers, num_kv_heads=8,
                       head_dim=128, block_size=64, num_blocks=16,
                       recurrent=rc)
    cache = jax.eval_shape(lambda: kv.cache_zeros(64))
    assert cache["kv"].shape == (1, 17, 64, 2, 8, 128)
    assert cache["ssm"].shape == (9, 65, 128, 64, 128)
    assert cache["ssm"].dtype == cache["conv"].dtype == jnp.bfloat16
    assert cache["conv"].shape == (9, 65, 4, 8448)
    assert rc.bytes_per_seq(9) == 19482624 == A.state_bytes_per_seq(m)
    assert "19,482,624 bytes" in config["deployment"]
    assert A.kv_bytes_per_token(m) == 4096       # 4 KiB, the one layer


def test_a_decode_step_and_a_chunk_step_by_hand(config):
    m = A.model(config)
    d = 4096
    mixer = d * 16768 + 8192 * d
    attn = d * 32 * 128 + 2 * d * 8 * 128 + 32 * 128 * d
    fixed = 9 * mixer + attn + 10 * (d * 72 + 3 * d * 1536)
    assert A.fixed_params(m) == fixed
    s = {"n_tokens": 63, "n_seqs": 63, "kv_tokens_full": 190000,
         "state_rows": 63, "scan_tokens": 0, "state_starts": 0,
         "state_replays": 0, "moe_assignments": 3150,
         "moe_assignments_made": 6300, "moe_experts_touched": 350}
    state = 128 * 64 * 128
    row_io = 16768 + 8192
    update = 9 * 63 * (2 * 2 * (state + 3 * 8448) + 2 * row_io)
    assert A.update_bytes(m, s) == update
    experts = 2 * (350 * 3 * d * 768 + 3150 * 3 * (d + 768))
    assert A.expert_gemm_bytes(m, s) == experts
    assert A.step_bytes(m, s) == 2 * (fixed + d * 100352) \
        + (190000 + 63) * 4096 + update + experts + 63 * d * 2
    assert A.step_flops(m, s) == 2.0 * 63 * fixed + 4.0 * 32 * 128 * 190000 \
        + 5.0 * 9 * 63 * state + 2.0 * 3150 * 3 * d * 768 \
        + 2.0 * 63 * d * 100352
    # a step that carries a prompt's chunk of 449 tokens beside 63 rows:
    # the chunked form's least products and its inputs and output, the
    # run's first state read and its last written
    c = dict(s, n_tokens=512, n_seqs=64, scan_tokens=449,
             kv_tokens_full=193000)
    q = (256 + 1) / 2.0
    assert A.scan_flops(m, c) == 9 * 449 * (2.0 * q * (128 + 8192)
                                            + 4.0 * state)
    assert A.scan_bytes(m, c) == 9 * (449 * 2 * row_io
                                      + 2 * 2 * (state + 3 * 8448))
    assert A.step_flops(m, c) > A.step_flops(m, s)


def test_benchmark_json_lists_the_cell_and_what_it_joins():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "serve-ssm-moe-rag")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-small-d10", "rag-closed-64", 1)
    assert bench["workloads"][-1] is cell
    assert bench["configs"][-1]["name"] == "granite-4.0-h-small-d10"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["out_tokens_per_s"]["workloads"][-1] == "serve-ssm-moe-rag"
    # 128 of 128 entries stood: the cell joins the lists of readers that
    # take nothing from a name, last in each, and adds no entry
    assert len(bench["per_layer"]) == 128
    mine = [m for m in bench["per_layer"]
            if "serve-ssm-moe-rag" in m.get("workloads", ())]
    assert all(m["workloads"][-1] == "serve-ssm-moe-rag"
               and m["moves"] == "out_tokens_per_s" for m in mine)
    assert {m["name"] for m in mine} == {
        "batch_tokens_per_step", "serve_hbm_peak_gb",
        "moe_expert_gemm_share", "moe_route_share",
        "moe_expert_load_max_over_mean", "moe.serve_step_p50_ms",
        "moe.serve_host_ms_per_step", "moe.serve_window_compiles",
        "moe.serve_step_retries", "moe.itl_p95_ms", "moe.itl_p99_ms",
        "moe.serve_idle_share", "moe.sampler_share", "moe.paged_attn_share",
        "moe.kv_write_share", "ssm_share", "ssm_state_rows_per_step"}
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "rag-closed-64.json")) as f:
        mix = json.load(f)
    assert mix["driver"] == "serve_state_share" and mix["clients"] == 64
    assert (mix["prompt_tokens"], mix["answer_tokens"]) == (
        {"dist": "loguniform", "lo": 512, "hi": 8192},
        {"dist": "uniform", "lo": 128, "hi": 1024})
    assert mix["engine"]["num_kv_blocks"] == 9216 == mix["engine"][
        "max_seq_len"] and mix["engine"]["token_budget"] == 512
    assert (mix["warmup_s"], mix["trace_s"]) == (40, 5)
