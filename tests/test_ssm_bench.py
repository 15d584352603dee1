"""The cell ``serve-ssm-chat``: its configuration file against the
shapes the system makes, ``benchmarks/lib/arith_ssm.py`` against hand
counts, and the cell rehearsed on the CPU (``benchmarks/run.py
--rehearse``).  Under ``tests/`` so that tier-1 counts it:
``benchmarks/tests/`` is not on the driver's line."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import arith_ssm as A
from deepspeed_tpu.inference.ragged.state import KVCacheConfig
from deepspeed_tpu.models.transformer import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MiB = 1 << 20


@pytest.fixture(scope="module")
def d6():
    """The file through the cell's driver, abstract shapes only."""
    with open(os.path.join(
            ROOT, "benchmarks/configs/falcon-h1-34b-d6.json")) as f:
        config = json.load(f)
    from benchmarks.lib.drivers.serve_recurrent import preset_config
    cfg = preset_config(config)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k)[0],
                            jax.random.PRNGKey(0))
    return config, cfg, shapes


def test_configuration_file_loads_and_counts_what_deployment_says(d6):
    config, cfg, shapes = d6
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == 5_254_594_112 and "5,254.6 M parameters" in config["deployment"]
    layer = sum(a.size for a in jax.tree.leaves(shapes["blocks"])) // 6
    assert layer == 430_120_032 and "430.12 M" in config["deployment"]
    kv_token = cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    assert kv_token == 12 * 1024 and "12 KiB a token" in config["deployment"]
    # the state rows as the ENGINE makes them, from its own configuration
    with open(os.path.join(
            ROOT, "benchmarks/traffic/chat-closed-128.json")) as f:
        sizes = json.load(f)["engine"]
    from deepspeed_tpu.inference.ragged.state import RecurrentConfig
    sd = cfg.ssm_dims
    rc = RecurrentConfig(heads=sd.heads, head_dim=sd.head_dim,
                         state=sd.state, conv=sd.conv,
                         channels=sd.conv_channels, chunk=sd.chunk)
    cache = jax.eval_shape(lambda: KVCacheConfig(
        num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, block_size=sizes["kv_block_size"],
        num_blocks=sizes["num_kv_blocks"], recurrent=rc
    ).cache_zeros(sizes["max_seqs"]))
    assert cache["ssm"].shape == (6, 129, 32, 128, 256)
    assert cache["conv"].shape == (6, 129, 4, 5120)
    assert cache["ssm"].dtype == cache["conv"].dtype == jnp.bfloat16
    per_seq = rc.bytes_per_seq(cfg.num_layers)
    assert per_seq == 6 * (2 * MiB + 40 * 1024)
    assert "2 MiB stored in bf16" in config["deployment"] \
        and "40 KiB" in config["deployment"]
    rows = sum(a.size * a.dtype.itemsize for a in (cache["ssm"],
                                                   cache["conv"]))
    assert rows == 129 * per_seq and 1.53 < rows / 2 ** 30 < 1.55
    pool = cache["kv"].size * 2
    assert (pool - 64 * kv_token) == 1.125 * 2 ** 30   # less the trash block
    assert 13.3e9 < 2 * n + rows + pool < 13.5e9
    # published widths: what the file states is what the system makes
    a, m = shapes["blocks"]["attn"], shapes["blocks"]["ssm"]
    assert a["wq"].shape == (6, 5120, 20, 128)
    assert a["wk"].shape == a["wv"].shape == (6, 5120, 4, 128)
    assert a["wo"].shape == (6, 20, 128, 5120)
    assert m["w_in"].shape == (6, 5120, 9248)
    assert m["w_out"].shape == (6, 4096, 5120)
    assert m["conv_w"].shape == (6, 5120, 4)
    assert shapes["blocks"]["mlp"]["wi"].shape == (
        6, config["hidden_size"], config["intermediate_size"])
    assert shapes["lm_head"]["kernel"].shape == (5120, config["vocab_size"])
    assert config["head_dim"] == config["arith"]["head_dim"] \
        == cfg.head_dim == 128


def test_configuration_file_holds_the_catalog_entry(d6):
    """Every key of the published config.json stands in the file as
    published, but the two of ``reduced``."""
    config = d6[0]
    published = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_key_value_heads": 4, "num_logits_to_keep": 1,
        "projectors_bias": False, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 100000000000,
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120}
    for k, v in published.items():
        assert config[k] == v, k
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert (config["num_hidden_layers"],
            config["max_position_embeddings"]) == (6, 1024)
    assert config["published"] == {"num_hidden_layers": 72,
                                   "max_position_embeddings": 262144}


def test_driver_checks_the_keys_the_harness_does_not_know(d6):
    from benchmarks.lib.drivers.serve_recurrent import preset_config
    config = d6[0]
    for key, wrong in (("head_dim", 256), ("mamba_d_state", 128),
                       ("key_multiplier", 1.0),
                       ("ssm_multipliers", [1, 1, 1, 1, 1]),
                       ("mamba_chunk_size", 256)):
        with pytest.raises(SystemExit, match=key):
            preset_config({**config, key: wrong})


def test_decode_step_bytes_by_hand(d6):
    m = A.model(d6[0])
    assert A.in_proj(m) == 9248 and A.conv_channels(m) == 5120
    assert A.mixer_params(m) == 5120 * 9248 + 4096 * 5120 == 68_321_280
    assert A.layer_params(m) == 31_457_280 + 330_301_440 + 68_321_280
    # one sequence, one layer: 1 Mi state elements and three tail rows
    assert A.state_elements(m) == 32 * 128 * 256 == 1 << 20
    assert A.tail_elements(m) == 3 * 5120
    assert A.state_bytes_per_seq(m) == 6 * 2 * ((1 << 20) + 3 * 5120)
    # a row's state and tail read and written, x B C dt z in, y out
    row = 2 * 2 * ((1 << 20) + 15360) + 2 * (9248 + 4096)
    assert A.update_bytes(m, 1) == 6 * row
    assert A.update_bytes(m, 128) == 128 * 6 * row
    assert 3.2e9 < A.update_bytes(m, 128) < 3.3e9     # "3.2 GB of state"
    assert A.update_flops(m, 128) == 5.0 * 6 * 128 * (1 << 20)
    # a whole decode step of 128 sequences at a mean context of 285
    s = {"n_tokens": 128, "n_seqs": 128, "kv_tokens_full": 128 * 285,
         "state_rows": 128, "scan_tokens": 0, "state_starts": 0,
         "state_replays": 0}
    weights = (6 * 430_080_000 + 5120 * 261120) * 2
    kv = (128 * 285 + 128) * 12 * 1024
    assert A.step_bytes(m, s) == weights + kv + 128 * 6 * row \
        + 128 * 5120 * 2
    assert 11.3e9 < A.step_bytes(m, s) < 11.7e9       # "11.5 GB"
    assert A.step_flops(m, s) == (
        2.0 * 128 * 6 * 430_080_000 + 4.0 * 6 * 20 * 128 * 128 * 285
        + 5.0 * 6 * 128 * (1 << 20) + 2.0 * 128 * 5120 * 261120)


def test_scan_counts_by_hand(d6):
    m = A.model(d6[0])
    # a scanned token: the causal half of a chunk of 128 against B and
    # against x, and its share of the chunk's state in and out
    per_token = 2 * 64.5 * (2 * 256 + 32 * 128) + 4 * (1 << 20)
    assert A.scan_flops(m, 100) == 6 * 100 * per_token
    # two runs of several tokens, one starting at position 0: two last
    # states written, one first state read
    s = {"n_tokens": 131, "n_seqs": 5, "kv_tokens_full": 0,
         "state_rows": 3, "scan_tokens": 128, "state_starts": 1,
         "state_replays": 0}
    assert A.scan_runs(s) == 2
    assert A.scan_bytes(m, 128, 2, 1) == 6 * (
        128 * 2 * (9248 + 4096) + 3 * 2 * ((1 << 20) + 15360))


def run(*argv):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "benchmarks/run.py", *argv],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_ssm_cell_rehearses(trace):
    p = run("--workload", "serve-ssm-chat", "--seed", str(2 ** 31 + 42),
            "--seconds", "2", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(line) for line in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    checks = last["compared_with_reference"]["checks"]
    # serve.py's two, and the driver's own four
    assert set(checks) == {"logits_prefill", "logits_decode",
                           "chunked_prefill", "chunked_decode",
                           "reused_slots_prefill", "reused_slots_decode"}
    assert all(c["ok"] for c in checks.values())
    # a slot that changed hands reads as it did the first time
    assert checks["reused_slots_decode"]["rel"] \
        == checks["logits_decode"]["rel"]
    own = next(n for n in lines if n.get("note") == "reference_recurrent")
    # 150 tokens in steps of 64, then three fed
    assert own["steps"] == 6 and own["slots_taken_again"] == [0, 1]
    from benchmarks.lib.common import last_line_faults
    assert last_line_faults(last, traced=bool(trace), on_chip=False) == []
    values = next(n for n in lines
                  if n.get("note") == "rehearsal_values")["values"]
    if trace:
        assert values["ssm.serve_window_compiles"]["value"] == 0
        assert values["ssm.batch_tokens_per_step"]["value"] > 0
    else:
        assert values["setup_s"]["value"] > 0
        assert values["out_tokens_per_s"]["value"] > 0


def test_benchmark_json_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "serve-ssm-chat")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-d6", "chat-closed-128", 1)
    # (a later cell is appended behind it in an entry's list)
    mine = [m for m in bench["per_layer"]
            if m.get("workloads", [None])[0] == "serve-ssm-chat"]
    names = {m["name"] for m in mine}
    assert {"ssm_update_roofline", "ssm_scan_roofline", "ssm_share",
            "ssm_step_roofline", "ssm_state_rows_per_step"} <= names
    assert len(mine) == 17 and all(m["moves"] == "out_tokens_per_s"
                                   for m in mine)
    # arith.py knows no mixer: none of its shares is entered for the cell
    for m in bench["per_layer"]:
        if "serve-ssm-chat" in m.get("workloads", ()):
            assert m in mine
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    # (a later cell is appended behind it)
    assert "serve-ssm-chat" in e2e["out_tokens_per_s"]["workloads"]
