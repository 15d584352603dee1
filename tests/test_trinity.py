"""The layer pattern (window and full attention layers in one model,
leading dense layers, a sigmoid router beside an ungated shared expert,
gated attention, an RMSNorm per head on q and k, four norms a layer) at
``trinity-tiny``, against the benchmark's own plain reference
(``benchmarks/reference/trinity-mini-d5.py``, imported by path): through
``apply``, the window kernel against the masked XLA formulation, what
refuses the pattern, and the cell's configuration file.  The engine's
paged path, the routing it says, its counters and the older presets
through a period of two layers: ``tests/test_trinity_serving.py``."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.inference.model as M
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import apply, init_params
from deepspeed_tpu.ops.paged_attention import (LONG, SHORT, query_tiles,
                                               window_blocks)
from tests.test_paged_attention import (BS_T, D_T, HKV_T, TILE_BATCHES,
                                        _built_batch, _on_kernel, _on_xla,
                                        _random_pool)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # float32 system against the float32 reference


def _load(path, name):
    from benchmarks.lib.common import load_module
    return load_module(os.path.join(ROOT, path), name)


@pytest.fixture(scope="module")
def ref():
    return _load("benchmarks/reference/trinity-mini-d5.py", "trinity_ref")


@pytest.fixture(scope="module")
def tiny():
    cfg = build_config("trinity-tiny")
    axes = {}

    def init(key):          # one compiled call, not an operation at a time
        params, axes["axes"] = init_params(cfg, key)
        return params

    return cfg, jax.jit(init)(jax.random.PRNGKey(3)), axes["axes"]


def ref_config(cfg):
    """What the reference reads of a configuration file, for ``cfg``."""
    return dict(
        rms_norm_eps=cfg.eps, rope_theta=cfg.rope_theta,
        sliding_window=cfg.attn_window,
        num_experts_per_tok=cfg.moe_top_k, route_norm=cfg.moe_norm_topk,
        route_scale=cfg.moe_route_scale, hidden_size=cfg.d_model,
        num_dense_layers=cfg.num_dense_layers,
        num_hidden_layers=cfg.num_layers,
        layer_types=["sliding_attention" if k == "window"
                     else "full_attention" for k in cfg.layer_kinds])


def rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_tiny_preset_is_the_pattern(tiny):
    cfg, params, _ = tiny
    # one dense layer and two periods of (window, window, window, full)
    assert cfg.layer_plan == (1, 2, 0)
    assert cfg.layer_kinds == ("window",) * 4 + ("full",) \
        + ("window",) * 3 + ("full",)
    assert cfg.head_dim == 32 != cfg.d_model // cfg.num_heads
    assert params["blocks"]["attn"]["wq"].shape == (8, 64, 4, 32)
    assert params["blocks"]["attn"]["q_norm"].shape == (8, 32)
    assert params["dense_blocks"]["mlp"]["wi"].shape == (1, 64, 96)
    assert params["blocks"]["experts"]["wi"].shape == (8, 8, 64, 32)
    assert "gate" not in params["blocks"]["shared"]
    assert "mlp" not in params["blocks"]
    # seeded away from what a trainer starts with
    assert float(jnp.abs(params["blocks"]["gate"]["bias"]).min()) > 0
    # the four norms of a layer start at one, as a trainer's do
    for stack in ("blocks", "dense_blocks"):
        for name in ("ln1", "ln1_post", "ln2", "ln2_post"):
            assert np.all(np.asarray(params[stack][name]["scale"]) == 1.0)


def test_published_depth_is_two_dense_layers_seven_periods_and_a_tail():
    cfg = build_config("trinity-mini")
    assert cfg.layer_plan == (2, 7, 2)
    published = ("window", "window", "window", "full") * 8
    assert cfg.layer_kinds == published


def test_apply_agrees_with_the_reference(tiny, ref):
    cfg, params, _ = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 80)
    want = np.asarray(ref.logits(params, ids, ref_config(cfg)))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, i: apply(cfg, p, i))(
            params, jnp.asarray(ids)[None]))[0]
    assert rel(got, want) < TOL


def test_apply_with_a_tail_agrees_with_the_reference(ref):
    """Seven layers behind the dense one: a period and three layers of
    the next, which run outside the scan."""
    cfg = build_config("trinity-tiny", num_layers=8)
    assert cfg.layer_plan == (1, 1, 3)
    params = jax.jit(lambda k: init_params(cfg, k)[0])(jax.random.PRNGKey(5))
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 50)
    want = np.asarray(ref.logits(params, ids, ref_config(cfg)))
    with jax.default_matmul_precision("highest"):
        got, aux = jax.jit(lambda p, i: apply(cfg, p, i, with_aux=True))(
            params, jnp.asarray(ids)[None])
    assert rel(np.asarray(got)[0], want) < TOL
    assert np.isfinite(float(aux["moe_aux_loss"]))


# --------------------------------------------------------------------------
# the window kernel against the masked XLA formulation
# --------------------------------------------------------------------------

# bs = 8.  24 = three blocks: a decode token at context 31 starts its
# window at position 8, the first of block 1; at 30, inside block 0; a
# window of 20 starts inside a block wherever the context ends
WINDOW_BATCHES = dict(TILE_BATCHES, **{
    # tiles that start before (context under the window), inside and at
    # the edge of the first in-window block, and far behind it
    "decode-around-the-edge": ([(1, 5, 1), (2, 29, 1), (3, 30, 1),
                                (4, 31, 1), (5, 90, 1)], 32),
    # a chunk of 300 from position 3: its first tile's window is not
    # full, the second and third start inside it
    "chunk-through-the-window": ([(1, 3, 300)], 320),
})

# 64 = eight blocks, half a group of the short call: a decode token at
# 100 reads blocks 4..12 (its first block is not a multiple of the
# group, W/bs + 1 blocks); 68 = a window that starts inside a block


@pytest.mark.parametrize("window", [24, 20, 8, 64, 68])
@pytest.mark.parametrize("name", sorted(WINDOW_BATCHES))
def test_window_kernel_matches_masked_xla(name, window):
    runs, T = WINDOW_BATCHES[name]
    batch, _ = _built_batch(runs, T)
    kv, H, nb = _random_pool(3), HKV_T * 4, 48
    q = jnp.asarray(np.random.RandomState(11).randn(T, H, D_T), jnp.float32)
    scale = float(1.0 / np.sqrt(D_T))
    # (one compiled program for the cases of one shape and window)
    want = _on_xla(kv, q, batch, nb, scale, window=window)
    got = _on_kernel(kv, q, batch, nb, scale, window=window)
    full = _on_xla(kv, q, batch, nb, scale)
    valid = np.asarray(batch.token_valid)
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(want)[valid], atol=1e-5, rtol=1e-5)
    assert not np.asarray(got)[~valid].any()
    deep = np.asarray(batch.positions)[valid] >= window
    if deep.any():      # the window is not a no-op on this batch
        assert np.abs(np.asarray(want)[valid][deep]
                      - np.asarray(full)[valid][deep]).max() > 1e-3


def test_chunked_xla_formulation_masks_the_window(monkeypatch):
    runs, T = WINDOW_BATCHES["chunk-through-the-window"]
    batch, _ = _built_batch(runs, T)
    kv, H = _random_pool(4), HKV_T * 2
    q = jnp.asarray(np.random.RandomState(5).randn(T, H, D_T), jnp.float32)
    one_shot = M._paged_attention(kv, q, batch, BS_T, 48, 0.25, window=24)
    monkeypatch.setattr(M, "_ONE_SHOT_GATHER_BYTES", 0)
    chunked = M._paged_attention(kv, q, batch, BS_T, 48, 0.25, window=24)
    valid = np.asarray(batch.token_valid)
    np.testing.assert_allclose(np.asarray(chunked)[valid],
                               np.asarray(one_shot)[valid],
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("context", [600, 2047, 2048, 2111, 4100, 12287])
def test_window_tile_visits_a_bounded_number_of_blocks(context):
    """At the published window and block size a window tile's loop
    walks at most ceil((2048 + tile) / 64) + 1 blocks whatever the
    context, where a full layer's walks the context's."""
    W, bs, nb, T, seqs = 2048, 64, 192, 512, 8
    n_chunk = 300                       # three long tiles
    slot = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    valid = np.zeros(T, bool)
    for s in range(4):                  # decode tokens around ``context``
        slot[s], pos[s], valid[s] = s, max(0, context - 37 * s), True
    start = max(0, context - n_chunk)
    slot[4:4 + n_chunk] = 5
    pos[4:4 + n_chunk] = start + np.arange(n_chunk)
    valid[4:4 + n_chunk] = True
    tables = np.tile(np.arange(nb, dtype=np.int32), (seqs, 1))
    tiles = query_tiles(jnp.asarray(slot), jnp.asarray(pos),
                        jnp.asarray(valid), jnp.asarray(tables), bs, nb,
                        trash=nb)
    for tl, height in ((tiles.short, SHORT), (tiles.long, LONG)):
        n = int(tl.count)
        first, last = window_blocks(tl.pos[:n], tl.length[:n], W, bs)
        visits = np.asarray(last - first + 1)
        assert visits.max() <= math.ceil((W + height) / bs) + 1
        # the first block holds the first query's window start, the last
        # one the last query
        p, ln = np.asarray(tl.pos[:n]), np.asarray(tl.length[:n])
        np.testing.assert_array_equal(np.asarray(first),
                                      np.maximum(p - (W - 1), 0) // bs)
        np.testing.assert_array_equal(np.asarray(last), (p + ln - 1) // bs)
        if height == LONG and context >= 4100:
            assert visits.max() < ((p + ln - 1) // bs + 1).max() // 1.8


def test_what_serves_one_block_type_only_says_so(tiny):
    cfg, params, axes = tiny
    assert not cfg.plain_stack and build_config("llama-tiny").plain_stack
    with pytest.raises(NotImplementedError, match="one block type"):
        M.ragged_forward(cfg, params, None, None, 8, 4, quant={})


def test_pipeline_refuses_a_model_with_a_pattern(tiny):
    from deepspeed_tpu.comm.mesh import MeshConfig, MeshTopology
    from deepspeed_tpu.parallel.pipeline import make_pipelined_loss_fn
    topo = MeshTopology.build(MeshConfig(), devices=jax.devices()[:1])
    with pytest.raises(NotImplementedError, match="one block type"):
        make_pipelined_loss_fn(tiny[0], topo, 1)


def test_capacity_dispatch_refuses_the_sigmoid_router():
    cfg = build_config("trinity-tiny", moe_dispatch="scatter")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="moe_dispatch='ragged'"):
        apply(cfg, params, jnp.zeros((1, 8), jnp.int32))


def test_flash_attention_refuses_window_layers():
    from deepspeed_tpu.models.transformer import _resolve_attention
    with pytest.raises(ValueError, match="window layers"):
        _resolve_attention(build_config("trinity-tiny",
                                        attention_impl="xla_flash"))


# --------------------------------------------------------------------------
# the benchmark's configuration file against the shapes the system makes
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def d5():
    with open(os.path.join(ROOT, "benchmarks/configs/trinity-mini-d5.json")) as f:
        config = json.load(f)
    from benchmarks.lib.drivers.serve_routed import preset_config
    cfg = preset_config(config)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k)[0],
                            jax.random.PRNGKey(0))
    return config, cfg, shapes


def test_configuration_file_loads_and_counts_what_deployment_says(d5):
    config, cfg, shapes = d5
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == 4_241_534_720 and "4,241 M parameters" in config["deployment"]
    kv_token = cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    assert kv_token == 10 * 1024 and "10 KiB a token" in config["deployment"]
    # published widths: what the file states is what the system makes
    a = shapes["blocks"]["attn"]
    assert a["wq"].shape == a["wg"].shape == (4, 2048, 32, 128)
    assert a["wk"].shape == (4, 2048, 4, 128)
    assert config["head_dim"] == config["arith"]["head_dim"] \
        == cfg.head_dim == 128
    assert shapes["dense_blocks"]["mlp"]["wi"].shape == (
        1, config["hidden_size"], config["intermediate_size"])
    assert shapes["blocks"]["experts"]["wi"].shape == (
        4, config["num_experts"], config["hidden_size"],
        config["moe_intermediate_size"])
    assert shapes["blocks"]["shared"]["wi"].shape == (
        4, 2048, config["num_shared_experts"] * 1024)
    assert shapes["lm_head"]["kernel"].shape == (2048, config["vocab_size"])
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    assert tuple(kinds[k] for k in config["layer_types"]) == cfg.layer_kinds
    assert (cfg.attn_window, cfg.moe_top_k, cfg.moe_route_scale,
            cfg.moe_score, cfg.num_dense_layers) == (
        config["sliding_window"], config["num_experts_per_tok"],
        config["route_scale"], config["score_func"],
        config["num_dense_layers"])
    assert cfg.embed_scale == math.sqrt(config["hidden_size"])


def test_configuration_file_holds_the_catalog_entry(d5):
    """Every key of the published config.json stands in the file as
    published, but the depth's four (``reduced``)."""
    config = d5[0]
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    for k, v in published.items():
        assert config[k] == v, k
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types", "max_position_embeddings"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["max_position_embeddings"]) == (5, 1, 12288)
    assert config["published"]["num_hidden_layers"] == 32
