"""The layout the engine holds the attention projections in
(``inference/model.py`` ``fold_projections``): ``wq``/``wk``/``wv`` and the
attention gate ``[L, d, H, D]`` of the model's tree as ``[L, d, H*D]`` of
the tree the engine serves, and ``wo`` ``[L, H, D, d]`` as ``[L, H*D, d]``.  The folded tree against the unfolded one to
the bit for every form of block that reaches ``_qkv_proj``, what the
forward refuses, ``refresh_params``, the quantized forms, a tensor-parallel
mesh and the NVMe weight stream."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.inference.model as M
from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.inference.quantization import (_quantize_stacked,
                                                  quantize_model_params)
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, init_params
from deepspeed_tpu.ops.quant import dequantize_any

TINY = dict(vocab_size=128, num_layers=2, d_model=64, d_ff=128,
            max_seq_len=128)
# every form of block whose layers go through ``_qkv_proj``
FORMS = {
    # grouped-query attention, four query heads a kv head, with biases
    "gqa4-biases": ("qwen2-tiny", dict(TINY, num_heads=8, num_kv_heads=2)),
    # one query head a kv head, an RMSNorm over the whole projection
    "rep1-qk-norm": ("olmoe-tiny", {}),
    # a leading dense block, gated window and full layers, a norm per head
    "gated-window-dense": ("trinity-tiny", {}),
    # a Mamba-2 mixer beside the attention in every block
    "hybrid": ("falcon-h1-tiny", {}),
}
PROMPTS = {0: [5, 17, 99, 3, 42, 7, 11], 1: [8, 9, 10]}
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=5)


def model_of(form: str, seed: int = 0) -> Model:
    preset, over = FORMS[form]
    cfg = build_config(preset, **over)
    params, axes = init_params(cfg, jax.random.PRNGKey(seed))
    if cfg.attn_bias:
        # the biases are seeded zero: make them a visible term
        for i, b in enumerate(("bq", "bk", "bv")):
            a = params["blocks"]["attn"][b]
            params["blocks"]["attn"][b] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(100 + i), a.shape, a.dtype)
    return Model.from_params(cfg, params, param_axes=axes)


def engine(model, topology=None, **over):
    kw = dict(token_budget=32, max_seqs=4, kv_block_size=8,
              num_kv_blocks=64, max_seq_len=128, attn_impl="xla",
              param_dtype=jnp.float32, kv_dtype=jnp.float32)
    kw.update(over)
    return InferenceEngine(model, InferenceConfig(**kw), topology=topology)


def step_logits(eng, params=None):
    """The logits of one step over ``PROMPTS`` through ``_build_step``,
    with ``params`` in place of the engine's own tree."""
    for u, p in PROMPTS.items():
        eng.put(u, list(p))
    sched = eng._schedule()
    batch = eng._stage(eng.state.build_batch(sched, eng.icfg.token_budget))
    logits, eng.state.kv = eng._build_step()(
        eng.params if params is None else params, eng._quant, eng.state.kv,
        batch)
    rows = np.stack([np.asarray(logits[eng.state.slot(u)]) for u in PROMPTS])
    for u in PROMPTS:
        eng.flush(u)
    return rows


def attn_leaves(tree):
    return {f"{g}.{k}": v for g in ("blocks", "dense_blocks") if g in tree
            for k, v in tree[g]["attn"].items()
            if k in M._HEAD_PROJECTIONS + ("wo",)}


@pytest.mark.parametrize("form", list(FORMS))
def test_folded_tree_serves_the_unfolded_trees_logits(form, monkeypatch):
    model = model_of(form)
    cfg = model.config
    eng = engine(model)
    held = attn_leaves(eng.params)
    assert len(held) == (4 + cfg.attn_gate) * (1 + bool(cfg.num_dense_layers))
    for name, w in held.items():
        heads = cfg.num_kv_heads if name[-1] in "kv" else cfg.num_heads
        want = (cfg.d_model, heads * cfg.head_dim)
        assert w.shape[1:] == (want[::-1] if name.endswith("wo")
                               else want), name
    # the model's own tree is what training, the loaders and ``apply`` read
    assert model.params["blocks"]["attn"]["wq"].shape[1:] == (
        cfg.d_model, cfg.num_heads, cfg.head_dim)
    folded = step_logits(eng)
    # the same step over the model's tree, each head projection's product
    # as it was written before the fold: ``_mm`` reshaping the weight
    # (``_out_proj`` takes ``wo`` at either rank)
    monkeypatch.setattr(M, "_head_proj",
                        lambda h, w, dt, heads, head_dim: M._mm(h, w, dt))
    unfolded = step_logits(engine(model), params=model.params)
    assert np.array_equal(folded, unfolded)


def test_forward_refuses_an_unfolded_projection():
    model = model_of("gqa4-biases")
    cfg = model.config
    layer = jax.tree.map(lambda a: a[0], model.params["blocks"]["attn"])
    h = jnp.ones((3, cfg.d_model))
    cos, sin = M.L.rope_freqs(cfg.rotary_dim, cfg.max_seq_len,
                              cfg.rope_theta)
    with pytest.raises(AssertionError, match="fold_projections"):
        M._qkv_proj(cfg, layer, h, jnp.float32, cos, sin, jnp.arange(3))
    folded = jax.tree.map(lambda a: a[0], M.fold_projections(
        model.params)["blocks"]["attn"])
    q, k, v = M._qkv_proj(cfg, folded, h, jnp.float32, cos, sin,
                          jnp.arange(3))
    assert q.shape == (3, cfg.num_heads, cfg.head_dim)
    assert k.shape == v.shape == (3, cfg.num_kv_heads, cfg.head_dim)


@pytest.mark.parametrize("form", ["gqa4-biases", "gated-window-dense"])
def test_fold_twice_is_fold_once(form):
    params = model_of(form).params
    once = M.fold_projections(params)
    twice = M.fold_projections(once)
    assert jax.tree.structure(once) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(twice)):
        assert a is b
    changed = [p for (p, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(once))
        if a is not b]
    assert len(changed) == len(attn_leaves(params))
    # an engine built over a tree that is folded already holds that tree
    # (a replica minted from a template's weight store)
    eng = engine(Model.from_params(model_of(form).config, once))
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(eng.params)):
        assert a is b


def test_refresh_params_folds():
    model = model_of("gqa4-biases")
    eng = engine(model)
    before = step_logits(eng)
    other = model_of("gqa4-biases", seed=1)
    eng.refresh_params(other.params)
    assert not np.array_equal(step_logits(eng), before)
    # the model's own tree back, then the tree the engine served
    eng.refresh_params(model.params)
    assert eng.params["blocks"]["attn"]["wk"].ndim == 3
    assert np.array_equal(step_logits(eng), before)
    eng.refresh_params(eng.params)
    assert np.array_equal(step_logits(eng), before)


@pytest.mark.parametrize("bits", [8, 4, 6, 12])
def test_quantized_payloads_of_a_folded_leaf_are_the_unfolded_leafs(bits):
    w = jax.random.normal(jax.random.PRNGKey(bits), (3, 64, 4, 32))
    path = (jax.tree_util.DictKey("attn"), jax.tree_util.DictKey("wq"))
    born = _quantize_stacked(M.fold_projection(path, w), bits)
    moved = M.fold_projection(path, _quantize_stacked(w, bits))
    assert born.shape == moved.shape == (3, 64, 128)
    assert born.layout == moved.layout
    for a, b in ((born.data, moved.data), (born.scale, moved.scale)):
        assert a.shape == b.shape and np.array_equal(a, b)
    assert np.array_equal(
        dequantize_any(moved),
        np.asarray(dequantize_any(_quantize_stacked(w, bits))).reshape(
            3, 64, 128))
    assert M.fold_projection(path, moved) is moved


@pytest.mark.parametrize("weight_quant", ["int8", "int4"])
def test_weight_quant_serves_the_folded_tree(weight_quant):
    model = model_of("gqa4-biases")
    eng = engine(model, weight_quant=weight_quant, mixed_gemm="off")
    assert "wq" not in eng.params["blocks"]["attn"]
    _, unfolded = quantize_model_params(
        jax.tree.map(lambda x: x, model.params),
        bits=8 if weight_quant == "int8" else 4)
    for name in ("wq", "wk", "wv"):
        held = eng._quant["blocks"]["attn"][name]
        want = unfolded["blocks"]["attn"][name]
        assert len(held.shape) == 3 and len(want.shape) == 4
        assert np.array_equal(held.data,
                              np.asarray(want.data).reshape(held.data.shape))
        assert np.array_equal(held.scale, np.asarray(want.scale).reshape(
            held.scale.shape))
    # a tree quantized before the engine saw it (a quantized checkpoint)
    # is folded where the engine takes it, and serves the same logits
    dense, quant = quantize_model_params(
        jax.tree.map(lambda x: x, model.params),
        bits=8 if weight_quant == "int8" else 4)
    prebuilt = InferenceEngine(
        Model.from_params(model.config, dense), eng.icfg, quant_tree=quant)
    assert len(prebuilt._quant["blocks"]["attn"]["wq"].shape) == 3
    want = step_logits(eng)
    # (``wo``'s scales go by head: quantized, it is held as the model's)
    assert len(prebuilt._quant["blocks"]["attn"]["wo"].shape) == 4
    assert len(eng._quant["blocks"]["attn"]["wo"].shape) == 4
    assert np.array_equal(step_logits(prebuilt), want)
    # the mixed-input GEMM takes a layer's folded payload as it lies
    mixed = engine(model, weight_quant=weight_quant, mixed_gemm="on")
    np.testing.assert_allclose(step_logits(mixed), want, atol=0.1, rtol=0.1)


def test_tensor_parallel_splits_a_folded_leaf_at_head_boundaries(devices):
    from deepspeed_tpu.comm.mesh import MeshTopology
    from deepspeed_tpu.config.config import MeshConfig

    model = model_of("gqa4-biases")
    cfg = model.config
    topo = MeshTopology.build(MeshConfig(tensor=2), devices=devices[:2])
    eng = engine(model, topology=topo)
    for name, heads in (("wq", cfg.num_heads), ("wk", cfg.num_kv_heads),
                        ("wv", cfg.num_kv_heads)):
        w = eng.params["blocks"]["attn"][name]
        assert w.sharding.spec == jax.sharding.PartitionSpec(
            None, None, "tensor"), name
        # a device's columns are whole heads: H / tp of them
        shard = w.addressable_shards[0].data
        assert shard.shape == (cfg.num_layers, cfg.d_model,
                               heads // 2 * cfg.head_dim)
    wo = eng.params["blocks"]["attn"]["wo"]
    assert wo.sharding.spec == jax.sharding.PartitionSpec(None, "tensor")
    assert wo.addressable_shards[0].data.shape == (
        cfg.num_layers, cfg.num_heads // 2 * cfg.head_dim, cfg.d_model)
    assert eng.generate({u: list(p) for u, p in PROMPTS.items()}, GREEDY) \
        == engine(model).generate({u: list(p) for u, p in PROMPTS.items()},
                                  GREEDY)
    # three kv heads do not split over two devices: the axis stays whole
    cfg3 = build_config("qwen2-tiny", **dict(TINY, d_model=96, num_heads=6,
                                             num_kv_heads=3))
    params, axes = init_params(cfg3, jax.random.PRNGKey(0))
    odd = Model.from_params(cfg3, params, param_axes=axes)
    held = engine(odd, topology=topo).params["blocks"]["attn"]
    assert "tensor" not in tuple(held["wk"].sharding.spec)


def test_weight_stream_round_trips_a_folded_layer(tmp_path):
    model = model_of("gqa4-biases")
    cfg = model.config
    resident = engine(model)
    eng = engine(model, weight_stream=str(tmp_path / "w"))
    assert "blocks" not in eng.params
    layer = eng._stream.result_shapes()["dense"]["attn"]
    assert layer["wq"].shape == (cfg.d_model, cfg.num_heads * cfg.head_dim)
    assert layer["wk"].shape == (cfg.d_model,
                                 cfg.num_kv_heads * cfg.head_dim)
    assert layer["wo"].shape == (cfg.num_heads * cfg.head_dim, cfg.d_model)
    got = jax.tree.unflatten(eng._stream._treedef,
                             list(eng._stream._fetch_host(1)))
    for name in ("wq", "wk", "wv", "wo"):
        assert np.array_equal(got["dense"]["attn"][name],
                              resident.params["blocks"]["attn"][name][1])
    assert np.array_equal(step_logits(eng), step_logits(resident))
