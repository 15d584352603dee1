"""Multi-chip (TP) serving: logits/token parity vs the single-device
engine on the virtual 8-device CPU mesh.

Reference parity target: AutoTP (`module_inject/auto_tp.py:189`) and the
v2 declarative sharding helpers
(`inference/v2/model_implementations/sharding/qkv.py`) — here expressed
as logical-axis specs + GSPMD instead of imperative tensor slicing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm.mesh import MeshTopology
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.inference.engine import InferenceConfig, InferenceEngine
from deepspeed_tpu.inference.sampler import SamplingParams
from deepspeed_tpu.models.transformer import Model, TransformerConfig
from tests.serving_ref import strict_generate

PROMPTS = {0: list(range(1, 20)), 1: list(range(30, 37)),
           2: list(range(100, 103))}
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)


def small_cfg(**kw):
    base = dict(vocab_size=256, d_model=64, num_layers=2, num_heads=4,
                num_kv_heads=4, d_ff=128, max_seq_len=128)
    base.update(kw)
    return TransformerConfig(**base)


def icfg(**kw):
    base = dict(token_budget=32, max_seqs=4, kv_block_size=8,
                num_kv_blocks=32, param_dtype=jnp.float32,
                kv_dtype=jnp.float32, attn_impl="xla")
    base.update(kw)
    return InferenceConfig(**base)


def topo_tp4_fsdp2(devices):
    return MeshTopology.build(MeshConfig(tensor=4, fsdp=2))


@pytest.fixture(scope="module")
def model():
    return Model(small_cfg(), seed=0)


def run_on(eng, prompts=PROMPTS, sampling=GREEDY):
    return eng.generate({u: list(p) for u, p in prompts.items()}, sampling)


def run(model, cfg, topology=None, prompts=PROMPTS, sampling=GREEDY):
    return run_on(InferenceEngine(model, cfg, topology=topology),
                  prompts, sampling)


def test_tp_generate_parity(devices, model):
    ref = run(model, icfg())
    tp = run(model, icfg(), topology=topo_tp4_fsdp2(devices))
    assert ref == tp


def test_tp_pallas_shard_map_parity(devices, model):
    """The Pallas kernel runs under shard_map, one head group per chip."""
    ref = run(model, icfg())
    tp = run(model, icfg(attn_impl="pallas"),
             topology=topo_tp4_fsdp2(devices))
    assert ref == tp


def test_tp_gqa_generate_equals_the_strict_loop(devices):
    """GQA (Hkv < H) under TP: ``generate()`` over the mesh, one launch
    ahead, against one chip's caller-fed loop."""
    model = Model(small_cfg(num_heads=8, num_kv_heads=4), seed=1)
    ref = strict_generate(InferenceEngine(model, icfg()), PROMPTS, GREEDY)
    tp = InferenceEngine(model, icfg(), topology=topo_tp4_fsdp2(devices))
    assert run_on(tp) == ref
    assert tp.metrics_snapshot()["serving_steps_ahead_total"] > 0


def test_tp_weight_quant_parity(devices, model):
    """ZeRO-Inference int8 weights memory-shard over the mesh; logits
    match the single-device quantized engine exactly."""
    ref = run(model, icfg(weight_quant="int8"))
    tp = run(model, icfg(weight_quant="int8"),
             topology=topo_tp4_fsdp2(devices))
    assert ref == tp


def test_tp_kv_cache_sharded(devices, model):
    """The paged KV cache is actually head-split over the tensor axis."""
    topo = topo_tp4_fsdp2(devices)
    eng = InferenceEngine(model, icfg(), topology=topo)
    spec = eng.state.kv.sharding.spec
    assert spec[4] == "tensor"
    # each shard holds Hkv/tp heads
    shard = eng.state.kv.addressable_shards[0]
    assert shard.data.shape[4] == model.config.num_kv_heads // 4


def test_tp_logits_parity_prefill(devices, model):
    """Step-level logits parity (not just greedy argmax)."""
    ref = InferenceEngine(model, icfg())
    tp = InferenceEngine(model, icfg(), topology=topo_tp4_fsdp2(devices))
    for eng in (ref, tp):
        eng.put(0, PROMPTS[0])
    sched_ref = ref._schedule()
    b_ref = ref.state.build_batch(sched_ref, ref.icfg.token_budget)
    lg_ref, _ = ref._build_step()(ref.params, ref._quant,
                                  ref.state.kv, b_ref)

    sched_tp = tp._schedule()
    b_tp = tp._stage(tp.state.build_batch(sched_tp, tp.icfg.token_budget))
    lg_tp, _ = tp._build_step()(tp.params, tp._quant, tp.state.kv, b_tp)
    np.testing.assert_allclose(np.asarray(lg_ref)[0], np.asarray(lg_tp)[0],
                               rtol=1e-4, atol=1e-4)


def test_tp_indivisible_heads_falls_back_replicated(devices):
    """num_kv_heads % tp != 0: the cache stays replicated, serving still
    works (logical-axis specs skip non-dividing dims)."""
    model = Model(small_cfg(d_model=96, num_heads=6, num_kv_heads=6), seed=2)
    topo = MeshTopology.build(MeshConfig(tensor=4, fsdp=2))
    ref = run(model, icfg())
    tp_eng = InferenceEngine(model, icfg(), topology=topo)
    assert tp_eng.state.kv.sharding.spec[4] is None
    tp = tp_eng.generate({u: list(p) for u, p in PROMPTS.items()}, GREEDY)
    assert ref == tp


def test_tp_alibi_parity(devices):
    """ALiBi serving under TP: the per-head slopes split with the kv
    head groups (both the XLA path via GSPMD and the Pallas kernel's
    explicit shard_map slopes operand)."""
    model = Model(small_cfg(position="alibi", embed_norm=True,
                            attention_impl="xla"), seed=2)
    ref = run(model, icfg())
    tp = run(model, icfg(), topology=topo_tp4_fsdp2(devices))
    assert ref == tp
    tp_pallas = run(model, icfg(attn_impl="pallas"),
                    topology=topo_tp4_fsdp2(devices))
    assert ref == tp_pallas


def test_tp_kv_quant_parity(devices, model):
    """int8 paged KV under TP: codes and scales head-split together;
    both the XLA path and the Pallas shard_map kernel match the
    single-device quantized engine exactly."""
    ref = run(model, icfg(kv_quant="int8"))
    tp = run(model, icfg(kv_quant="int8"), topology=topo_tp4_fsdp2(devices))
    assert ref == tp
    tp_pallas = run(model, icfg(kv_quant="int8", attn_impl="pallas"),
                    topology=topo_tp4_fsdp2(devices))
    assert ref == tp_pallas


def test_tp_weight_stream_parity(devices, model, tmp_path):
    """NVMe per-layer weight streaming under TP (previously a loud
    single-device reject): the fetch callback pins to one mesh device
    and GSPMD broadcasts each layer at first use; tokens match the
    single-device engine exactly (fp and int8, incl. the mixed kernel)."""
    for name, kw in (("fp", {}),
                     ("int8", {"weight_quant": "int8"}),
                     ("mixed", {"weight_quant": "int8",
                                "mixed_gemm": "on"})):
        ref = run(model, icfg(**kw))        # same numerics single-device
        tp = run(model, icfg(weight_stream=str(tmp_path / name), **kw),
                 topology=topo_tp4_fsdp2(devices))
        assert tp == ref, name
