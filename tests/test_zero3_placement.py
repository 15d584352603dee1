"""ZeRO stage 3 says what it means in the compiled step (parallel/zero.py
``ZeroPolicy.placement``): every use of a sharded parameter gathers the
parameter, activations stay split over the batch, and small per-layer
leaves stay replicated.

The checks read the compiled program's collectives by shape and JAX path
on four virtual CPU devices at toy widths whose dims are chosen so that no
parameter dim equals the sequence length or a batch size.  What the TPU's
compiler makes of the same step at the cell's widths is
``tests/test_tpu_compile.py``."""

import re

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.comm import MeshTopology
from deepspeed_tpu.config import MeshConfig
from deepspeed_tpu.models.transformer import Model, TransformerConfig
from deepspeed_tpu.parallel.zero import ZeroPolicy

GAUGE = "training_zero3_gather_bytes_per_step"
SEQ, PER_CHIP, CHIPS = 24, 2, 4
BATCH = PER_CHIP * CHIPS
DIMS = dict(vocab_size=320, num_layers=3, d_model=64, num_heads=4, d_ff=160,
            max_seq_len=SEQ, remat=True, remat_policy="nothing")
FAMILIES = {
    # parallel residual, two norms, partial rotary, biases everywhere
    "pythia": dict(position="rope", rope_pct=0.25, parallel_block=True,
                   parallel_separate_norms=True, tie_embeddings=False),
    # sequential residual, gated MLP, rmsnorm, grouped KV heads, no bias
    "mistral": dict(position="rope", gated_mlp=True, activation="silu",
                    norm="rmsnorm", num_kv_heads=2, attn_bias=False,
                    mlp_bias=False, tie_embeddings=False),
    # learned positions, the table read by the embedding and by the head
    "tied": dict(position="learned", tie_embeddings=True),
}

COLLECTIVE = re.compile(
    r"= (.+?) (all-gather|all-reduce|all-to-all|collective-permute|"
    r"reduce-scatter)(?:-start)?\(")
SHAPE = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")


def collectives_of(text):
    """(kind, [shape, ...], op_name, channel id) of every collective
    instruction in an HLO text.  The TPU's compiler repeats an async
    collective's instruction in every fusion it is spread over: there the
    channel id tells the copies of one collective apart from another."""
    out = []
    for line in text.splitlines():
        m = COLLECTIVE.search(line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            ch = re.search(r"channel_id=(\d+)", line)
            shapes = [tuple(int(d) for d in s.split(",") if d)
                      for s in SHAPE.findall(m.group(1))]
            out.append((m.group(2), shapes, op.group(1) if op else "",
                        ch.group(1) if ch else None))
    return out


def in_layers(op_name):
    """Whether a JAX path lies inside the layer loop: under the scan's
    scope, whether the scan runs unrolled or as a ``while``."""
    return "(layer_scan)" in op_name


def engine_for(family, stage, precision="bf16", opt="adamw", lr=1e-3,
               **zero):
    cfg = TransformerConfig(**DIMS, **FAMILIES[family])
    topo = MeshTopology.build(MeshConfig(fsdp=CHIPS),
                              devices=jax.devices()[:CHIPS])
    conf = {"train_micro_batch_size_per_device": PER_CHIP,
            "optimizer": {"type": opt, "params": {"lr": lr}},
            "zero_optimization": {"stage": stage, **zero},
            "steps_per_print": 1 << 30}
    if precision == "bf16":
        conf["bf16"] = {"enabled": True}
    return ds.initialize(model=Model(cfg, seed=0), topology=topo,
                         config=conf), cfg


def batch_of(seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, DIMS["vocab_size"],
                                      (BATCH, SEQ)).astype(np.int32)}


@pytest.mark.parametrize("form", ["unrolled", "rolled"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_stage3_gathers_parameters_not_activations(family, form,
                                                   monkeypatch):
    """In both forms of the layer scan: unrolled as these three layers
    run, and rolled as a model over the ceiling runs (the benchmark's 24
    layers), reached by patching the ceiling."""
    if form == "rolled":
        from deepspeed_tpu.models import transformer
        monkeypatch.setattr(transformer, "UNROLL_MAX_LAYERS", 0)
    eng, cfg = engine_for(family, stage=3)
    step = eng._pick_train_step()
    text = step.lower(eng.state, eng.shard_batch(batch_of()),
                      jax.random.PRNGKey(0)).compile().as_text()
    assert ("/while/body/" in text) == (form == "rolled")
    found = collectives_of(text)
    kinds = {f[0] for f in found}
    assert "all-to-all" not in kinds and "collective-permute" not in kinds, \
        [f for f in found if f[0] in ("all-to-all", "collective-permute")]

    def activation_shaped(shape):
        # no parameter has a dim of SEQ; [b, SEQ] is a per-token scalar
        return SEQ in shape and len(shape) > 2

    in_loop = [f for f in found if in_layers(f[2])]
    assert in_loop, "the layer scan holds no collective at all"
    for kind, shapes, op, _ in found:
        if not in_layers(op) and "scatter-add" in op:
            # the one exception, outside the loop: the gradient of the
            # vocabulary-sharded embedding table gathers the batch's
            # cotangent (rows go to their owners), which is fewer bytes
            # than reduce-scattering a whole table from every chip
            continue
        for shape in shapes:
            assert not activation_shaped(shape), (kind, shape, op)
            # the global batch is nobody's to hold, in or out of the loop
            assert not (len(shape) >= 2 and shape[0] == BATCH
                        and shape[1] == SEQ), (kind, shape, op)
            # the logits (whole or a vocabulary shard) are never moved
            assert not (len(shape) == 3 and shape[-1] in (
                cfg.vocab_size, cfg.vocab_size // CHIPS)), (kind, shape, op)
    # it engages: a layer's MLP weight is gathered whole inside the loop
    wi = (cfg.d_model, cfg.d_ff)
    assert any(k == "all-gather" and any(s[-2:] == wi for s in shapes)
               for k, shapes, _, _ in in_loop), in_loop
    # and says so: the gauge is the bf16 bytes of every sharded leaf, less
    # the quarter a chip already holds
    held = jax.tree.leaves(eng.param_specs,
                           is_leaf=lambda x: isinstance(x, P))
    shapes = jax.tree.leaves(eng.param_shapes,
                             is_leaf=lambda x: isinstance(x, tuple))
    sharded = sum(int(np.prod(sh)) for sp, sh in zip(held, shapes)
                  if "fsdp" in jax.tree.leaves(tuple(sp)))
    assert eng.metrics_snapshot()[GAUGE] \
        == sharded * 2 * (CHIPS - 1) // CHIPS


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_stage3_matches_stage0(family):
    """Same batch, one SGD step in float32: loss and every updated
    parameter agree with the unsharded engine's."""
    out = {}
    for stage in (0, 3):
        eng, _ = engine_for(family, stage, precision="fp32", opt="sgd",
                            lr=0.1)
        m = eng.train_batch(batch_of(seed=3))
        out[stage] = (float(m["loss"]),
                      jax.tree.map(np.asarray, eng.state.master))
    assert out[3][0] == pytest.approx(out[0][0], rel=1e-5)
    flat0, flat3 = jax.tree.leaves(out[0][1]), jax.tree.leaves(out[3][1])
    for a, b in zip(flat0, flat3):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=2e-6)


def test_below_stage3_nothing_is_stated():
    """No gauge, and the loss the engine runs is the one it was given."""
    for stage in (0, 2):
        eng, _ = engine_for("pythia", stage)
        eng._pick_train_step()
        assert GAUGE not in eng.metrics_snapshot()
        assert eng.loss_fn is eng._model.loss_fn


def test_persistence_threshold_is_per_layer():
    topo = MeshTopology.build(MeshConfig(fsdp=CHIPS),
                              devices=jax.devices()[:CHIPS])
    pol = ZeroPolicy(stage=3, topology=topo,
                     param_persistence_threshold=10_000)
    # 24 x 2048 = 49k elements stacked, 2048 a layer: a norm scale or bias
    assert pol.param_spec(("layers", "norm"), (24, 2048)) == P()
    assert pol.param_spec(("layers", "heads", "head_dim"),
                          (24, 16, 128)) == P()
    # 16k a layer: above the threshold
    assert pol.param_spec(("layers", "embed", None),
                          (24, 2048, 8)) == P(None, "fsdp")
    # a leaf that is not stacked is compared whole, as before
    assert pol.param_spec(("embed",), (2048,)) == P()
    assert pol.param_spec(("embed", "mlp"), (128, 128)) == P("fsdp")
    # masters and gradients of the small leaves stay sharded (stage 1)
    assert pol.master_spec(("layers", "norm"), (24, 2048)) \
        == P(None, "fsdp")


def test_checkpoint_of_the_old_layout_loads(tmp_path):
    """Before, the threshold saw the stacked leaf, so the small per-layer
    leaves were saved sharded; threshold 0 writes that layout.  It loads
    into the replicated layout, and back."""
    def eng_with(threshold):
        return engine_for("pythia", 3, precision="fp32",
                          param_persistence_threshold=threshold)[0]

    old, new = eng_with(0), eng_with(10_000)
    assert old.param_specs["blocks"]["ln1"]["scale"] == P(None, "fsdp")
    assert new.param_specs["blocks"]["ln1"]["scale"] == P()
    old.train_batch(batch_of(seed=1))
    old.save_checkpoint(str(tmp_path / "old"), tag="t")
    new.load_checkpoint(str(tmp_path / "old"), tag="t")
    want = jax.tree.map(np.asarray, old.state.master)
    for a, b in zip(jax.tree.leaves(want),
                    jax.tree.leaves(new.state.master)):
        np.testing.assert_array_equal(np.asarray(b), a)
    loss_new = float(new.train_batch(batch_of(seed=2))["loss"])
    loss_old = float(old.train_batch(batch_of(seed=2))["loss"])
    assert loss_new == pytest.approx(loss_old, rel=1e-5)
    new.save_checkpoint(str(tmp_path / "new"), tag="t")
    back = eng_with(0)
    back.load_checkpoint(str(tmp_path / "new"), tag="t")
    for a, b in zip(jax.tree.leaves(new.state.master),
                    jax.tree.leaves(back.state.master)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


# stage 3 on the other meshes that run the same forward: one rule that
# adapts to the mesh it finds (mesh, zero options, model options)
MESHES = {
    "tensor": (dict(fsdp=2, tensor=2), {}, {}),
    "data-fsdp": (dict(data=2, fsdp=4), {}, {}),
    # the quantised gradient reduction takes `data` into a shard_map:
    # inside it only `fsdp` is left to state
    "qgz": (dict(data=2, fsdp=4), {"zero_quantized_gradients": True}, {}),
    "ulysses": (dict(fsdp=2, seq=2), {}, {}),
    "moe": (dict(fsdp=2, expert=2), {}, dict(num_experts=4, moe_top_k=2)),
    "hpz": (dict(fsdp=4), {"zero_hpz_partition_size": 2}, {}),
    "gas": (dict(fsdp=4), {}, {}),
    # the pipeline's stages run inside a shard_map of their own: that
    # loss takes no placement and runs as it did
    "pipe": (dict(pipe=2, fsdp=2), {}, {}),
}


@pytest.mark.parametrize("case", sorted(MESHES))
def test_stage3_on_other_meshes_matches_stage0(case):
    mesh, zero, model = MESHES[case]
    seen = {}
    for stage in (0, 3):
        cfg = TransformerConfig(**{**DIMS, **FAMILIES["pythia"], **model,
                                   "num_layers": 4})
        conf = {"train_micro_batch_size_per_device": PER_CHIP,
                "optimizer": {"type": "sgd", "params": {"lr": 0.1}},
                "zero_optimization": {"stage": stage,
                                      **(zero if stage == 3 else {})},
                "mesh": mesh, "steps_per_print": 1 << 30,
                "allow_feature_degradation": case == "qgz"}
        if case == "gas":
            conf["gradient_accumulation_steps"] = 2
        eng = ds.initialize(model=Model(cfg, seed=0), config=conf)
        batch = {"input_ids": np.random.default_rng(1).integers(
            0, cfg.vocab_size, (eng.train_batch_size, SEQ)).astype(np.int32)}
        seen[stage] = [float(eng.train_batch(batch)["loss"])
                       for _ in range(2)]
        stated = GAUGE in eng.metrics_snapshot()
        assert stated == (stage == 3 and case != "pipe")
        if stage == 3 and case == "tensor":
            # a gathered weight keeps its tensor split
            wi = eng.param_specs["blocks"]["mlp"]["wi"]
            assert "fsdp" in jax.tree.leaves(tuple(wi))
            assert eng.use_specs["blocks"]["mlp"]["wi"] \
                == P(None, None, "tensor")
    # the int8 gradient wire moves the second loss a little
    assert seen[3] == pytest.approx(
        seen[0], rel=1e-4 if case == "qgz" else 2e-6)
