"""The training forward's layer scan runs unrolled over its whole trip
count where it holds up to ``models/transformer.py`` ``UNROLL_MAX_LAYERS``
layers, and rolled above that.  The two forms are one ``lax.scan`` with another ``unroll``:
the same numbers (here: loss and every gradient leaf, every way a model
uses the scan), another compiled program (no ``while``, no dynamic slice
or update of a stack, a stacked gradient assembled by one
``concatenate``).  The rolled form is reached by patching the module's
constant, as ISSUE 41 rules: nothing a user sets chooses."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.comm import MeshTopology
from deepspeed_tpu.config import MeshConfig
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.transformer import (Model, TransformerConfig,
                                              init_params, lm_loss_fn)

GAUGE = "training_layers_unrolled"
SEQ, BATCH = 16, 4
DIMS = dict(vocab_size=96, num_layers=4, d_model=32, num_heads=4, d_ff=80,
            max_seq_len=SEQ, attention_impl="xla")
MOE = dict(num_experts=4, moe_top_k=2, moe_d_ff=24)
# name -> (config, how the loss is built and called)
CASES = {
    "no_remat": (dict(remat=False), {}),
    "remat_nothing": (dict(remat=True, remat_policy="nothing"), {}),
    "remat_dots": (dict(remat=True, remat_policy="dots"), {}),
    # every layer an expert layer: the aux metrics are the scan's stacked
    # outputs, their means the loss's second term
    "moe_every_layer": (dict(remat=True, remat_policy="dots", **MOE), {}),
    # a dense layer in front, periods of (window, full), the last one cut:
    # 1 + 2 x 2 + 1 layers, two of them outside the scan
    "pattern_lead_and_tail": (
        dict(remat=True, remat_policy="nothing", num_layers=6,
             num_dense_layers=1, layer_pattern=("window", "full"),
             attn_window=8, **MOE), {}),
    "pld": (dict(remat=True, remat_policy="nothing"), dict(pld=True)),
    "ltd": (dict(remat=True, remat_policy="nothing"), dict(ltd_keep=8)),
}


def batch_of(cfg, pld=False):
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (BATCH, SEQ)).astype(np.int32))}
    if pld:
        batch["_pld_theta"] = jnp.full((BATCH,), 0.5, jnp.float32)
    return batch


def loss_and_grads(case, monkeypatch, ceiling=None):
    """(loss, aux, gradients) of one case's loss under ``jax.jit``; the
    scan rolled where ``ceiling`` is given."""
    over, how = CASES[case]
    cfg = TransformerConfig(**{**DIMS, **over})
    if ceiling is not None:
        monkeypatch.setattr(T, "UNROLL_MAX_LAYERS", ceiling)
    assert T.layers_unrolled(cfg) == (
        0 if ceiling is not None
        else cfg.layer_plan[1] * len(cfg.layer_pattern))
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    loss = lm_loss_fn(cfg, T._resolve_attention(cfg), **how)

    def scalar(p, b, r):
        out = loss(p, b, r)
        return out if isinstance(out, tuple) else (out, {})

    (value, aux), grads = jax.jit(jax.value_and_grad(scalar, has_aux=True))(
        params, batch_of(cfg, pld=how.get("pld", False)),
        jax.random.PRNGKey(7))
    return value, aux, grads


@pytest.mark.parametrize("case", sorted(CASES))
def test_unrolled_equals_rolled(case, monkeypatch):
    """Loss, the experts' aux means and every gradient leaf: the layers'
    arithmetic is the body's, traced once for both forms, and a leaf's
    gradient is one layer's product either way, so they agree to float32
    round-off (XLA fuses the two programs differently)."""
    loss_u, aux_u, grads_u = loss_and_grads(case, monkeypatch)
    loss_r, aux_r, grads_r = loss_and_grads(case, monkeypatch, ceiling=0)
    np.testing.assert_allclose(loss_u, loss_r, rtol=1e-6)
    assert sorted(aux_u) == sorted(aux_r)
    assert ("moe_aux_loss" in aux_u) == ("num_experts" in CASES[case][0])
    for k in aux_u:
        np.testing.assert_allclose(aux_u[k], aux_r[k], rtol=1e-6, atol=1e-7)
    flat_u = jax.tree_util.tree_leaves_with_path(grads_u)
    flat_r = jax.tree.leaves(grads_r)
    assert len(flat_u) == len(flat_r)
    # one scale for all leaves: a key bias's gradient is zero but for
    # round-off (the softmax is blind to it)
    scale = max(float(jnp.abs(r).max()) for r in flat_r)
    for (path, u), r in zip(flat_u, flat_r):
        np.testing.assert_allclose(
            u, r, rtol=2e-5, atol=2e-6 * scale,
            err_msg=jax.tree_util.keystr(path))


# ---------------------------------------------------------------- ZeRO-3
def test_zero3_unrolled_equals_rolled(monkeypatch):
    """ZeRO stage 3 over four virtual devices (the engine and batch of
    ``tests/test_zero3_placement.py``: pythia's block, three layers),
    float32, one SGD step: each layer's slice is gathered inside the body
    in both forms; the loss and every updated parameter agree."""
    from tests.test_zero3_placement import batch_of as zero3_batch
    from tests.test_zero3_placement import engine_for
    out = {}
    for form, ceiling in (("unrolled", None), ("rolled", 0)):
        with monkeypatch.context() as m:
            if ceiling is not None:
                m.setattr(T, "UNROLL_MAX_LAYERS", ceiling)
            eng, _ = engine_for("pythia", stage=3, precision="fp32",
                                opt="sgd", lr=1e-2,
                                param_persistence_threshold=0)
            loss = float(eng.train_batch(zero3_batch())["loss"])
            out[form] = (loss, jax.tree.map(np.asarray, eng.state.master),
                         eng.metrics_snapshot()[GAUGE])
    assert out["unrolled"][2] == 3 and out["rolled"][2] == 0
    np.testing.assert_allclose(out["unrolled"][0], out["rolled"][0],
                               rtol=1e-6)
    for u, r in zip(jax.tree.leaves(out["unrolled"][1]),
                    jax.tree.leaves(out["rolled"][1])):
        np.testing.assert_allclose(u, r, rtol=1e-5, atol=1e-7)


# ------------------------------------------------------ the program's shape
LAYERS = 3
# (dtype, dims, opcode) of an instruction with one array result; a
# ``while`` returns a tuple and is counted by its opcode alone
INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.-]+ = (\w+)\[([0-9,]*)\][^ ]* "
                   r"([\w-]+)\(", re.M)
WHILE = re.compile(r"\) while\(")


def train_engine(layers=LAYERS):
    cfg = TransformerConfig(**{**DIMS, "num_layers": layers, "remat": True,
                               "remat_policy": "dots"})
    eng = ds.initialize(
        model=Model(cfg, seed=0),
        topology=MeshTopology.build(MeshConfig(data=1),
                                    devices=jax.devices()[:1]),
        config={"train_micro_batch_size_per_device": BATCH,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "steps_per_print": 1 << 30})
    return eng, cfg


def compiled_text(eng, cfg):
    return eng._pick_train_step().lower(
        eng.state, eng.shard_batch(batch_of(cfg)),
        jax.random.PRNGKey(0)).compile().as_text()


def stack_shapes(eng):
    """The shapes of the stacked per-layer leaves, as HLO prints them."""
    return {",".join(str(d) for d in np.shape(leaf))
            for leaf in jax.tree.leaves(eng.state.master["blocks"])}


@pytest.fixture(scope="module")
def unrolled_step():
    eng, cfg = train_engine()
    return eng, compiled_text(eng, cfg)


def test_the_unrolled_step_has_no_loop(unrolled_step):
    """No ``while`` at all: the step holds no other loop."""
    assert not WHILE.search(unrolled_step[1])


@pytest.mark.parametrize("opcode", ["dynamic-update-slice", "dynamic-slice",
                                    "pad"])
def test_the_unrolled_step_cuts_no_stack(unrolled_step, opcode):
    """No dynamic slice, dynamic update or zero-padding whose result is a
    weight stack: a layer's gradient is neither written into its stack a
    trip nor padded to the stack and summed."""
    eng, text = unrolled_step
    stacks = stack_shapes(eng)
    assert not [dims for _, dims, op in INSTR.findall(text)
                if op == opcode and dims in stacks]


def test_a_stacked_gradient_is_concatenated_from_its_layers(unrolled_step):
    """A stacked matrix's gradient is ONE ``concatenate`` of its layers'
    (the transposed scan's own, by its path), each layer an operand once:
    no chain of partial stacks.  XLA may copy that instruction into a
    consumer's fusion (the gradient norm's), so it can stand twice."""
    eng, text = unrolled_step
    blocks = eng.state.master["blocks"]
    for part, name in (("mlp", "wi"), ("mlp", "wo"), ("attn", "wo")):
        dims = ",".join(str(d) for d in blocks[part][name].shape)
        lines = [l for l in text.splitlines()
                 if re.search(r"= \w+\[%s\][^ ]* concatenate\(" % dims, l)]
        assert lines, (part, name)
        for line in lines:
            operands = line.split(" concatenate(")[1].split(")")[0]
            assert len(operands.split(",")) == LAYERS, line
            assert 'transpose(jvp(layer_scan))/concatenate"' in line, line


@pytest.mark.parametrize("ceiling,whiles", [(LAYERS, 0), (LAYERS - 1, 2)])
def test_the_trip_count_chooses_the_form(monkeypatch, ceiling, whiles):
    """At the ceiling the step is unrolled and the gauge reads its
    layers; one period above it the forward's and the backward's
    ``while`` are there as before, and the gauge reads 0."""
    monkeypatch.setattr(T, "UNROLL_MAX_LAYERS", ceiling)
    eng, cfg = train_engine()
    text = compiled_text(eng, cfg)
    assert len(WHILE.findall(text)) == whiles
    eng.train_batch({"input_ids": np.zeros((BATCH, SEQ), np.int32)})
    snap = eng.metrics_snapshot()
    assert snap[GAUGE] == (LAYERS if whiles == 0 else 0)
    assert GAUGE in eng.metrics.prometheus_text()
    if whiles:
        stacks = stack_shapes(eng)
        assert [d for _, d, op in INSTR.findall(text)
                if op == "dynamic-update-slice" and d in stacks]


def test_nothing_a_user_sets_chooses_the_form():
    assert not hasattr(TransformerConfig(**DIMS), "scan_unroll")
    with pytest.raises(TypeError):
        TransformerConfig(**DIMS, scan_unroll=2)


@pytest.mark.parametrize("cell,unrolled", [("train-1chip", 6),
                                           ("train-zero3-4chip", 0)])
def test_the_benchmarks_train_cells(cell, unrolled):
    """``train-1chip``'s six layers run unrolled (the gauge reads 6 in its
    engine); ``train-zero3-4chip``'s 24 keep the rolled scan, whose
    program the chip preferred (PERF.md section 6, PR 41)."""
    from benchmarks.lib.common import load_cell
    from benchmarks.lib.weights import transformer_config
    cfg = transformer_config(load_cell(cell)[2], remat=True)
    assert T.layers_unrolled(cfg) == unrolled
