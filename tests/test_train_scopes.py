"""The train step names its own parts: the ``jax.named_scope`` names in
the training forward (``models/transformer.py``), the loss, the step
(``runtime/engine.py``) and ZeRO-3's gathers (``parallel/zero.py``), as
the engine's own compiled step carries them; that they cost the compiled
step nothing; and their reader (``benchmarks/lib/train_scopes.py``) on
hand-written operations.

The three pass markers (``jvp(``, ``transpose(``,
``rematted_computation``) are what autodiff and ``jax.checkpoint`` of
this tree's JAX write into an operation's path: the contract between
program and reader, held here on the compiled text."""

import contextlib
import json
import os
import re

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmarks.lib import train_scopes as ts
from benchmarks.lib.common import load_module, reader_path
from deepspeed_tpu.comm import MeshTopology
from deepspeed_tpu.config import MeshConfig
from deepspeed_tpu.models.transformer import Model, TransformerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, PER_CHIP, CHIPS = 24, 2, 4
MODEL = ("embed", "layer_scan", "qkv", "attn", "attn_out", "ffn", "unembed",
         "loss")
STEP = ("cast_params", "grad_accumulate", "grad_epilogue", "optimizer")
NEW = ("train_device_step_ms", "train_recompute_share", "train_attn_share",
       "train_loss_head_share", "train_update_share", "train_unscoped_share",
       "zero3_gather_passes")


def lowered_step(stage: int):
    """The engine's own train step, lowered: two layers of pythia's block
    (parallel residual, partial rotary, biases), each recomputed whole,
    over four virtual devices."""
    cfg = TransformerConfig(
        vocab_size=320, num_layers=2, d_model=64, num_heads=4, d_ff=160,
        max_seq_len=SEQ, remat=True, remat_policy="nothing",
        position="rope", rope_pct=0.25, parallel_block=True,
        parallel_separate_norms=True, tie_embeddings=False)
    mesh = MeshConfig(fsdp=CHIPS) if stage == 3 else MeshConfig(data=CHIPS)
    eng = ds.initialize(
        model=Model(cfg, seed=0),
        topology=MeshTopology.build(mesh, devices=jax.devices()[:CHIPS]),
        config={"train_micro_batch_size_per_device": PER_CHIP,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": stage,
                                      "param_persistence_threshold": 0},
                "gradient_clipping": 1.0, "steps_per_print": 1 << 30})
    batch = eng.shard_batch(
        {"input_ids": np.zeros((PER_CHIP * CHIPS, SEQ), np.int32)})
    return eng._pick_train_step().lower(
        eng.state, batch, jax.random.PRNGKey(0))


def compiled_step_text(stage: int) -> str:
    """The optimised HLO of that step."""
    return lowered_step(stage).compile().as_text()


def without_metadata(text: str) -> str:
    """The text less each instruction's ``metadata={...}`` and the
    module's tables of source files, functions and stack frames."""
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:\d+ .*\n)*", "", text, flags=re.M)
    return re.sub(r",?\s*metadata=\{[^}]*\}", "", text)


@pytest.fixture(scope="module")
def lowered():
    return {stage: lowered_step(stage) for stage in (1, 3)}


@pytest.fixture(scope="module")
def texts(lowered):
    return {stage: low.compile().as_text() for stage, low in lowered.items()}


@pytest.fixture(scope="module")
def booked(texts):
    """stage -> {(scope, pass)} over the compiled text's op_names, by the
    reader's own classifier; and the paths themselves."""
    out = {}
    for stage, text in texts.items():
        paths = set(re.findall(r'op_name="([^"]*)"', text))
        out[stage] = ({ts.classify([p]) for p in paths}, paths)
    return out


@pytest.fixture(scope="module")
def written(lowered):
    """stage -> {(scope, pass)} over the paths JAX wrote into the step
    before any compiler touched it."""
    return {stage: {ts.classify([p]) for p in re.findall(
        r'loc\("([^"]*)"', low.as_text(debug_info=True))
        if not p.endswith(".py")}
        for stage, low in lowered.items()}


# The scope's one operation converts each gradient to float32.  With the
# layer scan unrolled the CPU's compiler (which computes bf16 in f32)
# folds that convert into the gradients' concatenation under stage 3, and
# no instruction is left to carry the name; on the chip the scope never
# had an operation of its own (PERF.md section 5's table has no such row)
FOLDED = {("grad_accumulate", 3)}


@pytest.mark.parametrize("stage", [1, 3])
@pytest.mark.parametrize("scope", MODEL + STEP)
def test_the_compiled_step_carries_every_scope(booked, written, stage,
                                               scope):
    assert scope in {s for s, _ in written[stage]}
    if (scope, stage) not in FOLDED:
        assert scope in {s for s, _ in booked[stage][0]}


@pytest.mark.parametrize("stage", [1, 3])
@pytest.mark.parametrize("scope", ["qkv", "attn", "ffn"])
def test_a_layers_scopes_run_in_all_three_passes(booked, stage, scope):
    assert {p for s, p in booked[stage][0] if s == scope} \
        == {"forward", "recompute", "backward"}


@pytest.mark.parametrize("stage", [1, 3])
def test_the_steps_own_scopes_lie_under_no_transform(booked, written, stage):
    pairs, paths = booked[stage]
    for scope in STEP:
        assert {p for s, p in written[stage] if s == scope} == {"update"}
        assert {p for s, p in pairs if s == scope} == (
            set() if (scope, stage) in FOLDED else {"update"})
    under = [p for p in paths if "optimizer" in ts.components(p)]
    assert under and not any("jvp(" in p or "transpose(" in p for p in under)


@pytest.fixture(scope="module")
def rolled_paths():
    """The stage-1 step's paths with the layer scan rolled: what a model
    deeper than the ceiling compiles to."""
    from deepspeed_tpu.models import transformer
    with pytest.MonkeyPatch.context() as m:
        m.setattr(transformer, "UNROLL_MAX_LAYERS", 0)
        text = compiled_step_text(1)
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("form", ["unrolled", "rolled"])
def test_the_three_pass_markers_are_what_this_jax_writes(booked,
                                                         rolled_paths, form):
    """A scope in the scan body is a whole component; one directly under
    the transform is wrapped by it.  Unrolled, the body hangs straight
    under the scan's scope; rolled, under its ``while/body``."""
    paths = booked[1][1] if form == "unrolled" else rolled_paths
    loop = "" if form == "unrolled" else "while/body/"
    assert any("/while/body/" in p for p in paths) == (form == "rolled")
    body = loop + "closed_call"
    assert f"jit(train_step)/jvp(layer_scan)/{body}/qkv/add" in paths
    back = f"jit(train_step)/transpose(jvp(layer_scan))/{body}/checkpoint/"
    assert any(p.startswith(back + "rematted_computation/ffn/")
               for p in paths)
    assert any(p.startswith(back + "ffn/") for p in paths)
    # what the scan does outside its body keeps the scan's own scope:
    # rolled, a dynamic slice and update a trip; unrolled, the static
    # slices of the stacks and the gradients' concatenation
    own = ([f"jvp(layer_scan)/{loop}dynamic_slice",
            f"transpose(jvp(layer_scan))/{loop}dynamic_update_slice"]
           if form == "rolled" else
           ["jvp(layer_scan)/slice", "transpose(jvp(layer_scan))/concatenate"])
    for tail, pass_ in zip(own, ("forward", "backward")):
        assert "jit(train_step)/" + tail in paths
        assert ts.classify(["jit(train_step)/" + tail]) \
            == ("layer_scan", pass_)
    assert any(p.startswith("jit(train_step)/jvp(loss)/") for p in paths)
    assert any(p.startswith("jit(train_step)/transpose(jvp(unembed))/")
               for p in paths)


def test_zero3_alone_names_its_gathers(booked):
    zero = lambda stage: {(s, p) for s, p in booked[stage][0]
                          if s.startswith("zero_")}
    assert zero(1) == set()
    # the table and the head outside the loop; every layer's slices in the
    # recomputation.  The cotangent's constraint outside the loop survives
    # on the CPU (on the chip the reduce-scatter is a ring of permutes
    # inside the backward matmul and carries the matmul's name)
    assert {("zero_gather", "forward"), ("zero_gather", "recompute"),
            ("zero_scatter", "backward")} <= zero(3)


@pytest.mark.parametrize("stage", [1, 3])
def test_the_scopes_cost_the_compiled_step_nothing(texts, stage, monkeypatch):
    """Compiled with ``jax.named_scope`` a null context, the optimised HLO
    is the same text once ``metadata={...}`` is stripped."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled_step_text(stage)
    assert "/qkv/" not in bare and "/qkv/" in texts[stage]
    assert without_metadata(bare) == without_metadata(texts[stage])


# ------------------------------------------------- the reader's booking
FUS = ("%fusion.{0} = bf16[8]{{0}} fusion(%p), kind=kLoop, "
       "calls=%fused_computation.{0}")
BODY = ("jit(train_step)/transpose(jvp(layer_scan))/while/body/closed_call/"
        "checkpoint")


@pytest.mark.parametrize("path,want", [
    ("jit(train_step)/jvp(layer_scan)/while/body/closed_call/qkv/dot_general",
     ("qkv", "forward")),
    ("jit(train_step)/jvp(loss)/reduce_sum", ("loss", "forward")),
    ("jit(train_step)/transpose(jvp(loss))/mul", ("loss", "backward")),
    ("jit(train_step)/transpose(jvp(unembed))/dot_general",
     ("unembed", "backward")),
    ("jit(train_step)/transpose(embed)/scatter-add", ("embed", "backward")),
    (BODY + "/rematted_computation/ffn/tanh", ("ffn", "recompute")),
    (BODY + "/ffn/dot_general", ("ffn", "backward")),
    (BODY + "/attn_out/bshk,hkd->bsd/dot_general", ("attn_out", "backward")),
    # the gather is a gather wherever it is made
    ("jit(train_step)/jvp(embed)/zero_gather/sharding_constraint",
     ("zero_gather", "forward")),
    (BODY + "/rematted_computation/zero_gather/sharding_constraint",
     ("zero_gather", "recompute")),
    # update before recompute before backward
    ("jit(train_step)/optimizer/sub", ("optimizer", "update")),
    (BODY + "/rematted_computation/grad_accumulate/add",
     ("grad_accumulate", "update")),
    ("jit(train_step)/transpose(jvp())/grad_epilogue/mul",
     ("grad_epilogue", "update")),
    # the scan's own slices and stacking, outside its body
    ("jit(train_step)/jvp(layer_scan)/while/body/dynamic_slice",
     ("layer_scan", "forward")),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/"
     "dynamic_update_slice", ("layer_scan", "backward")),
    # no scope: the pass still counts; a name inside another is no scope
    ("jit(train_step)/jvp()/while/body/dynamic_slice", ("none", "forward")),
    ("jit(train_step)/transpose(jvp())/while/body/dynamic_update_slice",
     ("none", "backward")),
    ("jit(train_step)/jvp(loss_scale)/mul", ("none", "forward")),
    ("jit(train_step)/jvp()/attn_output/mul", ("none", "forward")),
])
def test_scope_and_pass_of_a_path(path, want):
    assert ts.classify([path, "@transformer.py"]) == want


def test_an_operation_with_no_path_is_unscoped_forward():
    assert ts.classify(()) == ("none", "forward")
    assert ts.classify(("@engine.py",)) == ("none", "forward")


def hand_written():
    ops = [(0.0, 1.0, "%while.1 = (s32[]) while(%t), body=%b"),  # container
           (0.0, 0.2, FUS.format(1)), (0.2, 0.5, FUS.format(2)),
           (0.5, 0.6, FUS.format(3)), (0.6, 0.7, "%copy.4 = bf16[8] copy(%p)"),
           (0.7, 0.8, "%all-gather-start.5 = (bf16[2], bf16[8]) "
                      "all-gather-start(%p), channel_id=3"),
           (0.8, 0.9, "%all-gather-done.5 = bf16[8] all-gather-done(%a)"),
           (0.9, 1.0, FUS.format(6))]
    names = {
        "fusion.1": ("jit(train_step)/jvp(layer_scan)/while/body/closed_call/"
                     "attn/bqhrd,bkhd->bhrqk/dot_general",),
        "fusion.2": (BODY + "/rematted_computation/attn/exp",),
        "fusion.3": ("jit(train_step)/transpose(jvp(loss))/mul",),
        "copy.4": ("jit(train_step)/jvp()/while/body/dynamic_slice",),
        "all-gather-start.5": (BODY + "/rematted_computation/zero_gather/"
                               "sharding_constraint",),
        "all-gather-done.5": (BODY + "/rematted_computation/zero_gather/"
                              "sharding_constraint",),
        "fusion.6": ("jit(train_step)/optimizer/add",)}
    return ops, names


def test_booking_leaves_containers_out_and_sums_both_ways():
    ops, names = hand_written()
    got = ts.book(ops, names)
    assert got["busy_s"] == pytest.approx(1.0)
    assert got["op_s"] == pytest.approx(1.0)
    assert got["by_scope"] == pytest.approx(
        {"attn": 0.5, "loss": 0.1, "none": 0.1, "zero_gather": 0.2,
         "optimizer": 0.1})
    assert got["by_pass"] == pytest.approx(
        {"forward": 0.3, "recompute": 0.5, "backward": 0.1, "update": 0.1})
    assert sum(got["by_scope"].values()) == pytest.approx(got["op_s"])
    assert sum(got["by_pass"].values()) == pytest.approx(got["op_s"])
    assert got["by_scope_pass"]["attn|recompute"] == pytest.approx(0.3)
    assert got["by_scope_group"] == pytest.approx(
        {"attn|matmul_fusions": 0.2, "attn|other_fusions": 0.3,
         "loss|other_fusions": 0.1, "none|copy": 0.1,
         "zero_gather|collectives": 0.2, "optimizer|other_fusions": 0.1})
    # a collective's two halves are one event and both halves' seconds
    assert got["collectives"] == {"zero_gather|recompute|all-gather": {
        "events": 1, "seconds": pytest.approx(0.2)}}
    assert got["unscoped"] == pytest.approx(
        {"copy while/body/dynamic_slice": 0.1})


def threads_with_steps(*starts):
    return {(0, 0): [(s - 0.01, s - 0.005, "ds.train.stage", {"step": i})
                     for i, s in enumerate(starts, 1)]
            + [(s, s + 0.002, "ds.train.dispatch", {"step": i})
               for i, s in enumerate(starts, 1)]}


def test_steps_are_the_programs_dispatch_spans_begun_in_the_window():
    ops, names = hand_written()
    threads = threads_with_steps(-0.3, 0.05, 0.55, 1.2)
    whole = ts.Booked(threads, ops, names)
    assert whole.steps == 2                     # first operation to last
    assert whole.device_step_ms() == pytest.approx(500.0)
    cut = ts.Booked(threads, ops, names, window=(0.5, 1.5))
    assert cut.steps == 2                       # 0.55 and 1.2
    assert cut.booked["busy_s"] == pytest.approx(0.5)
    assert cut.idle["idle_s"] == pytest.approx(0.5)
    assert cut.share("by_pass", "update") == pytest.approx(20.0)
    assert cut.share("by_scope", "unembed", "loss") == pytest.approx(20.0)
    assert cut.share("by_scope", "none") == pytest.approx(20.0)
    assert cut.passes_under("zero_gather") == 1
    assert cut.passes_under("zero_scatter") is None
    assert ts.Booked({}, ops, names).device_step_ms() is None


def write_trace(tmp_path):
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(b"")
    return str(tmp_path)


def test_of_reads_once_and_prints_one_line(tmp_path, monkeypatch, capsys):
    ops, names = hand_written()
    calls = []
    monkeypatch.setattr(ts.program_spans, "read", lambda path: (
        calls.append(path), (threads_with_steps(0.05, 0.55), ops, names))[1])
    rec = {"kind": "train", "trace_dir": write_trace(tmp_path),
           "trace": {"window": (0.0, 1.0)}, "config": {}}
    got = {n: load_module(reader_path("layer_metrics", n), "metric_" + n)
           .read(rec) for n in NEW}
    assert got == pytest.approx({
        "train_device_step_ms": 500.0, "train_recompute_share": 50.0,
        "train_attn_share": 50.0, "train_loss_head_share": 10.0,
        "train_update_share": 10.0, "train_unscoped_share": 10.0,
        "zero3_gather_passes": 1})
    assert len(calls) == 1
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["note"] for ln in lines] == ["train_scopes"]
    line = lines[0]
    assert line["steps"] == 2 and line["busy_s"] == pytest.approx(1.0)
    assert sum(line["by_scope"].values()) == pytest.approx(line["op_s"])
    assert sum(line["by_pass"].values()) == pytest.approx(line["op_s"])
    assert line["unscoped_top"] == [["copy while/body/dynamic_slice",
                                     pytest.approx(0.1)]]
    assert line["idle_s"] == 0.0 and line["reader_s"] >= 0.0


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("why", ["untraced", "no_file", "serving",
                                 "no_training_scope"])
def test_readers_report_nothing(tmp_path, monkeypatch, name, why):
    """No trace, a serving cell, and the parent's trace, whose operations
    carry no training scope (its ``ds.train.*`` spans are there)."""
    ops, names = hand_written()
    monkeypatch.setattr(ts.program_spans, "read", lambda path: (
        threads_with_steps(0.05), ops,
        {k: (v[0].replace("/attn/", "/").replace("/optimizer/", "/")
             .replace("(loss)", "()").replace("/zero_gather/", "/")
             .replace("(layer_scan)", "()"),)
         for k, v in names.items()} if why == "no_training_scope" else names))
    traced = write_trace(tmp_path)
    rec = {"untraced": {"kind": "train", "trace_dir": None},
           "no_file": {"kind": "train", "trace_dir": "/nonexistent"},
           "serving": {"kind": "serve", "trace_dir": traced},
           "no_training_scope": {"kind": "train", "trace_dir": traced}}[why]
    reader = load_module(reader_path("layer_metrics", name), "metric_" + name)
    assert reader.read(rec) is None


def test_each_new_entry_has_a_reader_and_the_agreed_keys():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    both = ["train-1chip", "train-zero3-4chip"]
    for name in NEW:
        m = by_name[name]
        assert (m["moves"], m["better"]) == ("train_tokens_per_s", "lower")
        assert m["workloads"] == (both[1:] if name.startswith("zero3")
                                  else both)
        assert m["source"] == ("program_span" if name.endswith("_ms")
                               else "device_trace")
        assert m["layer"] in ("Train step", "Kernels", "Parallelism")
        assert os.path.exists(reader_path("layer_metrics", name))
    # appended, in this order, after everything the benchmark had then
    # (later PRs append behind them)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == list(NEW)
    assert at >= 88 - len(NEW)
