"""DeepSeek-V2's block (``deepseek-v2-tiny``): latent attention with a
query latent under YaRN in every layer, a leading dense layer, and a
share of WHOLE device groups of softmax-routed experts behind the
group-limited greedy router, against the benchmark's own plain reference
(``benchmarks/reference/deepseek-v2-d5.py``, imported by path): through
``apply``; through the engine's chunked prefill and decode past the toy
original context; the YaRN table against its closed form; the two group
rules on a crafted score row; every wrong forward the reference knows;
the four shares adding up to the uncut layer; a prefix hit under
eviction pressure; the spans' new counts; the configuration's file and
the benchmark's arithmetic."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceConfig, InferenceEngine
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, apply, init_params
from deepspeed_tpu.parallel import moe as M
from test_falcon_h1 import ROOT, TOL, _load, rel

BLOCK, BUDGET = 8, 37


@pytest.fixture(scope="module")
def ref():
    return _load("benchmarks/reference/deepseek-v2-d5.py", "dsv2_ref")


@pytest.fixture(scope="module")
def tiny():
    cfg = build_config("deepseek-v2-tiny")
    axes = {}

    def init(key):
        params, axes["axes"] = init_params(cfg, key)
        return params

    return cfg, jax.jit(init)(jax.random.PRNGKey(5)), axes["axes"]


def ref_config(cfg, **over):
    """What the reference reads of a configuration file, for ``cfg``."""
    md, y = cfg.mla_dims, cfg.rope_yarn
    return {**dict(
        num_hidden_layers=cfg.num_layers,
        first_k_dense_replace=cfg.num_dense_layers, rms_norm_eps=cfg.eps,
        rope_theta=cfg.rope_theta, rope_scaling=dict(
            factor=y.factor, original_max_position_embeddings=y.original,
            beta_fast=y.beta_fast, beta_slow=y.beta_slow, mscale=y.mscale,
            mscale_all_dim=y.mscale_all_dim),
        num_attention_heads=cfg.num_heads, qk_nope_head_dim=md.nope_dim,
        qk_rope_head_dim=md.rope_dim, v_head_dim=md.value_dim,
        kv_lora_rank=md.kv_rank, q_lora_rank=md.q_rank,
        num_experts_per_tok=cfg.moe_top_k,
        routed_scaling_factor=cfg.moe_route_scale, n_group=cfg.moe_groups,
        topk_group=cfg.moe_groups_kept, norm_topk_prob=cfg.moe_norm_topk,
        n_routed_experts=cfg.experts_here,
        experts_held=list(cfg.experts_held or (0, cfg.num_experts))),
        **over}


def share_of(tiny, first, count):
    """The model as the holder of experts ``[first, first + count)``."""
    cfg, params, axes = tiny
    params = dict(params, blocks=dict(params["blocks"], experts=jax.tree.map(
        lambda a: a[:, first:first + count], params["blocks"]["experts"])))
    return (build_config("deepseek-v2-tiny", experts_held=(first, count)),
            params, axes)


@pytest.fixture(scope="module")
def held(tiny):
    """ONE SHARE: groups 2 and 3 of eight, experts 4..7 of 16."""
    return share_of(tiny, 4, 4)


@pytest.fixture(scope="module")
def served(held):
    """ONE engine for the file, on the share, with a pool of 40 blocks of
    8 rows.  → (engine, the logits-returning step that also says the
    experts each row took)."""
    cfg, params, axes = held
    eng = InferenceEngine(
        Model.from_params(cfg, params, param_axes=axes),
        InferenceConfig(token_budget=BUDGET, max_seqs=4, kv_block_size=BLOCK,
                        num_kv_blocks=40, max_seq_len=256, trace=True,
                        param_dtype=jnp.float32, kv_dtype=jnp.float32))
    return eng, eng._build_step(eng.max_blocks_per_seq, with_routing=True)


def run_steps(served):
    """The engine's ordinary steps through the file's one step until
    nothing is scheduled → {uid: its last row's logits}, the routing of
    every step ``[(schedule, [layers, rows, k])]``."""
    eng, step = served
    rows, took = {}, []
    with jax.default_matmul_precision("highest"):
        while True:
            sched = eng._schedule()
            if not sched:
                return rows, took
            batch = eng._stage(eng.state.build_batch(sched, BUDGET))
            logits, eng.state.kv, routing = step(eng.params, eng._quant,
                                                 eng.state.kv, batch)
            took.append(([(u, len(t)) for u, t in sched],
                         np.asarray(routing)))
            for u, _ in sched:
                rows[u] = np.asarray(logits[eng.state.slot(u)])


def paged(served, tokens, n_prompt, uid=1, flush=True):
    """One sequence: its prompt in the scheduler's chunks, then the rest
    fed a token at a time → [rows], row i the logits after token
    ``n_prompt - 1 + i``."""
    eng, _ = served
    eng.put(uid, list(tokens[:n_prompt]))
    out = [run_steps(served)[0][uid]]
    for t in tokens[n_prompt:]:
        eng.put(uid, [int(t)])
        out.append(run_steps(served)[0][uid])
    if flush:
        eng.flush(uid)
    return np.stack(out)


def test_tiny_preset_is_the_block(tiny):
    cfg, params, _ = tiny
    assert cfg.mixer_stacks == ("mla",) and not cfg.has_ssm
    assert cfg.layer_kinds == ("mla",) * 4 and cfg.layer_plan == (1, 3, 0)
    assert not cfg.plain_stack and cfg.held_groups is None
    assert params["dense_blocks"]["mlp"]["wi"].shape == (1, 64, 160)
    assert params["blocks"]["experts"]["wi"].shape == (3, 16, 64, 48)
    assert params["blocks"]["shared"]["wi"].shape == (3, 64, 96)
    assert "bias" not in params["blocks"]["gate"]
    assert "gate" not in params["blocks"]["shared"]
    md = cfg.mla_dims
    assert (md.q_scale, md.kv_scale) == (1.0, 1.0)
    assert md.scale == pytest.approx(24 ** -0.5 * cfg.rope_yarn.score_scale)


def test_published_preset_is_the_catalog_entry():
    cfg = build_config("deepseek-v2")
    md = cfg.mla_dims
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.d_ff) == (
        60, 5120, 128, 12288)
    assert (md.q_rank, md.kv_rank, md.nope_dim, md.rope_dim,
            md.value_dim, md.row) == (1536, 512, 128, 64, 128, 576)
    assert (cfg.num_experts, cfg.moe_top_k, cfg.moe_d_ff, cfg.moe_shared_ff,
            cfg.moe_groups, cfg.moe_groups_kept, cfg.moe_group_score,
            cfg.moe_route_scale, cfg.moe_norm_topk, cfg.moe_score) == (
        160, 6, 1536, 3072, 8, 3, "max", 16.0, False, "softmax")
    y = cfg.rope_yarn
    assert y == L.Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert y.ramp_ends(64, 10000.0) == (10, 23)
    assert y.table_scale == 1.0
    assert y.score_scale == pytest.approx(1.26081 ** 2, rel=1e-5)
    assert md.scale == pytest.approx(0.072169 * 1.58964, rel=1e-4)
    assert md.scale == 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    from deepspeed_tpu.ops.mla import tile_heights
    assert tile_heights(128) == (1, 8)


@pytest.mark.parametrize("preset", ["deepseek-v2", "deepseek-v2-tiny"])
def test_the_yarn_table_is_the_closed_form(preset):
    cfg = build_config(preset, max_seq_len=300)
    y, dim, base = cfg.rope_yarn, cfg.rotary_dim, cfg.rope_theta
    cos, sin = L.rope_freqs(dim, 300, base, y)

    def d(r):
        return dim * math.log(y.original / (2 * math.pi * r)) \
            / (2 * math.log(base))

    low, high = max(math.floor(d(32)), 0), min(math.ceil(d(1)), dim - 1)
    want = []
    for i in range(dim // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        f = base ** (-2 * i / dim)
        want.append(f / y.factor * ramp + f * (1 - ramp))
    ang = np.arange(300)[:, None] * np.asarray(want)[None]
    np.testing.assert_allclose(cos, np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(sin, np.sin(ang), atol=2e-5)
    # the fast pairs keep their frequency, the slow ones are divided
    plain = L.rope_freqs(dim, 300, base)[0]
    np.testing.assert_allclose(cos[:, :low + 1], plain[:, :low + 1],
                               atol=2e-5)
    assert not np.allclose(cos[:, high:], plain[:, high:], atol=1e-3)
    # no model without the key reads another table than before
    assert build_config("longcat-tiny").rope_yarn is None


def test_yarn_is_refused_where_it_is_not_written():
    with pytest.raises(AssertionError, match="latent layers"):
        build_config("llama-tiny", rope_yarn=(4.0, 64))


def test_max_and_sum_of_two_open_different_groups():
    """A crafted score row: group 0 holds the single best score, group 1
    the best pair.  V2's rule opens 0, V3's opens 1."""
    p = np.full((1, 8), 0.02, np.float32)
    p[0, 0], p[0, 1] = 0.40, 0.01            # group 0: max .40, pair .41
    p[0, 2], p[0, 3] = 0.25, 0.24            # group 1: max .25, pair .49
    p[0, 4:] = (0.03, 0.03, 0.02, 0.02)      # groups 2, 3
    logits = jnp.log(jnp.asarray(p / p.sum()))
    kw = dict(top_k=2, norm_topk=False, route_scale=16.0, groups=(4, 1))
    w_max, e_max, open_max = M.route(logits, group_score="max", **kw)
    w_sum, e_sum, open_sum = M.route(logits, **kw)
    assert sorted(np.asarray(e_max)[0]) == [0, 1]
    assert sorted(np.asarray(e_sum)[0]) == [2, 3]
    assert np.asarray(open_max)[0].tolist() == [True, False, False, False]
    assert np.asarray(open_sum)[0].tolist() == [False, True, False, False]
    # 16 x the softmax scores, not renormalised
    np.testing.assert_allclose(sorted(np.asarray(w_max)[0]),
                               sorted(16 * (p / p.sum())[0, :2]), rtol=1e-5)


def test_a_share_that_splits_a_group_is_refused():
    with pytest.raises(ValueError, match="splits a group"):
        build_config("deepseek-v2-tiny", experts_held=(3, 4))
    with pytest.raises(ValueError, match="splits a group"):
        build_config("deepseek-v2", experts_held=(0, 30))
    assert build_config("deepseek-v2", experts_held=(40, 40)
                        ).held_groups == (2, 2)
    # V3's rule does not make a group a device: ling's 128 of 512 stand
    assert build_config("ling-3.0-flash",
                        experts_held=(0, 100)).held_groups is None


def run_apply(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, i: apply(cfg, p, i)[0])(
            params, jnp.asarray(ids)[None]))


@pytest.fixture(scope="module")
def seq(tiny):
    """A sequence past the toy original context (64): 150 prompt tokens
    over five steps of 37 rows and 19 chunks of 8, then 4 fed tokens."""
    return np.random.default_rng(2).integers(
        0, tiny[0].vocab_size, 154).tolist()


@pytest.mark.parametrize("n", [90, 5])
def test_apply_agrees_with_the_reference(tiny, ref, seq, n):
    cfg, params, _ = tiny
    want = np.asarray(ref.logits(params, np.asarray(seq[:n]),
                                 ref_config(cfg)))
    assert rel(run_apply(cfg, params, seq[:n]), want) < TOL


@pytest.fixture(scope="module")
def system_rows(served, seq):
    return paged(served, seq, 150)


def test_chunked_prefill_and_decode_agree_with_the_reference(
        held, ref, seq, system_rows):
    cfg, params, _ = held
    want = np.asarray(ref.logits(params, np.asarray(seq), ref_config(cfg),
                                 last=5))
    assert system_rows.shape == want.shape
    assert rel(system_rows[:1], want[:1]) < TOL      # the prompt's end
    assert rel(system_rows[1:], want[1:]) < TOL      # through the cache


@pytest.mark.parametrize("wrong", ["no_yarn_scale", "no_yarn_freqs",
                                   "group_by_top2_sum",
                                   "renormalised_weights",
                                   "unscaled_weights", "int8"])
def test_every_wrong_forward_differs_from_the_system(held, ref, seq,
                                                     system_rows, wrong):
    cfg, params, _ = held
    got = np.asarray(ref.logits(params, np.asarray(seq), ref_config(cfg),
                                wrong=wrong, last=5))
    assert rel(system_rows, got) > 50 * TOL


def test_following_the_engines_routing(held, served, ref, seq):
    """The reference that follows the system's choice reads the system's
    logits and no shortfall; following under the other group rule, the
    shortfall says that choice was not its own."""
    cfg, params, _ = held
    eng, _ = served
    eng.state.reset_prefix_cache()      # every row is to be routed here
    eng.put(3, list(seq[:60]))
    rows, took = run_steps(served)
    eng.flush(3)
    routing = np.concatenate([r[:, :n] for ((_, n),), r in took], axis=1)
    assert routing.shape == (3, 60, cfg.moe_top_k)
    want, short = ref.following(params, np.asarray(seq[:60]),
                                ref_config(cfg), routing, last=1)
    assert rel(rows[3], np.asarray(want)[0]) < TOL and short < 1e-5
    _, other = ref.following(params, np.asarray(seq[:60]), ref_config(cfg),
                             routing, wrong="group_by_top2_sum", last=1)
    assert other > 0.05


@pytest.mark.parametrize("third, reads", [(0.2495, 0.002), (0.10, 0.6)])
def test_a_group_taken_from_below_the_open_ones_reads_by_its_group(
        ref, third, reads):
    """A crafted score row, four groups of two, two open, two experts
    taken: the reference opens groups 0 and 1 (best scores .40 and .25)
    and the choice it is given took the best of groups 0 and 2.  With
    group 2's best within rounding of group 1's the swap reads nothing;
    with it clearly below (.10) the shortfall is the GROUP's alone (the
    taken experts are the two best of the set that holds their groups,
    so the expert's term is zero): (.25 - .10) / .25, four times the
    benchmark's limit of 0.15."""
    p = np.asarray([[0.40, 0.01, 0.25, 0.02, third, 0.02, 0.03, 0.01]])
    c = dict(num_experts_per_tok=2, n_group=4, topk_group=2,
             norm_topk_prob=False, routed_scaling_factor=16.0)
    gate = {"kernel": jnp.eye(8)}
    logits = jnp.log(jnp.asarray(p / p.sum(), jnp.float32))
    with jax.default_matmul_precision("highest"):
        w, own = ref._route(logits, gate, c, None, None)
        _, router = ref._route(logits, gate, c, None, jnp.asarray([[0, 4]]))
    assert np.flatnonzero(np.asarray(w)[0]).tolist() == [0, 2]
    assert float(own[1, 0]) == 0.0          # its own choice: no shortfall
    short, first_form, gap = (float(router[i, 0]) for i in (1, 2, 3))
    assert short == pytest.approx(reads, rel=0.02)
    assert short == pytest.approx((0.25 - third) / 0.25, rel=1e-3)
    # the groups' distance, as the reference's line reports it; and the
    # statistic's first form, which counts the same swap twice
    assert gap == pytest.approx((0.25 - third) / 0.25, rel=1e-3)
    assert first_form == pytest.approx(2 * short, rel=1e-3)
    assert (short > 0.15) == (third < 0.2)
    # the given choice spans group 2, which the reference closed
    assert float(router[4, 0]) == pytest.approx(gap) and own[4, 0] == 0


def test_the_four_shares_add_up_to_the_uncut_layer(tiny, ref):
    """Four chips share a layer, two whole groups each; what every chip
    computes alike (the shared MLP) counted once, the shares' routed
    parts add up to the uncut layer."""
    from deepspeed_tpu.models import layers as Lx
    from deepspeed_tpu.models.transformer import _shared_expert, moe_share
    cfg, params, _ = tiny
    gate = jax.tree.map(lambda a: a[0], params["blocks"]["gate"])
    experts = jax.tree.map(lambda a: a[0], params["blocks"]["experts"])
    shared = jax.tree.map(lambda a: a[0], params["blocks"]["shared"])
    h = jax.random.normal(jax.random.PRNGKey(8), (33, cfg.d_model))
    h = h / jnp.sqrt((h * h).mean(-1, keepdims=True))    # a norm's output
    act = Lx.ACTIVATIONS[cfg.activation]
    kw = dict(top_k=cfg.moe_top_k, activation=act, gated=True,
              norm_topk=cfg.moe_norm_topk, score=cfg.moe_score,
              route_scale=cfg.moe_route_scale)

    def routed(c, ex):
        return M.moe_serve(gate, ex, h, held_groups=c.held_groups, **kw,
                           **moe_share(c))

    with jax.default_matmul_precision("highest"):
        whole, stats = routed(cfg, experts)
        parts, opened = [], []
        for first in (0, 4, 8, 12):
            c = build_config("deepseek-v2-tiny", experts_held=(first, 4))
            assert c.held_groups == (first // 2, 2)
            y, st = routed(c, jax.tree.map(lambda a: a[first:first + 4],
                                           experts))
            parts.append(y)
            opened.append(int(st[3]))
        once = _shared_expert(shared, h, act, True)
        assert rel(np.asarray(sum(parts)), np.asarray(whole)) < TOL
        # the uncut reference's layer: its routed part and the shared MLP
        rc = ref_config(cfg)
        run = ref._programs(ref._key(rc), None)
        # the layer's norm (a scale of ones) leaves such rows as they
        # are; the layer adds to the stream it is given
        b = params["blocks"]
        want, _ = run["moe"](jnp.pad(h, ((0, ref.TOKENS - 33), (0, 0))),
                             jnp.ones(cfg.d_model), b["gate"], b["shared"],
                             b["experts"], jnp.int32(0))
        want = want[:33] - h
        assert rel(np.asarray(sum(parts) + once), np.asarray(want)) < TOL
    assert stats.shape == (3,)
    # a row opens 3 of 8 groups: over the four shares of two, 3 openings
    # a row at most (two of its groups on one chip: 2), never none
    assert 33 * 2 <= sum(opened) <= 33 * 3 and max(opened) <= 33


def test_a_prefix_hit_under_eviction_reads_as_a_cold_prefill(held, served,
                                                             ref):
    """A document of 12 blocks asked twice with others between that fill
    the pool of 40 and evict; the second question aliases what is still
    indexed, reads as the reference's full forward does, and the stage
    span says what was aliased and what was evicted."""
    cfg, params, _ = held
    eng, _ = served
    rng = np.random.default_rng(21)
    doc = rng.integers(0, cfg.vocab_size, 12 * BLOCK).tolist()
    ask = [doc + rng.integers(0, cfg.vocab_size, n).tolist()
           for n in (5, 11)]
    eng.state.reset_prefix_cache()
    paged(served, ask[0], len(ask[0]), uid=10)
    ev0, hit0 = eng.state.prefix_evictions, eng.timings["cached_tokens"]
    # two colder documents of 15 blocks each: 12 + 30 > 40 blocks, so
    # the allocator reclaims indexed blocks, oldest released first
    for uid in (11, 12):
        paged(served, rng.integers(0, cfg.vocab_size, 15 * BLOCK).tolist(),
              15 * BLOCK, uid=uid)
    assert eng.state.prefix_evictions > ev0
    got = paged(served, ask[1], len(ask[1]), uid=13, flush=False)
    cached = eng.state.seqs[13].cached_tokens
    eng.flush(13)
    assert eng.timings["cached_tokens"] - hit0 == cached
    # the tail of the chain went first: what is left is a whole prefix
    assert 0 < cached < len(doc) and cached % BLOCK == 0
    want = np.asarray(ref.logits(params, np.asarray(ask[1]),
                                 ref_config(cfg), last=1))
    assert rel(got[-1], want[0]) < TOL
    # the served loop says so on its stage spans: the document once
    # more, and what its blocks push out
    mark = eng.tracer.events()[-1]["ts_ns"]
    ev1 = eng._evictions_seen       # as of the last step it staged
    from deepspeed_tpu.inference import SamplingParams
    eng.generate({14: ask[0]}, SamplingParams(temperature=0.0,
                                              max_new_tokens=2))
    stages = [e["args"] for e in eng.tracer.events()
              if e["name"] == "ds.serve.stage" and e["ts_ns"] > mark]
    assert sum(a["cached_tokens"] for a in stages) \
        == eng.timings["cached_tokens"] - hit0 - cached > 0
    assert sum(a["prefix_evictions"] for a in stages) \
        == eng.state.prefix_evictions - ev1 > 0
    eng.state.reset_prefix_cache()


def test_served_loop_spans_say_what_was_aliased_and_opened(held, served):
    """The served loop: ``cached_tokens`` and ``prefix_evictions`` on the
    stage span, ``moe_groups_open_here`` on the readback span, beside the
    counts that stood."""
    from deepspeed_tpu.inference import SamplingParams
    from deepspeed_tpu.inference.model import moe_stat_rows
    cfg, _, _ = held
    eng, _ = served
    assert moe_stat_rows(cfg) == 4
    assert moe_stat_rows(build_config("ling-tiny", experts_held=(0, 8))) == 3
    greedy = SamplingParams(temperature=0.0, max_new_tokens=3)
    rng = np.random.default_rng(9)
    doc = rng.integers(0, cfg.vocab_size, 4 * BLOCK).tolist()
    eng.state.reset_prefix_cache()
    mark = eng.tracer.events()[-1]["ts_ns"]
    for uid, n in ((20, 3), (21, 6)):
        eng.generate({uid: doc + rng.integers(0, cfg.vocab_size,
                                              n).tolist()}, greedy)
    ev = [e for e in eng.tracer.events() if e["ts_ns"] > mark]
    stage = [e["args"] for e in ev if e["name"] == "ds.serve.stage"]
    back = [e["args"] for e in ev if e["name"] == "ds.serve.readback"]
    assert sum(a["cached_tokens"] for a in stage) == 4 * BLOCK
    assert all(a["prefix_evictions"] == 0 for a in stage)
    assert {"latent_tokens", "latent_pairs"} <= set(stage[0])
    layers = cfg.expert_layers
    for a, b in zip(stage, back):
        assert 0 <= b["moe_groups_open_here"] <= a["n_tokens"] * layers
        assert b["moe_assignments"] <= b["moe_groups_open_here"] \
            * cfg.moe_top_k
        assert b["moe_assignments_made"] == a["n_tokens"] * layers \
            * cfg.moe_top_k
    assert sum(b["moe_groups_open_here"] for b in back) > 0
    eng.state.reset_prefix_cache()


@pytest.fixture(scope="module")
def d5():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "deepseek-v2-d5.json")) as f:
        return json.load(f)


def test_configuration_file_says_what_the_shapes_say(d5):
    from benchmarks.lib.drivers.serve_latent_groups import preset_config
    cfg = preset_config(d5)
    assert cfg.layer_kinds == ("mla",) * 5 and cfg.num_dense_layers == 1
    assert cfg.experts_held == (0, 40) and cfg.held_groups == (0, 2)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k)[0],
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    norms = 5 * (2 * 5120 + 1536 + 512) + 5120
    assert n - norms == 5163909120
    assert "5,163,909,120 parameters" in d5["deployment"]
    b = shapes["blocks"]
    assert b["experts"]["wi"].shape == (4, 40, 5120, 1536)
    assert b["gate"]["kernel"].shape == (4, 5120, 160)
    assert b["shared"]["wi"].shape == (4, 5120, 3072)
    assert b["mla"]["wq_b"].shape == (4, 1536, 128 * 192)
    assert shapes["dense_blocks"]["mlp"]["wi"].shape == (1, 5120, 12288)
    assert shapes["embed"]["table"].shape == (25600, 5120)
    # the first fitting key names the kernel by its source file
    assert next(iter(d5["trace_groups"])) \
        == "pallas_call@deepspeed_tpu/ops/mla.py"
    assert d5["trace_groups"]["pallas_call@deepspeed_tpu/ops/mla.py"] \
        == "latent_attn"
    assert set(d5["reference"]["compares"]) >= {
        "shared_prefix_prefill", "shared_prefix_decode"}
    # the comparison's long prompt and its aliased prefix are the
    # traffic's own: a document and a question
    sample = d5["reference"]["sample"]
    assert sample["shared_prefix_tokens"] == 16384 < sample["long_prompt"]


def test_arith_counts_a_decode_step_and_a_chunk_step_by_hand(d5):
    from benchmarks.lib import arith_dsv2 as A
    m = A.model(d5)
    d = 5120
    mla = d * 1536 + 1536 * 128 * 192 + d * 576 + 512 * 128 * 256 \
        + 128 * 128 * d
    assert A.mla_params(m) == mla == 149225472
    fixed = 5 * mla + 3 * d * 12288 + 4 * (3 * d * 3072 + d * 160)
    assert A.fixed_params(m) == fixed
    s = {"n_tokens": 24, "n_seqs": 24, "latent_tokens": 24 * 16600,
         "latent_tokens_one": 24 * 16600,
         "latent_pairs": 24 * 16600, "n_tiles_one": 24,
         "moe_assignments": 140, "moe_assignments_made": 576,
         "moe_experts_touched": 90, "moe_groups_open_here": 70,
         "cached_tokens": 0, "prefix_evictions": 0}
    row = 576 * 2
    assert A.one_token_bytes(m, s) == 5 * row * 24 * 16600
    # 242 operations a byte: on the chip's ridge (197e12 / 819e9 = 240)
    assert 240 < A.one_token_flops(m, s) / A.one_token_bytes(m, s) < 243
    assert A.one_token_flops(m, s) == 2.0 * 5 * 128 * (576 + 512) \
        * 24 * 16600
    assert A.run_flops(m, s) == 0 == A.run_bytes(m, s)
    # a chunk of 488 rows at 8,000 rows seen beside the 24 decode rows:
    # the run call counted in the least either form needs (192 + 128)
    pairs_run = 488 * 8000 + 488 * 489 // 2
    c = dict(s, n_tokens=512, n_seqs=25,
             latent_tokens=24 * 16600 + 8488,
             latent_pairs=24 * 16600 + pairs_run)
    assert A.run_flops(m, c) == 2.0 * 5 * 128 * 320 * pairs_run
    assert A.run_bytes(m, c) == 5 * row * 8488
    assert A.step_flops(m, c) > A.step_flops(m, s)
    experts = 2 * (90 * 3 * d * 1536 + 140 * 3 * (d + 1536))
    assert A.step_bytes(m, s) == 2 * (fixed + d * 25600) \
        + A.one_token_bytes(m, s) + 5 * row * 24 + experts + 24 * d * 2


def test_benchmark_json_lists_the_cell_and_what_it_joins():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "serve-mla-shared-docs")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2-d5", "shared-docs-closed-24", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek-v2-d5")
    assert set(entry["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                     "vocab_size", "max_position_embeddings"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "serve-mla-shared-docs" in e2e["out_tokens_per_s"]["workloads"]
    assert len(bench["per_layer"]) == 128
    assert [w["name"] for w in bench["workloads"]].index(
        "serve-mla-shared-docs") == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"][:11]) == 1
    mine = {m["name"] for m in bench["per_layer"]
            if "serve-mla-shared-docs" in m.get("workloads", ())}
    assert mine == {
        "batch_tokens_per_step", "serve_hbm_peak_gb",
        "moe_expert_gemm_share", "moe_route_share",
        "moe_expert_load_max_over_mean", "latent_attn_share",
        "moe.serve_step_p50_ms", "moe.serve_host_ms_per_step",
        "moe.serve_window_compiles", "moe.serve_step_retries",
        "moe.itl_p95_ms", "moe.itl_p99_ms", "moe.serve_idle_share",
        "moe.sampler_share"}
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "shared-docs-closed-24.json")) as f:
        mix = json.load(f)
    assert mix["driver"] == "serve_latent_groups" and mix["clients"] == 24
    assert mix["shared_prefix"] == {"share": 0.75, "prefixes": 8,
                                    "tokens": 16384}
    # ISSUE 56's pool, and no key the generator does not read
    assert mix["engine"]["num_kv_blocks"] == 9216 and "pool_fill" not in mix
    assert mix["engine"]["max_seq_len"] == 17408 \
        == mix["prompt_tokens"]["hi"] + mix["answer_tokens"]["hi"]
