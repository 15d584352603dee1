"""``trinity-tiny`` through the ENGINE (``tests/test_trinity.py`` holds the
model's forward, the window kernel and what refuses the pattern):
the paged path past four windows on both attention formulations against
the benchmark's plain reference, every wrong forward the reference
knows, the comparison that follows the engine's routing, the counters
the pattern brings, and the older presets through a period of two
layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, apply, init_params
from tests.test_trinity import (TOL, ref, ref_config, rel,  # noqa: F401
                                tiny)


def paged_logits(cfg, params, axes, seqs, n_prompt, impl, **over):
    """Each sequence's prompt through the engine's ordinary chunks, then
    the rest fed a token at a time → {uid: [rows]}, row i the logits
    after token ``n_prompt - 1 + i``."""
    kw = dict(token_budget=32, max_seqs=4, kv_block_size=8,
              num_kv_blocks=64, max_seq_len=128, attn_impl=impl,
              param_dtype=jnp.float32, kv_dtype=jnp.float32)
    kw.update(over)
    eng = InferenceEngine(Model.from_params(cfg, params, param_axes=axes),
                          InferenceConfig(**kw))
    step = eng._build_step(eng.max_blocks_per_seq)
    rows = {u: [] for u in seqs}
    fed = dict(n_prompt)
    for u, s in seqs.items():
        eng.put(u, list(s[:n_prompt[u]]))
    steps = 0
    while True:
        sched = eng._schedule()
        if not sched:
            return rows, steps
        steps += 1
        batch = eng._stage(eng.state.build_batch(sched,
                                                 eng.icfg.token_budget))
        logits, eng.state.kv = step(eng.params, eng._quant, eng.state.kv,
                                    batch)
        for u, _ in sched:
            if eng.state.seqs[u].seen_tokens >= n_prompt[u]:
                rows[u].append(np.asarray(logits[eng.state.slot(u)]))
                if fed[u] < len(seqs[u]):
                    eng.put(u, [int(seqs[u][fed[u]])])
                    fed[u] += 1


@pytest.fixture(scope="module")
def long_seqs(tiny):
    cfg = tiny[0]
    rng = np.random.default_rng(0)
    # 82 and 62 prompt tokens, 8 fed: more than five windows of 16
    seqs = {1: rng.integers(0, cfg.vocab_size, 90).tolist(),
            2: rng.integers(0, cfg.vocab_size, 70).tolist()}
    return seqs, {1: 82, 2: 62}


@pytest.fixture(scope="module")
def system_rows(tiny, long_seqs):
    cfg, params, axes = tiny
    return {impl: paged_logits(cfg, params, axes, *long_seqs, impl)
            for impl in ("xla", "pallas")}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_path_agrees_past_four_windows(tiny, ref, long_seqs,
                                             system_rows, impl):
    cfg, params, _ = tiny
    seqs, n_prompt = long_seqs
    rows, steps = system_rows[impl]
    # 144 prompt tokens at 32 a step, then the fed tokens
    assert steps >= 144 // 32 + 8
    assert min(map(len, seqs.values())) >= 4 * cfg.attn_window
    for u, s in seqs.items():
        want = np.asarray(ref.logits(params, np.asarray(s),
                                     ref_config(cfg)))[n_prompt[u] - 1:]
        got = np.stack(rows[u])
        assert got.shape == want.shape == (9, cfg.vocab_size)
        assert rel(got, want) < TOL


def test_paged_path_with_a_tail_agrees_with_the_reference(ref):
    """A period and three layers of the next behind the dense layer: the
    serving forward's layers outside its scan, their rows of the pool
    and of the stacked experts, and their routing statistics."""
    cfg = build_config("trinity-tiny", num_layers=8)
    params, axes = init_params(cfg, jax.random.PRNGKey(5))
    s = np.random.default_rng(2).integers(0, cfg.vocab_size, 60).tolist()
    rows, _ = paged_logits(cfg, params, axes, {1: s}, {1: 52}, "pallas")
    want = np.asarray(ref.logits(params, np.asarray(s), ref_config(cfg),
                                 last=9))
    assert rel(np.stack(rows[1]), want) < TOL
    # seven expert layers' assignments: top-2 of every real token
    eng = InferenceEngine(
        Model.from_params(cfg, params, param_axes=axes),
        InferenceConfig(token_budget=32, max_seqs=2, kv_block_size=8,
                        num_kv_blocks=32, max_seq_len=128, attn_impl="xla",
                        param_dtype=jnp.float32, kv_dtype=jnp.float32))
    eng.put(1, s[:20])
    eng.step(sampling=SamplingParams(temperature=0.0, max_new_tokens=4))
    assert eng.metrics_snapshot()["serving_moe_assignments_total"] \
        == 7 * 2 * 20


def test_every_wrong_forward_fails_the_tolerance(tiny, ref, long_seqs,
                                                 system_rows):
    """What the benchmark's tolerance is fitted against: the system
    agrees with the reference and with none of its wrong forms."""
    cfg, params, _ = tiny
    seqs, n_prompt = long_seqs
    got = np.stack(system_rows["xla"][0][1])
    for wrong in ref.WRONG:
        want = np.asarray(ref.logits(params, np.asarray(seqs[1]),
                                     ref_config(cfg), wrong=wrong,
                                     last=9))
        # the selection bias is small beside the scores (2% of a weight)
        assert rel(got, want) > (10 if wrong == "bias_in_weights"
                                 else 100) * TOL, wrong
    assert set(ref.WRONG) == {
        "no_window", "rope_in_full", "no_gate", "no_shared",
        "softmax_scores", "bias_in_weights", "int8"}


# --------------------------------------------------------------------------
# the comparison that follows the engine's routing
# (benchmarks/lib/drivers/serve_routed.py)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def routed():
    from benchmarks.lib.drivers import serve_routed
    return serve_routed


@pytest.fixture(scope="module")
def followed(tiny, long_seqs, routed):
    """``long_seqs`` through the engine in float32 and in bfloat16, with
    the experts each token took → {type: (params, system_side's)}."""
    cfg, params, axes = tiny
    out = {}
    for dt in (jnp.float32, jnp.bfloat16):
        p = jax.tree.map(lambda a: a.astype(dt), params)
        eng = InferenceEngine(
            Model.from_params(cfg, p, param_axes=axes),
            InferenceConfig(token_budget=32, max_seqs=4, kv_block_size=8,
                            num_kv_blocks=64, max_seq_len=128,
                            attn_impl="xla", param_dtype=dt, kv_dtype=dt))
        out[dt] = p, routed.system_side(eng, *long_seqs)
    return out


def worst(read):
    return max(max(r["prefill"], r["decode"]) for r in read.values())


def test_engine_says_which_experts_each_token_took(tiny, ref, long_seqs,
                                                   followed, routed):
    """In float32 the engine's choice is the reference's own: following
    it changes nothing, and no taken expert falls short of the eighth."""
    cfg = tiny[0]
    seqs, n_prompt = long_seqs
    params, system = followed[jnp.float32]
    for u, (got, took, steps) in system.items():
        assert took.shape == (8, len(seqs[u]), cfg.moe_top_k)
        assert steps == -(-n_prompt[u] // 32) + 8
        assert took.min() >= 0 and took.max() < cfg.num_experts
        own = np.asarray(ref.logits(params, np.asarray(seqs[u]),
                                    ref_config(cfg), last=9))
        given, short = ref.following(params, np.asarray(seqs[u]),
                                     ref_config(cfg), took, last=9)
        np.testing.assert_array_equal(np.asarray(given), own)
        assert short == 0.0
        assert rel(got, own) < TOL


def test_following_the_routing_reads_rounding_and_not_a_swap(
        tiny, ref, long_seqs, followed, routed):
    """In bfloat16 a token near a tie takes another expert than the
    float32 reference and a row reads a third of a layer; with the
    choice followed the same logits read bfloat16's rounding, and the
    wrong forwards stand out."""
    cfg = tiny[0]
    seqs, _ = long_seqs
    params, system = followed[jnp.bfloat16]
    c = ref_config(cfg)
    unfollowed = max(
        rel(system[u][0], np.asarray(ref.logits(params, np.asarray(s), c,
                                                last=9)))
        for u, s in seqs.items())
    true = routed.follow(ref, params, c, seqs, system)
    assert worst(true) < 0.03 < 0.2 < unfollowed
    # a near-tie taken the other way: a rounding of the scores
    assert max(r["short"] for r in true.values()) < 5e-3
    for wrong in ("no_window", "rope_in_full", "no_gate", "no_shared",
                  "int8"):
        assert worst(routed.follow(ref, params, c, seqs, system,
                                   wrong=wrong)) > 1.5 * 0.03, wrong


def test_a_choice_made_by_another_rule_falls_short(tiny, ref, long_seqs,
                                                   followed):
    """What a given choice could hide: experts taken by another rule
    than the largest biased scores lie far under the reference's own
    eighth."""
    cfg = tiny[0]
    seqs, _ = long_seqs
    params, system = followed[jnp.float32]
    took = system[1][1]
    # the expert after each taken one
    _, short = ref.following(params, np.asarray(seqs[1]), ref_config(cfg),
                             (took + 1) % cfg.num_experts, last=9)
    assert short > 0.02


def test_driver_checks_the_keys_the_harness_does_not_know(routed):
    cfg = build_config("trinity-tiny")
    told = {"head_dim": 32, "num_experts": 8, "num_experts_per_tok": 2,
            "moe_intermediate_size": 32, "num_shared_experts": 1,
            "num_dense_layers": 1, "sliding_window": 16,
            "score_func": "sigmoid", "route_norm": True,
            "route_scale": 2.826,
            "layer_types": ["sliding_attention"] * 4 + ["full_attention"]
            + ["sliding_attention"] * 3 + ["full_attention"]}
    routed.check_config(told, cfg)
    for key, other in (("head_dim", 16), ("num_experts", 16),
                       ("sliding_window", 32), ("score_func", "softmax"),
                       ("layer_types", ["full_attention"] * 9)):
        with pytest.raises(SystemExit, match=key):
            routed.check_config({**told, key: other}, cfg)


# --------------------------------------------------------------------------
# what the engine counts
# --------------------------------------------------------------------------

def test_stage_span_counters_and_gauge(tiny):
    cfg, params, axes = tiny
    eng = InferenceEngine(
        Model.from_params(cfg, params, param_axes=axes),
        InferenceConfig(token_budget=64, max_seqs=4, kv_block_size=8,
                        num_kv_blocks=64, max_seq_len=128, attn_impl="xla",
                        param_dtype=jnp.float32, kv_dtype=jnp.float32,
                        trace=True))
    sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
    W = cfg.attn_window
    eng.put(1, list(range(1, 41)))          # 40 tokens: 2.5 windows
    eng.put(2, list(range(1, 6)))           # 5: inside the window
    out = eng.step(sampling=sp)
    spans = [e for e in eng.tracer.events() if e["name"] == "ds.serve.stage"]
    assert spans[-1]["args"]["kv_tokens_full"] == 45
    assert spans[-1]["args"]["kv_tokens_window"] == \
        min(40, W + 40 - 1) + min(5, W + 5 - 1)
    for u, t in out.items():
        eng.put(u, [int(t)])
    eng.step(sampling=sp)
    span = [e for e in eng.tracer.events()
            if e["name"] == "ds.serve.stage"][-1]
    assert span["args"]["kv_tokens_full"] == 41 + 6
    assert span["args"]["kv_tokens_window"] == W + 6
    # the experts that took a row, summed over the eight expert layers
    back = [e for e in eng.tracer.events()
            if e["name"] == "ds.serve.readback"][-1]["args"]
    assert back["moe_assignments"] == 8 * cfg.moe_top_k * 2
    assert cfg.moe_top_k * 8 <= back["moe_experts_touched"] \
        <= 8 * min(cfg.num_experts, 2 * cfg.moe_top_k)
    snap = eng.metrics_snapshot()
    assert snap["serving_attn_kv_tokens_total"] == {
        '{kind="full"}': 45 + 47, '{kind="window"}': 45 + W + 6}
    # sequence 1 holds 41 tokens: its next query sees positions 26..41
    assert snap["serving_kv_tokens_behind_window"] == 41 - W + 1
    assert snap["serving_moe_assignments_total"] > 0


def test_window_layers_count_their_own_group_steps(tiny):
    """Under the Pallas kernel the stage span carries the grid steps
    the short call makes in a full layer and in a window layer: a decode
    token at context 41 reads 6 blocks of 8 in a full layer and, behind
    a window of 16, the 3 its window touches."""
    from deepspeed_tpu.ops.paged_attention import SHORT, kv_group
    cfg, params, axes = tiny
    eng = InferenceEngine(
        Model.from_params(cfg, params, param_axes=axes),
        InferenceConfig(token_budget=64, max_seqs=4, kv_block_size=8,
                        num_kv_blocks=64, max_seq_len=128,
                        attn_impl="pallas", param_dtype=jnp.float32,
                        kv_dtype=jnp.float32, trace=True))
    k = kv_group(SHORT, cfg.num_heads // cfg.num_kv_heads, cfg.num_kv_heads,
                 cfg.head_dim, 8, jnp.float32, eng.max_blocks_per_seq)
    sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
    W = cfg.attn_window
    eng.put(1, list(range(1, 41)))
    out = eng.step(sampling=sp)
    eng.put(1, [int(out[1])])
    eng.step(sampling=sp)
    span = [e for e in eng.tracer.events()
            if e["name"] == "ds.serve.stage"][-1]["args"]
    assert span["kv_steps_full"] == -(-6 // k)
    first = (40 - (W - 1)) // 8
    assert span["kv_steps_window"] == -(-(40 // 8 - first + 1) // k)
    snap = eng.metrics_snapshot()
    assert snap["serving_attn_kv_group_steps_total"] == {
        '{kind="full"}': span["kv_steps_full"],
        '{kind="window"}': span["kv_steps_window"]}
    assert snap["serving_attn_kv_group_fill"] == pytest.approx(
        (6 + 40 // 8 - first + 1) / (k * (span["kv_steps_full"]
                                          + span["kv_steps_window"])))


def test_a_model_without_window_layers_counts_the_full_kind_only():
    from tests.test_inference import make_fp32_engine, tiny_model
    eng = make_fp32_engine(tiny_model(), trace=True)
    eng.put(1, [3, 4, 5])
    eng.step(sampling=SamplingParams(temperature=0.0, max_new_tokens=4))
    (span,) = [e for e in eng.tracer.events()
               if e["name"] == "ds.serve.stage"]
    assert span["args"]["kv_tokens_full"] == 3
    assert "kv_tokens_window" not in span["args"]
    snap = eng.metrics_snapshot()
    assert snap["serving_attn_kv_tokens_total"] == {'{kind="full"}': 3}
    assert "serving_kv_tokens_behind_window" not in snap


# --------------------------------------------------------------------------
# the older presets: a period of two layers of one kind is the stack
# --------------------------------------------------------------------------

OLDER = {
    "pythia-1.4b": dict(num_layers=4, d_model=64, num_heads=4, d_ff=128,
                        vocab_size=256, max_seq_len=128),
    "mistral-7b": dict(num_layers=4, d_model=64, num_heads=4,
                       num_kv_heads=2, d_ff=96, vocab_size=256,
                       max_seq_len=128),
    "olmoe-1b-7b": dict(num_layers=4, d_model=64, num_heads=4,
                        num_kv_heads=4, d_ff=32, vocab_size=256,
                        max_seq_len=128, num_experts=8, moe_top_k=4,
                        moe_dispatch="ragged"),
    "gpt2": dict(num_layers=4, d_model=64, num_heads=4, vocab_size=256,
                 max_seq_len=128),
}


def test_routing_of_a_model_of_one_block_type():
    """The experts each row took, from a model whose scan body is one
    layer; a dense model has none to give."""
    cfg = build_config("olmoe-1b-7b", **OLDER["olmoe-1b-7b"])
    params, axes = init_params(cfg, jax.random.PRNGKey(2))
    eng = InferenceEngine(
        Model.from_params(cfg, params, param_axes=axes),
        InferenceConfig(token_budget=16, max_seqs=2, kv_block_size=8,
                        num_kv_blocks=16, max_seq_len=64, attn_impl="xla",
                        param_dtype=jnp.float32, kv_dtype=jnp.float32))
    eng.put(1, list(range(1, 12)))
    batch = eng._stage(eng.state.build_batch(eng._schedule(), 16))
    _, _, took = eng._build_step(with_routing=True)(
        eng.params, eng._quant, eng.state.kv, batch)
    took = np.asarray(took)
    assert took.shape == (4, 16, 4)
    assert (took[:, 11:] == cfg.num_experts).all()      # the bucket's padding
    real = np.sort(took[:, :11], axis=-1)
    assert real.max() < cfg.num_experts and (np.diff(real) > 0).all()
    from tests.test_inference import make_fp32_engine, tiny_model
    with pytest.raises(ValueError, match="routes no token"):
        make_fp32_engine(tiny_model())._build_step(with_routing=True)


@pytest.mark.parametrize("preset", sorted(OLDER))
def test_older_presets_are_bit_equal_through_a_period_of_two(preset):
    """The four configurations the benchmark had are patterns of period
    one with no leading dense layer.  Read through the general path (a
    period of two ``full`` layers: the scan body holds two layers and
    runs half as often) the same weights give the same bits, in ``apply``
    and in the engine's paged path."""
    one = build_config(preset, **OLDER[preset])
    two = build_config(preset, layer_pattern=("full", "full"),
                       **OLDER[preset])
    assert one.layer_plan == (0, 4, 0) and two.layer_plan == (0, 2, 0)
    params, axes = init_params(one, jax.random.PRNGKey(2))
    ids = np.random.default_rng(4).integers(0, one.vocab_size, (2, 24))
    a = np.asarray(apply(one, params, jnp.asarray(ids)))
    b = np.asarray(apply(two, params, jnp.asarray(ids)))
    np.testing.assert_array_equal(a, b)
    seqs = {1: ids[0].tolist(), 2: ids[1].tolist()}
    rows = [paged_logits(cfg, params, axes, seqs, {1: 20, 2: 16}, "xla",
                         token_budget=16)[0] for cfg in (one, two)]
    for u in seqs:
        np.testing.assert_array_equal(np.stack(rows[0][u]),
                                      np.stack(rows[1][u]))
