"""The layer pattern (window and full attention layers in one model,
leading dense layers, a sigmoid router beside an ungated shared expert,
gated attention, an RMSNorm per head on q and k, four norms a layer) at
``trinity-tiny``, against the benchmark's own plain reference
(``benchmarks/reference/trinity-mini-d5.py``, imported by path): through
``apply``, through the engine's paged path past four windows on both
attention formulations, the window kernel against the masked XLA
formulation, every wrong forward the reference knows, the counters the
pattern brings, and the older presets through a period of two layers."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.inference.model as M
from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, apply, init_params
from deepspeed_tpu.ops.paged_attention import (LONG, SHORT, query_tiles,
                                               window_blocks)
from tests.test_paged_attention import (BS_T, D_T, HKV_T, TILE_BATCHES,
                                        _built_batch, _random_pool)

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
    params, axes = init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params, axes


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
        got = np.asarray(apply(cfg, params, jnp.asarray(ids)[None]))[0]
    assert rel(got, want) < TOL


def test_apply_with_a_tail_agrees_with_the_reference(ref):
    """Seven layers behind the dense one: a period and three layers of
    the next, which run outside the scan."""
    cfg = build_config("trinity-tiny", num_layers=8)
    assert cfg.layer_plan == (1, 1, 3)
    params, _ = init_params(cfg, jax.random.PRNGKey(5))
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 50)
    want = np.asarray(ref.logits(params, ids, ref_config(cfg)))
    with jax.default_matmul_precision("highest"):
        got, aux = apply(cfg, params, jnp.asarray(ids)[None], with_aux=True)
    assert rel(np.asarray(got)[0], want) < TOL
    assert np.isfinite(float(aux["moe_aux_loss"]))


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
# the window kernel against the masked XLA formulation
# --------------------------------------------------------------------------

# bs = 8.  24 = three blocks: a decode token at context 31 starts its
# window at position 8, the first of block 1; at 30, inside block 0; a
# window of 20 starts inside a block wherever the context ends
WINDOW_BATCHES = dict(TILE_BATCHES, **{
    # tiles that start before (context under the window), inside and at
    # the edge of the first in-window block, and far behind it
    "decode-around-the-edge": ([(1, 5, 1), (2, 29, 1), (3, 30, 1),
                                (4, 31, 1), (5, 90, 1)], 16),
    # a chunk of 300 from position 3: its first tile's window is not
    # full, the second and third start inside it
    "chunk-through-the-window": ([(1, 3, 300)], 320),
})

# 64 = eight blocks, one group of the short call's grid step: a decode
# token at 100 reads blocks 4..12 (its first block is not a multiple of
# the group, W/bs + 1 blocks, two grid steps); 68 = a window that starts
# inside a block


@pytest.mark.parametrize("window", [24, 20, 8, 64, 68])
@pytest.mark.parametrize("name", sorted(WINDOW_BATCHES))
def test_window_kernel_matches_masked_xla(name, window):
    runs, T = WINDOW_BATCHES[name]
    batch, _ = _built_batch(runs, T)
    kv, H, nb = _random_pool(3), HKV_T * 4, 48
    q = jnp.asarray(np.random.RandomState(11).randn(T, H, D_T), jnp.float32)
    scale = 1.0 / np.sqrt(D_T)
    want = M._paged_attention(kv, q, batch, BS_T, nb, scale, window=window)
    got = M._paged_attention_pallas(kv, q, batch, BS_T, nb, scale,
                                    window=window)
    full = M._paged_attention(kv, q, batch, BS_T, nb, scale)
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
    """At the published window and block size a window tile's grid row
    is at most ceil((2048 + tile) / 64) + 1 blocks whatever the context,
    where a full layer's is the context's."""
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
                        trash=nb, window=W)
    for tl, height in ((tiles.short, SHORT), (tiles.long, LONG)):
        bound = math.ceil((W + height) / bs) + 1
        assert int(tl.wblocks) <= bound
        n = int(tl.count)
        first, last = window_blocks(tl.pos[:n], tl.length[:n], W, bs)
        visits = np.asarray(last - first + 1)
        assert visits.max() == int(tl.wblocks) <= int(tl.blocks)
        # the first block holds the first query's window start, the last
        # one the last query
        p, ln = np.asarray(tl.pos[:n]), np.asarray(tl.length[:n])
        np.testing.assert_array_equal(np.asarray(first),
                                      np.maximum(p - (W - 1), 0) // bs)
        np.testing.assert_array_equal(np.asarray(last), (p + ln - 1) // bs)
    assert int(tiles.long.blocks) == (context - 1) // bs + 1 \
        or context < n_chunk
    if context >= 4100:
        assert int(tiles.long.wblocks) < int(tiles.long.blocks) // 1.8
    # without a window the field repeats ``blocks``
    plain = query_tiles(jnp.asarray(slot), jnp.asarray(pos),
                        jnp.asarray(valid), jnp.asarray(tables), bs, nb,
                        trash=nb)
    assert int(plain.long.wblocks) == int(plain.long.blocks)


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


def test_what_serves_one_block_type_only_says_so(tiny):
    cfg, params, axes = tiny
    assert not cfg.plain_stack and build_config("llama-tiny").plain_stack
    with pytest.raises(NotImplementedError, match="one block type"):
        M.decode_burst_forward(cfg, params, jnp.zeros((9, 1, 8, 2, 2, 32)),
                               None, None, 1, None, None)


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
