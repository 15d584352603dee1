"""Delta-rule linear attention (KDA) in most layers, latent attention
(MLA) over a latent paged cache in the rest, and a share of each expert
layer's group-routed experts (``ling-tiny``), against the benchmark's own
plain reference (``benchmarks/reference/ling-3.0-flash-d7.py``, imported
by path, which writes the delta rule token by token and MLA with keys
and values expanded): through ``apply``; through the engine's chunked
prefill and decode; the chunked form at the gate's bound; steps that hold
decode rows and several prefill runs; a slot that changes hands; ``hold``
and resume with a row launched ahead; what the engine refuses for such a
model; every wrong forward the reference knows; the shares of an expert
layer adding up; the router's group limit; the spans and counters; the
configuration's file against the shapes; the older configuration's
programs."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceConfig, InferenceEngine
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, apply, init_params
from deepspeed_tpu.ops import kda
from deepspeed_tpu.parallel import moe as M
from test_falcon_h1 import (GREEDY, ROOT, TOL, _load, next_logits,
                            older_programs, paged_logits, rel)


@pytest.fixture(scope="module")
def ref():
    return _load("benchmarks/reference/ling-3.0-flash-d7.py", "ling_ref")


@pytest.fixture(scope="module")
def tiny():
    cfg = build_config("ling-tiny")
    axes = {}

    def init(key):          # one compiled call, not an operation at a time
        params, axes["axes"] = init_params(cfg, key)
        return params

    return cfg, jax.jit(init)(jax.random.PRNGKey(3)), axes["axes"]


def ref_config(cfg):
    """What the reference reads of a configuration file, for ``cfg``."""
    md = cfg.mla_dims
    return dict(
        num_hidden_layers=cfg.num_layers,
        first_k_dense_replace=cfg.num_dense_layers,
        layer_types=list(cfg.layer_kinds), rms_norm_eps=cfg.eps,
        rope_theta=cfg.rope_theta, qk_nope_head_dim=md.nope_dim,
        qk_rope_head_dim=md.rope_dim, v_head_dim=md.value_dim,
        kv_lora_rank=md.kv_rank, kda_lower_bound=cfg.kda_gate_bound,
        n_group=cfg.moe_groups, topk_group=cfg.moe_groups_kept,
        num_experts_per_tok=cfg.moe_top_k, norm_topk_prob=cfg.moe_norm_topk,
        routed_scaling_factor=cfg.moe_route_scale,
        num_experts=cfg.experts_here,
        experts_held=list(cfg.experts_held or (0, cfg.num_experts)))


def engine(tiny, **over):
    cfg, params, axes = tiny
    kw = dict(token_budget=37, max_seqs=4, kv_block_size=8,
              num_kv_blocks=64, max_seq_len=256,
              param_dtype=jnp.float32, kv_dtype=jnp.float32)
    kw.update(over)
    return InferenceEngine(Model.from_params(cfg, params, param_axes=axes),
                           InferenceConfig(**kw))


def test_tiny_preset_is_the_block(tiny):
    cfg, params, _ = tiny
    assert cfg.has_ssm and cfg.recurrent_kind == "kda"
    assert not cfg.plain_stack and cfg.mixer_stacks == ("kda", "mla")
    assert cfg.layer_plan == (1, 2, 0)
    assert cfg.layer_kinds == ("kda", "kda", "kda", "mla", "kda", "kda",
                               "mla")
    assert cfg.head_dim == 32 != cfg.d_model // cfg.num_heads
    kd, md = cfg.kda_dims, cfg.mla_dims
    assert (kd.heads, kd.key_dim, kd.value_dim, kd.conv, kd.chunk) \
        == (4, 16, 16, 4, 64)
    assert (md.kv_rank, md.rope_dim, md.row) == (16, 8, 24)
    assert (cfg.num_experts, cfg.moe_groups, cfg.moe_groups_kept,
            cfg.moe_top_k) == (16, 4, 2, 4)
    # a mixer's stack holds the layers of its kind and no other
    assert params["blocks"]["kda"]["w_qkv"].shape == (4, 64, 192)
    assert params["blocks"]["mla"]["w_kva"].shape == (2, 64, 24)
    assert params["dense_blocks"]["kda"]["w_qkv"].shape == (1, 64, 192)
    assert "attn" not in params["blocks"]
    # seeded so that the decays spread over 0.9 .. 0.9999 and move
    k = params["blocks"]["kda"]
    x = np.exp(np.asarray(k["A_log"]))[:, :, None] \
        * np.asarray(k["dt_bias"]).reshape(4, 4, 16)
    assert -10.9 < x.min() and x.max() < -3.7
    bias = np.asarray(params["blocks"]["gate"]["bias"])
    assert np.abs(bias).max() > 0.01


def test_published_preset_is_the_catalog_entry():
    cfg = build_config("ling-3.0-flash")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.head_dim,
            cfg.d_ff, cfg.moe_d_ff, cfg.vocab_size, cfg.num_experts,
            cfg.moe_top_k) == (42, 2560, 32, 128, 6144, 768, 157184, 512, 8)
    assert cfg.layer_plan == (2, 6, 4)
    # layer l holds latent attention where (l + 1) % 6 == 0
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "mla"] \
        == [5, 11, 17, 23, 29, 35, 41]
    assert cfg.mla_dims.row == 576 and cfg.kda_dims.conv_channels == 12288


@pytest.mark.parametrize("n", [70, 64, 5])
def test_apply_agrees_with_the_reference(tiny, ref, n):
    """Lengths that the chunk of 64 divides and does not."""
    cfg, params, _ = tiny
    ids = np.random.default_rng(n).integers(0, cfg.vocab_size, n)
    want = np.asarray(ref.logits(params, ids, ref_config(cfg)))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, i: apply(cfg, p, i))(
            params, jnp.asarray(ids)[None]))[0]
    assert rel(got, want) < TOL


def token_rule(q, k, v, g, beta, init):
    """The delta rule's three equations token by token.  q, k, g: [T, H,
    K]; v: [T, H, V]; beta: [T, H]; init: [H, K, V] → (o [T, H, V], the
    last state)."""
    hi = jax.lax.Precision.HIGHEST

    def one(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s_hat = jnp.exp(g_t)[..., None] * s
        pred = jnp.einsum("hkv,hk->hv", s_hat, k_t, precision=hi)
        s = s_hat + k_t[..., None] * (b_t[:, None] * (v_t - pred))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=hi)

    last, o = jax.lax.scan(one, init, (q, k, v, g, beta))
    return o, last


@pytest.mark.parametrize("g", [-4.9999, -0.3, None])
def test_chunked_form_agrees_with_the_token_rule(g):
    """Decays at the gate's bound in every channel of a block, mild
    ones, and seeded ones; a first state that is not zero; a last chunk
    that is not full."""
    H, K, V, T, Q = 2, 16, 16, 150, 64
    dims = kda.KDADims(H, K, V, 4, Q, 16, -5.0)
    ks = jax.random.split(jax.random.PRNGKey(0), 7)

    def l2(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q = l2(jax.random.normal(ks[0], (T, H, K))) * K ** -0.5
    k = l2(jax.random.normal(ks[1], (T, H, K)))
    v = jax.random.normal(ks[2], (T, H, V))
    gs = jnp.full((T, H, K), g) if g is not None else \
        -5 * jax.nn.sigmoid(jax.random.normal(ks[3], (T, H, K)) * 3 - 3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    init = jax.random.normal(ks[5], (H, K, V))
    want, last = token_rule(q, k, v, gs, beta, init)
    nc = -(-T // Q)

    def chunks(t):
        t = jnp.pad(t, ((0, nc * Q - T),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape((nc, Q) + t.shape[1:])

    got, left = kda.chunk_rule(
        chunks(q), chunks(k), chunks(v), chunks(gs), chunks(beta),
        jnp.arange(nc) == 0, jnp.broadcast_to(init, (nc, H, K, V)), dims)
    got = got.reshape(nc * Q, H, V)[:T]
    assert rel(np.asarray(got), np.asarray(want)) < 2e-3
    assert rel(np.asarray(left[-1]), np.asarray(last)) < 2e-3
    assert bool(jnp.isfinite(got).all())


@pytest.fixture(scope="module")
def seqs(tiny):
    cfg = tiny[0]
    rng = np.random.default_rng(0)
    # prompts cut into steps of 37 tokens, which divide neither them nor
    # the chunk of 64 nor its blocks of 16; then 8 fed tokens
    lens = {1: 153, 2: 31, 3: 9}
    return ({u: rng.integers(0, cfg.vocab_size, n + 8).tolist()
             for u, n in lens.items()}, lens)


@pytest.fixture(scope="module")
def system_rows(tiny, seqs):
    with jax.default_matmul_precision("highest"):
        return paged_logits(engine(tiny), *seqs)


def test_chunked_prefill_and_decode_agree_with_the_reference(
        tiny, ref, seqs, system_rows):
    cfg, params, _ = tiny
    tokens, n_prompt = seqs
    rows, scheds = system_rows
    for u, s in tokens.items():
        want = np.asarray(ref.logits(params, np.asarray(s), ref_config(cfg),
                                     last=9))
        got = np.stack(rows[u])
        assert got.shape == want.shape
        assert rel(got, want) < TOL, u
    # a prompt went over several steps, and a step held decode rows of
    # several sequences beside a run of several tokens
    assert sum(1 for s in scheds if (1, 37) in s) >= 4
    assert any(sum(n == 1 for _, n in s) >= 2 and any(n > 1 for _, n in s)
               for s in scheds)


def test_the_kernel_serves_what_the_xla_formulation_serves(tiny, seqs,
                                                           system_rows):
    """``attn_impl="pallas"`` (the latent layers' kernel, interpreted
    here; a delta-rule layer has no call to make): the same schedules
    and the XLA formulation's logits over chunked prefill and decode."""
    eng = engine(tiny, attn_impl="pallas")
    assert eng.attn_impl == "pallas"
    with jax.default_matmul_precision("highest"):
        rows, scheds = paged_logits(eng, *seqs)
    assert scheds == system_rows[1]
    for u, want in system_rows[0].items():
        got, want = np.stack(rows[u]), np.stack(want)
        assert rel(got, want) < 1e-4, u
        assert (got.argmax(-1) == want.argmax(-1)).all()


def test_step_of_decode_rows_and_two_prefill_runs(tiny, ref):
    """Two sequences decoding while two prompts prefill in one step."""
    cfg, params, _ = tiny
    rng = np.random.default_rng(5)
    toks = {u: rng.integers(0, cfg.vocab_size, n).tolist()
            for u, n in ((1, 12), (2, 7), (3, 6), (4, 5))}
    eng = engine(tiny, token_budget=20)
    step = eng._build_step(eng.max_blocks_per_seq)

    def run(feed):
        for u, t in feed.items():
            eng.put(u, t)
        sched = eng._schedule()
        batch = eng._stage(eng.state.build_batch(sched, 20))
        logits, eng.state.kv = step(eng.params, eng._quant, eng.state.kv,
                                    batch)
        return sched, batch, np.asarray(logits)

    with jax.default_matmul_precision("highest"):
        run({1: toks[1][:11], 2: toks[2][:6]})
        sched, batch, logits = run({1: toks[1][11:], 2: toks[2][6:],
                                    3: toks[3], 4: toks[4]})
    assert sorted(len(t) for _, t in sched) == [1, 1, 5, 6]
    assert int((np.asarray(batch.rec.chunks)[:, 1] > 0).sum()) == 2
    for u, t in toks.items():
        want = np.asarray(ref.logits(params, np.asarray(t), ref_config(cfg),
                                     last=1))[0]
        assert rel(logits[eng.state.slot(u)], want) < TOL, u


def test_a_slot_taken_by_a_new_sequence_starts_from_zeros(tiny, ref):
    cfg, params, _ = tiny
    rng = np.random.default_rng(7)
    first = {1: rng.integers(0, cfg.vocab_size, 30).tolist()}
    second = {2: rng.integers(0, cfg.vocab_size, 17).tolist()}
    eng = engine(tiny, max_seqs=1)
    with jax.default_matmul_precision("highest"):
        paged_logits(eng, first, {1: 25})
        assert float(jnp.abs(eng.state.kv["ssm"][:, 0]).max()) > 0
        eng.flush(1)
        rows, _ = paged_logits(eng, second, {2: 9})
    assert eng.state.slot(2) == 0
    want = np.asarray(ref.logits(params, np.asarray(second[2]),
                                 ref_config(cfg), last=9))
    assert rel(np.stack(rows[2]), want) < TOL


def served_engine(tiny, **kw):
    from deepspeed_tpu.inference.overload import OverloadConfig
    kw.setdefault("overload", OverloadConfig(prefill_chunk=16))
    return engine(tiny, token_budget=32, **kw)


def test_hold_and_resume_with_a_row_launched_ahead(tiny):
    """The row launched ahead and thrown away has moved the state one
    token and written its latent row: fed again it must leave the state
    where one pass would, and write the same row again.  (The engine
    that replays reads its latent rows by the kernel, the plain pass by
    the XLA formulation: one test holds both to one answer.)"""
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, tiny[0].vocab_size, 21).tolist()
    plain = served_engine(tiny)
    plain.put(7, prompt, max_new_tokens=40)
    want = []
    while len(want) < 9:
        want += list(plain.step(sampling=GREEDY).values())
    eng = served_engine(tiny, attn_impl="pallas")
    eng.put(7, prompt, max_new_tokens=40)
    got = []
    while len(got) < 3:
        got += list(eng.step(sampling=GREEDY).values())
    assert eng._ahead is not None and 7 in eng._ahead.uids
    eng.hold(7)
    assert 7 in eng._void and eng.state.seqs[7].state_ahead == 1
    for _ in range(3):                            # nothing comes by itself
        assert eng.step(sampling=GREEDY) == {}
    eng.put(7, [got[-1]])                         # the held token resumes
    while len(got) < 9:
        got += list(eng.step(sampling=GREEDY).values())
    assert got[:9] == want[:9]
    assert eng.metrics.snapshot()["serving_state_replayed_rows_total"] == 1
    for e in (plain, eng):
        e.hold(7)
        while e.in_flight:
            e.step(sampling=GREEDY)
    assert plain.state.seqs[7].seen_tokens == eng.state.seqs[7].seen_tokens
    a, b = (e.state.kv for e in (plain, eng))
    sa, sb = plain.state.slot(7), eng.state.slot(7)
    np.testing.assert_allclose(np.asarray(b["ssm"][:, sb]),
                               np.asarray(a["ssm"][:, sa]), atol=1e-5)
    tok = plain.state.seqs[7].tokens[-1]
    np.testing.assert_allclose(next_logits(eng, 7, tok),
                               next_logits(plain, 7, tok), atol=1e-4)


@pytest.mark.parametrize("option,value", [
    ("prefix_cache", "on"), ("spec_decode", "on"), ("kv_tier", "on")])
def test_engine_refuses_by_name_what_cannot_serve_the_model(tiny, option,
                                                            value):
    with pytest.raises(ValueError, match=option):
        engine(tiny, **{option: value})


def test_a_cache_per_layer_kind_and_auto_resolves_to_off(tiny):
    from deepspeed_tpu.inference import SamplingParams
    eng = engine(tiny)                  # prefix_cache, spec_decode: auto
    assert not eng.state.prefix_cache and eng._spec is None
    assert eng.attn_impl == "xla"
    kd, md = tiny[0].kda_dims, tiny[0].mla_dims
    # five KDA layers hold state rows and no blocks; two MLA layers hold
    # blocks of latent rows and no state
    assert eng.state.kv["ssm"].shape == (5, 5, kd.heads, kd.key_dim,
                                         kd.value_dim)
    assert eng.state.kv["conv"].shape == (5, 5, kd.conv, kd.conv_channels)
    # (a row's 24 values in one whole vector of 128 lanes)
    assert eng.state.kv["kv"].shape == (2, 65, 8, 128) and md.row == 24
    with pytest.raises(NotImplementedError, match="one block type"):
        engine(tiny, weight_quant="int8").generate(
            {1: [1, 2, 3]}, SamplingParams(max_new_tokens=2))


def test_every_wrong_forward_fails_the_tolerance(tiny, ref, seqs,
                                                 system_rows):
    """What the chip's comparison has to tell, told here in float32: the
    true forward passes ``TOL`` above, each control reads far over it
    (``norm_over_held`` where a share is held: below)."""
    cfg, params, _ = tiny
    tokens, _ = seqs
    got = np.stack(system_rows[0][1])
    s = np.asarray(tokens[1])
    at = {"state_reset": "@153", "no_tail": "@111"}
    for wrong in ref.WRONG:
        if wrong == "norm_over_held":
            continue
        bad = np.asarray(ref.logits(params, s, ref_config(cfg),
                                    wrong=wrong + at.get(wrong, ""),
                                    last=9))
        assert rel(got, bad) > 50 * TOL, wrong


# ---- a share of the experts -------------------------------------------

def expert_layer(tiny, li=0):
    cfg, params, _ = tiny
    b = params["blocks"]
    take = lambda t: jax.tree.map(lambda a: a[li], t)   # noqa: E731
    return cfg, take(b["gate"]), take(b["experts"]), take(b["shared"])


def test_the_four_shares_add_up_to_the_uncut_layer(tiny, ref):
    """The expert layer run four times, each holding a quarter of the
    experts: the routed parts summed, the shared expert counted once,
    equal the uncut reference's whole layer."""
    cfg, gate, experts, shared = expert_layer(tiny)
    h = jax.random.normal(jax.random.PRNGKey(1), (23, cfg.d_model))
    act = jax.nn.silu
    kw = dict(top_k=cfg.moe_top_k, activation=act, gated=True,
              norm_topk=True, score="sigmoid",
              route_scale=cfg.moe_route_scale,
              groups=(cfg.moe_groups, cfg.moe_groups_kept))
    # (the reference takes the experts as the stack of all layers)
    stack = lambda e: jax.tree.map(lambda a: a[None], e)   # noqa: E731
    lp = {"gate": gate, "experts": stack(experts), "shared": shared}
    with jax.default_matmul_precision("highest"):
        want, _ = ref._experts(h, lp, ref_config(cfg), None)
        whole, stats = M.moe_serve(gate, experts, h, **kw)
        parts, computed = 0.0, 0
        for first in range(0, 16, 4):
            mine = jax.tree.map(lambda a: a[first:first + 4], experts)
            y, st, ids = M.moe_serve(gate, mine, h, held=(first, 4),
                                     with_ids=True, **kw)
            parts = parts + y
            computed += int(st[0])
            # the router's own numbering, all of its choices
            assert int(ids.max()) > 3 or first == 12
            # and against the reference that holds the same share
            c = dict(ref_config(cfg), experts_held=[first, 4])
            one, _ = ref._experts(h, {**lp, "experts": stack(mine)}, c,
                                  None)
            sh = ref._swiglu(h, shared)
            assert rel(np.asarray(y), np.asarray(one - sh)) < TOL
        sh = ref._swiglu(h, shared)
    assert computed == int(stats[0]) == 23 * cfg.moe_top_k
    assert rel(np.asarray(parts + sh), np.asarray(want)) < TOL
    assert rel(np.asarray(whole + sh), np.asarray(want)) < TOL


def test_weights_normalised_over_the_held_alone_fail(tiny, ref):
    cfg, gate, experts, shared = expert_layer(tiny)
    h = jax.random.normal(jax.random.PRNGKey(2), (23, cfg.d_model))
    lp = {"gate": gate, "shared": shared,
          "experts": jax.tree.map(lambda a: a[None, :4], experts)}
    c = dict(ref_config(cfg), experts_held=[0, 4])
    with jax.default_matmul_precision("highest"):
        want, _ = ref._experts(h, lp, c, None)
        bad, _ = ref._experts(h, lp, c, "norm_over_held")
    assert rel(np.asarray(bad), np.asarray(want)) > 0.1


def written_out_selection(scores, bias, n_group, keep, k):
    """DeepSeek-V3's ``noaux_tc`` for one token, in plain Python over
    float32 numbers (the sums round as the router's do); ties go to the
    lower index, as ``lax.top_k``'s do."""
    s = [np.float32(a) + np.float32(b) for a, b in zip(scores, bias)]
    E = len(s)
    per = E // n_group
    group = []
    for g in range(n_group):
        a, b = sorted(s[g * per:(g + 1) * per], reverse=True)[:2]
        group.append(a + b)
    kept = sorted(range(n_group), key=lambda g: (-group[g], g))[:keep]
    allowed = [e for e in range(E) if e // per in kept]
    taken = sorted(allowed, key=lambda e: (-s[e], e))[:k]
    total = sum(scores[e] for e in taken)
    return taken, [scores[e] / total for e in taken]


def test_route_with_groups_is_the_written_out_selection():
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(40, 16)).astype(np.float32)
    # ties: whole rows of equal logits, and pairs
    logits[5] = 0.25
    logits[6, 3] = logits[6, 9]
    bias = rng.uniform(-0.1, 0.1, 16).astype(np.float32)
    vals, ids, _ = M.route(jnp.asarray(logits), jnp.asarray(bias), top_k=4,
                           score="sigmoid", norm_topk=True, route_scale=2.5,
                           groups=(4, 2))
    scores = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)))
    moved = 0
    for t in range(40):
        taken, w = written_out_selection(scores[t], bias, 4, 2, 4)
        assert np.asarray(ids[t]).tolist() == taken, t
        np.testing.assert_allclose(np.asarray(vals[t]),
                                   2.5 * np.asarray(w), rtol=1e-5)
        free = np.argsort(-(scores[t] + bias), kind="stable")[:4].tolist()
        moved += sorted(free) != sorted(taken)
    assert moved > 5        # the group limit changes choices
    # no groups, no held: the call is what it was
    a = M.route(jnp.asarray(logits), jnp.asarray(bias), top_k=4,
                score="sigmoid")
    b = M.route(jnp.asarray(logits), jnp.asarray(bias), top_k=4,
                score="sigmoid", groups=(1, 1))
    assert a[2] is None and b[2] is None
    assert all(bool((x == y).all()) for x, y in zip(a[:2], b[:2]))


# ---- spans, counters, gauges ------------------------------------------

def test_stage_and_readback_spans_and_counters(tiny):
    cfg, params, axes = tiny
    held = build_config("ling-tiny", experts_held=(4, 8))
    p2 = jax.tree.map(lambda a: a, params)
    p2["blocks"] = dict(params["blocks"], experts=jax.tree.map(
        lambda a: a[:, 4:12], params["blocks"]["experts"]))
    eng = InferenceEngine(
        Model.from_params(held, p2, param_axes=axes),
        InferenceConfig(token_budget=32, max_seqs=4, kv_block_size=8,
                        num_kv_blocks=64, max_seq_len=256, trace=True,
                        attn_impl="pallas",
                        param_dtype=jnp.float32, kv_dtype=jnp.float32))
    rng = np.random.default_rng(2)
    eng.put(3, [5])
    eng.put(1, rng.integers(0, 1024, 9).tolist())
    eng.put(2, rng.integers(0, 1024, 40).tolist())
    out = eng.step(sampling=GREEDY)
    while eng.in_flight and not out:
        out = eng.step(sampling=GREEDY)
    ev = eng.tracer.events()
    stage = [e["args"] for e in ev if e["name"] == "ds.serve.stage"][0]
    assert (stage["state_rows"], stage["scan_tokens"],
            stage["state_starts"]) == (1, 31, 3)
    # the cached rows the latent layer reads: the sum of seen + n
    assert stage["latent_tokens"] == 1 + 9 + 22
    assert "kv_tokens_full" not in stage
    # the latent layers' kernel: the one-token run a tile of one row, the
    # runs of 9 and of 22 a tile each of 128 rows (four heads)
    assert (stage["n_tiles_one"], stage["n_tiles_run"],
            stage["tile_fill"]) == (1, 2, 31 / 256)
    assert "n_tiles_short" not in stage
    back = [e["args"] for e in ev if e["name"] == "ds.serve.readback"][0]
    made = 32 * held.moe_top_k * 6
    assert back["moe_assignments_made"] == made
    assert 0 < back["moe_assignments"] < made
    assert 0 < back["moe_experts_touched"] <= 6 * 8
    snap = eng.metrics.snapshot()
    asg = snap["serving_moe_assignments_total"]
    assert asg['{where="held"}'] + asg['{where="absent"}'] >= made
    assert snap["serving_attn_kv_tokens_total"]['{kind="latent"}'] >= 32
    assert snap["serving_attn_tiles_total"]['{height="run"}'] >= 2
    assert snap["serving_attn_tile_fill"] == pytest.approx(31 / 256,
                                                           abs=1e-6)
    assert snap["serving_latent_pool_bytes"] == 2 * 65 * 8 * 128 * 4
    assert snap["serving_state_bytes"] \
        == 3 * eng._recurrent.bytes_per_seq(5)


# ---- the configuration's file and the benchmark's arithmetic ----------

@pytest.fixture(scope="module")
def d7():
    from benchmarks.lib.drivers import serve_hybrid_share as D
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling-3.0-flash-d7.json")) as f:
        config = json.load(f)
    return config, D.preset_config(config)


def test_configuration_file_says_what_the_shapes_say(d7):
    """The file through the cell's driver, abstract shapes only."""
    from benchmarks.lib import arith_kda as A
    from deepspeed_tpu.inference.ragged.state import (KVCacheConfig,
                                                      RecurrentConfig)
    config, cfg = d7
    assert cfg.layer_kinds == ("kda",) * 6 + ("mla",)
    assert cfg.experts_held == (0, 128) and cfg.num_experts == 512
    shapes = jax.eval_shape(lambda k: init_params(cfg, k)[0],
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert round(n / 1e6, 1) == 5231.8
    assert "5,231.8 M parameters" in config["deployment"]
    assert shapes["blocks"]["experts"]["wi"].shape == (6, 128, 2560, 768)
    assert shapes["blocks"]["gate"]["kernel"].shape == (6, 2560, 512)
    # 1,152 bytes a token in the one latent layer
    kd = cfg.kda_dims
    rc = RecurrentConfig(heads=kd.heads, head_dim=kd.key_dim,
                         state=kd.value_dim, conv=kd.conv,
                         channels=kd.conv_channels, chunk=kd.chunk,
                         state_dtype=jnp.float32,
                         layers=cfg.layers_of("kda"))
    kv = KVCacheConfig(num_layers=cfg.layers_of("mla"), num_kv_heads=32,
                       head_dim=128, block_size=64, num_blocks=16,
                       recurrent=rc, latent_dim=cfg.mla_dims.row)
    cache = jax.eval_shape(lambda: kv.cache_zeros(128))
    # 576 values a token, in rows of five whole vectors of 128 lanes
    assert cache["kv"].shape == (1, 17, 64, 640)
    assert cfg.mla_dims.row * 2 == 1152 == A.latent_row(A.model(config)) * 2
    assert "1,152 bytes" in config["deployment"]
    assert cache["ssm"].shape == (6, 129, 32, 128, 128)
    assert cache["ssm"].dtype == jnp.float32        # the model's, below
    assert cache["conv"].shape == (6, 129, 4, 12288)
    assert cache["conv"].dtype == jnp.bfloat16
    per_seq = rc.bytes_per_seq(6)
    assert per_seq == 6 * (4 * 32 * 128 * 128 + 2 * 4 * 12288) \
        == A.state_bytes_per_seq(A.model(config))
    assert "2 MiB + 96 KiB" in config["deployment"]
    for key in config["reduced"]:
        assert key in config["published"]
    assert len(config["reference"]["tolerance"]) >= 3


def test_arith_kda_counts_a_decode_step_by_hand(d7):
    from benchmarks.lib import arith_kda as A
    m = A.model(d7[0])
    s = {"n_tokens": 128, "n_seqs": 128, "latent_tokens": 300000,
         "state_rows": 128, "scan_tokens": 0, "state_starts": 0,
         "state_replays": 0, "moe_assignments": 1500,
         "moe_assignments_made": 128 * 8 * 6, "moe_experts_touched": 700}
    kda_w = 6 * 2560 * 4096 + 2560 * 32
    mla_w = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 \
        + 32 * 128 * 2560 + 2560 * 32
    fixed = 6 * kda_w + mla_w + 3 * 2560 * 6144 \
        + 6 * (2560 * 512 + 3 * 2560 * 768)
    assert A.fixed_params(m) == fixed
    # a float32 state read and written, three bf16 inputs of the tail
    state = 6 * 128 * (2 * (4 * 32 * 128 * 128 + 2 * 3 * 12288)
                       + 2 * (5 * 4096 + 32))
    latent = 1152 * (300000 + 128)
    experts = 2 * (700 * 3 * 2560 * 768 + 1500 * 3 * (2560 + 768))
    want = 2 * (fixed + 2560 * 39296) + state + latent + experts \
        + 128 * 2560 * 2
    assert A.step_bytes(m, s) == want
    assert A.update_bytes(m, 128) == state
    assert A.latent_bytes(m, 300000, 128) == latent


def test_benchmark_json_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "serve-kda-reason")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-3.0-flash-d7", "reason-closed-128", 1)
    mine = [m for m in bench["per_layer"]
            if "serve-kda-reason" in m.get("workloads", ())]
    # (a later cell is appended behind it in an entry's list)
    assert all(m["workloads"][0] == "serve-kda-reason"
               and m["moves"] == "out_tokens_per_s" for m in mine)
    names = {m["name"] for m in mine}
    assert {"kda_update_roofline", "kda_chunk_roofline", "kda_share",
            "latent_attn_roofline", "latent_attn_share",
            "kda_expert_gemm_roofline", "kda_step_roofline",
            "kda_state_rows_per_step", "moe_held_assignment_share"} <= names
    # arith.py and arith_moe.py count every expert of every layer, and no
    # call of this model comes from the paged kernel
    assert len(mine) == 22 and not names & {
        "serve_step_roofline", "moe_step_roofline",
        "moe_expert_gemm_roofline"}
    assert not any(n.endswith(("paged_attn_share", "kv_write_share"))
                   for n in names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "serve-kda-reason" in e2e["out_tokens_per_s"]["workloads"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_rehearsal_of_the_new_cell_exits_zero():
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "serve-kda-reason", "--seed", "4400000007",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["failed"] == 0
    assert set(last["compared_with_reference"]["checks"]) >= {
        "followed_decode", "chunked_prefill", "long_decode",
        "reused_slots_decode", "routing_shortfall"}


def test_falcon_h1_compiles_to_the_programs_it_did():
    """The sixth older configuration (the other five:
    ``test_falcon_h1.py``), hashed on the parent commit (956a699); its
    serving step again by PR 45, which folds the attention projections
    it reads.  This model's own serving step reads none of them (no
    layer goes through ``_qkv_proj``) and was PR 44's until PR 53
    ordered the expert layer's assignments choice-major (``moe_serve``);
    its training forward, which does not call that, hashed there too."""
    assert older_programs("falcon-h1-34b-d6") \
        == ("27b9176e4c465481", "9907eada4b3b23a2")
    assert older_programs("ling-3.0-flash-d7") \
        == ("aa16abc28c14303a", "721c152f81f1245c")
