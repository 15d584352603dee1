"""Latent attention with a query latent in both sublayers of every
shortcut-connected layer, the latent pool as the model's ONLY cache, and
a share of each layer's experts behind a router that also takes experts
which compute nothing (``longcat-tiny``), against the benchmark's own
plain reference (``benchmarks/reference/longcat-flash-d4.py``, imported
by path): through ``apply``; through the engine's chunked prefill and
decode and blocks that change hands; the two constant multipliers; the
router over outputs that compute nothing; the shares of a layer adding
up; where the expert output joins the stream; every wrong forward the
reference knows; what the engine resolves for such a model; the spans
and counters; the configuration's file and the benchmark's arithmetic."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceConfig, InferenceEngine
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, apply, init_params
from deepspeed_tpu.parallel import moe as M
from test_falcon_h1 import ROOT, TOL, _load, rel


@pytest.fixture(scope="module")
def ref():
    return _load("benchmarks/reference/longcat-flash-d4.py", "longcat_ref")


@pytest.fixture(scope="module")
def tiny():
    cfg = build_config("longcat-tiny")
    axes = {}

    def init(key):
        params, axes["axes"] = init_params(cfg, key)
        return params

    return cfg, jax.jit(init)(jax.random.PRNGKey(3)), axes["axes"]


def ref_config(cfg, **over):
    """What the reference reads of a configuration file, for ``cfg``."""
    md = cfg.mla_dims
    return {**dict(
        num_layers=cfg.num_layers // 2, rms_norm_eps=cfg.eps,
        rope_theta=cfg.rope_theta, num_attention_heads=cfg.num_heads,
        qk_nope_head_dim=md.nope_dim, qk_rope_head_dim=md.rope_dim,
        v_head_dim=md.value_dim, kv_lora_rank=md.kv_rank,
        q_lora_rank=md.q_rank, mla_scale_q_lora=md.q_scale != 1.0,
        mla_scale_kv_lora=md.kv_scale != 1.0, moe_topk=cfg.moe_top_k,
        routed_scaling_factor=cfg.moe_route_scale,
        zero_expert_num=cfg.moe_zero_experts,
        n_routed_experts=cfg.experts_here,
        experts_held=list(cfg.experts_held or (0, cfg.num_experts))),
        **over}


def run_apply(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, i: apply(cfg, p, i))(
            params, jnp.asarray(ids)[None]))[0]


@pytest.fixture(scope="module")
def held(tiny):
    """The model as ONE SHARE of it holds it: experts 4..11 of 16."""
    cfg, params, axes = tiny
    params = dict(params, blocks=dict(params["blocks"], experts=jax.tree.map(
        lambda a: a[:, 4:12], params["blocks"]["experts"])))
    return build_config("longcat-tiny", experts_held=(4, 8)), params, axes


@pytest.fixture(scope="module")
def served(held):
    """ONE engine for the file (a step's program compiles once), on the
    share, with a pool of 24 blocks of 8 rows: what three sequences fill,
    so every later sequence takes blocks that others left.  → (engine,
    the logits-returning step that also says the experts each row
    took)."""
    eng = _engine(held, "auto")
    return eng, eng._build_step(eng.max_blocks_per_seq, with_routing=True)


def _engine(held, attn_impl):
    cfg, params, axes = held
    return InferenceEngine(
        Model.from_params(cfg, params, param_axes=axes),
        InferenceConfig(token_budget=37, max_seqs=4, kv_block_size=8,
                        num_kv_blocks=24, max_seq_len=192, trace=True,
                        attn_impl=attn_impl,
                        param_dtype=jnp.float32, kv_dtype=jnp.float32))


@pytest.fixture(scope="module")
def served_kernel(held):
    """``served``'s engine and step with the latent layers' attention by
    the Pallas kernel (``attn_impl="pallas"``; interpreted here)."""
    eng = _engine(held, "pallas")
    return eng, eng._build_step(eng.max_blocks_per_seq, with_routing=True)


def paged(served, seqs, n_prompt):
    """``test_falcon_h1.paged_logits`` through the file's one step, the
    sequences flushed behind it → ({uid: [rows]}, the steps' schedules,
    {uid: the blocks it held})."""
    eng, step = served
    eng.state.reset_prefix_cache()
    rows, fed, scheds = {u: [] for u in seqs}, dict(n_prompt), []
    for u, s in seqs.items():
        eng.put(u, list(s[:n_prompt[u]]))
    with jax.default_matmul_precision("highest"):
        while True:
            sched = eng._schedule()
            if not sched:
                break
            scheds.append([(u, len(t)) for u, t in sched])
            batch = eng._stage(eng.state.build_batch(
                sched, eng.icfg.token_budget))
            logits, eng.state.kv, _ = step(eng.params, eng._quant,
                                           eng.state.kv, batch)
            for u, _ in sched:
                if eng.state.seqs[u].seen_tokens >= n_prompt[u]:
                    rows[u].append(np.asarray(logits[eng.state.slot(u)]))
                    if fed[u] < len(seqs[u]):
                        eng.put(u, [int(seqs[u][fed[u]])])
                        fed[u] += 1
    blocks = {u: list(eng.state.seqs[u].blocks) for u in seqs}
    for u in seqs:
        eng.flush(u)
    return rows, scheds, blocks


def test_tiny_preset_is_the_block(tiny):
    cfg, params, _ = tiny
    assert not cfg.has_ssm and cfg.recurrent_kind is None
    assert not cfg.plain_stack and cfg.mixer_stacks == ("mla",)
    assert cfg.moe_shortcut and cfg.layer_plan == (0, 2, 0)
    assert cfg.layer_kinds == ("mla",) * 4 and cfg.expert_layers == 2
    md = cfg.mla_dims
    assert (md.kv_rank, md.rope_dim, md.row, md.q_rank) == (16, 8, 24, 24)
    assert md.q_scale == (64 / 24) ** 0.5 and md.kv_scale == 2.0
    assert (cfg.num_experts, cfg.moe_zero_experts, cfg.router_outputs,
            cfg.moe_top_k) == (16, 8, 24, 4)
    b = params["blocks"]
    # a row a SUBLAYER of the attentions, the dense MLPs and the norms;
    # a row a LAYER of the router and the experts
    assert b["mla"]["wq_a"].shape == (4, 64, 24) and "wq" not in b["mla"]
    assert b["mla"]["wq_b"].shape == (4, 24, 4 * 24)
    assert b["mlp"]["wi"].shape == (4, 64, 160)
    assert b["ln2"]["scale"].shape == (4, 64)
    assert b["gate"]["kernel"].shape == (2, 64, 24)
    assert b["gate"]["bias"].shape == (2, 24)
    assert b["experts"]["wi"].shape == (2, 16, 64, 48)
    # the bias a sixth of the softmax scores' spread, the norms' scales
    # away from one
    assert 0.05 / 24 < np.abs(np.asarray(b["gate"]["bias"])).max() < 0.15 / 24
    assert np.abs(np.asarray(b["mla"]["q_norm"]) - 1).max() > 0.3


def test_published_preset_is_the_catalog_entry():
    cfg = build_config("longcat-flash")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.d_ff,
            cfg.moe_d_ff, cfg.vocab_size, cfg.num_experts,
            cfg.moe_zero_experts, cfg.moe_top_k, cfg.moe_route_scale) \
        == (56, 6144, 64, 12288, 2048, 131072, 512, 256, 12, 6.0)
    md = cfg.mla_dims
    assert (md.q_rank, md.kv_rank, md.nope_dim, md.rope_dim, md.value_dim,
            md.row) == (1536, 512, 128, 64, 128, 576)
    assert md.q_scale == 2.0 and abs(md.kv_scale - 12 ** 0.5) < 1e-12
    assert (cfg.rope_theta, cfg.eps, cfg.rotary_dim) == (1e7, 1e-5, 64)
    assert cfg.layer_plan == (0, 28, 0) and cfg.expert_layers == 28
    assert not cfg.moe_norm_topk and cfg.moe_score == "softmax"


@pytest.mark.parametrize("n", [40, 5])
def test_apply_agrees_with_the_reference(tiny, ref, n):
    cfg, params, _ = tiny
    ids = np.random.default_rng(n).integers(0, cfg.vocab_size, n)
    want = np.asarray(ref.logits(params, ids, ref_config(cfg)))
    assert rel(run_apply(cfg, params, ids), want) < TOL


@pytest.fixture(scope="module")
def seqs(tiny):
    cfg = tiny[0]
    rng = np.random.default_rng(0)
    # prompts cut into steps of 37 tokens, which divide neither them nor
    # the chunk of 16 nor the blocks of 8; then 8 fed tokens
    lens = {1: 100, 2: 31, 3: 9}
    return ({u: rng.integers(0, cfg.vocab_size, n + 8).tolist()
             for u, n in lens.items()}, lens)


@pytest.fixture(scope="module")
def system_rows(served, seqs):
    return paged(served, *seqs)


@pytest.fixture(scope="module")
def wanted(held, ref, seqs):
    cfg, params, _ = held
    return {u: np.asarray(ref.logits(params, np.asarray(s), ref_config(cfg),
                                     last=9)) for u, s in seqs[0].items()}


def test_chunked_prefill_and_decode_agree_with_the_reference(
        seqs, system_rows, wanted):
    rows, scheds, blocks = system_rows
    for u, want in wanted.items():
        got = np.stack(rows[u])
        assert got.shape == want.shape
        assert rel(got, want) < TOL, u
    # a prompt went over several steps, and a step held decode rows of
    # several sequences beside a run of several tokens
    assert sum(1 for s in scheds if (1, 37) in s) >= 2
    assert any(any(n == 1 for _, n in s) and any(n > 1 for _, n in s)
               for s in scheds), scheds
    assert sum(len(b) for b in blocks.values()) == 22       # of 24


def test_the_kernel_serves_what_the_xla_formulation_serves(
        seqs, system_rows, served_kernel):
    """Chunked prefill and decode through ``attn_impl="pallas"``: the
    same schedules, the same blocks, the XLA formulation's logits."""
    rows, scheds, blocks = paged(served_kernel, *seqs)
    assert served_kernel[0].attn_impl == "pallas"
    assert (scheds, blocks) == system_rows[1:]
    for u, want in system_rows[0].items():
        got, want = np.stack(rows[u]), np.stack(want)
        assert rel(got, want) < 1e-4, u
        assert (got.argmax(-1) == want.argmax(-1)).all()


def test_freed_blocks_are_taken_again(held, served, ref, system_rows):
    """The pool was filled and left: a later sequence takes blocks that
    others held, and reads none of their rows."""
    cfg, params, _ = held
    before = {b for bs in system_rows[2].values() for b in bs}
    ids = np.random.default_rng(7).integers(0, cfg.vocab_size, 45).tolist()
    rows, _, blocks = paged(served, {7: ids}, {7: 37})
    assert len(set(blocks[7]) & before) >= 4
    want = np.asarray(ref.logits(params, np.asarray(ids), ref_config(cfg),
                                 last=9))
    assert rel(np.stack(rows[7]), want) < TOL


def test_a_model_with_both_multipliers_at_one_differs(tiny, ref):
    """The constant multipliers on the normed latents are part of the
    forward: a model with both at 1.0 differs, and the reference told
    the same (``mla_scale_*`` false) agrees with it."""
    cfg, params, _ = tiny
    ids = np.random.default_rng(40).integers(0, cfg.vocab_size, 40)
    want = np.asarray(ref.logits(params, ids, ref_config(cfg)))
    plain = build_config("longcat-tiny", mla_scale_latents=False)
    assert plain.mla_dims.q_scale == plain.mla_dims.kv_scale == 1.0
    got = run_apply(plain, params, ids)
    assert rel(got, want) > 0.1
    told = ref_config(cfg, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    assert rel(got, np.asarray(ref.logits(params, ids, told))) < TOL


def _layer(E=16, Z=8, d=32, ff=24, seed=0):
    """A small expert layer with ``Z`` outputs that compute nothing."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    gate = {"kernel": jax.random.normal(k[0], (d, E + Z)) * 0.3,
            "bias": jax.random.uniform(k[1], (E + Z,), minval=-0.01,
                                       maxval=0.01)}
    experts, _ = M.experts_init(k[2], E, d, ff, gated=True)
    h = jax.random.normal(k[3], (11, d))
    return gate, experts, h


def _serve(gate, experts, h, valid=None, **kw):
    return M.moe_serve(gate, experts, h, valid, top_k=4, activation=jax.nn.silu,
                       gated=True, norm_topk=False, score="softmax",
                       route_scale=6.0, **kw)


def _whole_layer(gate, experts, h):
    """``MoE(h)`` written out densely: the softmax over all outputs, the
    top 4 by the biased scores, 6 x the unbiased scores, the experts'
    terms and the identity part."""
    s = jax.nn.softmax(h @ gate["kernel"], axis=-1)
    _, ids = jax.lax.top_k(s + gate["bias"], 4)
    w = 6.0 * jnp.take_along_axis(s, ids, axis=1)
    # every expert's output for every row, then a row's input once for
    # each output that computes nothing: [T, E + Z, d]
    u = jax.nn.silu(jnp.einsum("td,edf->tef", h, experts["wg"])) \
        * jnp.einsum("td,edf->tef", h, experts["wi"])
    out = jnp.einsum("tef,efd->ted", u, experts["wo"])
    Z = gate["kernel"].shape[1] - out.shape[1]
    out = jnp.concatenate(
        [out, jnp.broadcast_to(h[:, None], (h.shape[0], Z, h.shape[1]))], 1)
    y = (jnp.take_along_axis(out, ids[:, :, None], axis=1)
         * w[:, :, None]).sum(1)
    return y, ids


def test_zero_experts_reach_no_group_and_give_the_input_back():
    gate, experts, h = _layer()
    valid = jnp.arange(11) < 9              # two rows pad the step
    with jax.default_matmul_precision("highest"):
        y, stats, taken = _serve(gate, experts, h, valid, zero=8,
                                 with_ids=True)
        want, ids = _whole_layer(gate, experts, h)
    # the router's own numbering; padding rows stay nowhere
    assert np.array_equal(np.sort(np.asarray(taken[:9])),
                          np.sort(np.asarray(ids[:9])))
    assert (np.asarray(taken[9:]) == 24).all()
    assert float(jnp.abs(y[9:]).max()) == 0.0
    assert rel(np.asarray(y[:9]), np.asarray(want[:9])) < 1e-5
    n_zero = int((np.asarray(ids[:9]) >= 16).sum())
    assert 0 < n_zero < 36
    # computed assignments count the experts with weights alone
    assert stats.shape == (4,)
    assert int(stats[0]) == 36 - n_zero and int(stats[3]) == n_zero
    # and a router without such outputs keeps its three statistics
    plain = {k: v[..., :16] for k, v in gate.items()}
    assert _serve(plain, experts, h)[1].shape == (3,)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """4 shares of 16 experts with weights and 8 without: the held parts,
    the identity part counted once, are the whole layer's output."""
    gate, experts, h = _layer()
    with jax.default_matmul_precision("highest"):
        want, _ = _whole_layer(gate, experts, h)
        whole, _ = _serve(gate, experts, h, zero=8)
        parts, stats = zip(*[_serve(
            gate, jax.tree.map(lambda a: a[f:f + 4], experts), h, zero=8,
            held=(f, 4)) for f in range(0, 16, 4)])
        s = jax.nn.softmax(h @ gate["kernel"], axis=-1)
        _, ids = jax.lax.top_k(s + gate["bias"], 4)
        identity = (6.0 * jnp.where(ids >= 16, jnp.take_along_axis(
            s, ids, axis=1), 0.0).sum(1))[:, None] * h
    assert rel(np.asarray(whole), np.asarray(want)) < 1e-5
    total = sum(parts) - 3 * identity
    assert rel(np.asarray(total), np.asarray(want)) < 1e-5
    # every share counts the same zero assignments, and its own computed
    assert len({int(st[3]) for st in stats}) == 1
    assert sum(int(st[0]) for st in stats) + int(stats[0][3]) == 11 * 4


def test_training_dispatches_refuse_experts_that_compute_nothing(tiny):
    gate, experts, h = _layer()
    for mode in ("scatter", "ragged"):
        with pytest.raises(ValueError, match="compute nothing"):
            M.moe_ffn(gate, experts, h[None], top_k=4, capacity_factor=1.25,
                      dispatch_mode=mode, zero=8)


@pytest.mark.parametrize("wrong", ["no_q_scale", "no_kv_scale",
                                   "renormalised", "no_zero", "early_skip"])
def test_every_wrong_forward_differs_from_the_system(held, ref, seqs,
                                                     system_rows, wanted,
                                                     wrong):
    """A dropped multiplier, renormalised weights, the identity part left
    out, the expert output added a sublayer EARLY (it joins the stream at
    the layer's end and not before): each wrong forward is far from what
    the engine computed, which is the true forward's.  (The reference
    knows more, ``WRONG``: the chip run reads them all.)"""
    cfg, params, _ = held
    assert wrong in ref.WRONG
    bad = np.asarray(ref.logits(params, np.asarray(seqs[0][3]),
                                ref_config(cfg), wrong=wrong, last=9))
    got = np.stack(system_rows[0][3])
    assert rel(got, wanted[3]) < TOL
    assert rel(got, bad) > 50 * TOL, wrong


def test_following_the_engines_routing(held, served, ref):
    """The step says which experts each row took, in the router's own
    numbering: the reference that follows them agrees, and finds no
    shortfall in float32."""
    cfg, params, _ = held
    eng, step = served
    ids = np.random.default_rng(9).integers(0, cfg.vocab_size, 30).tolist()
    eng.put(5, ids)
    batch = eng._stage(eng.state.build_batch(eng._schedule(), 37))
    with jax.default_matmul_precision("highest"):
        logits, eng.state.kv, took = step(eng.params, eng._quant,
                                          eng.state.kv, batch)
    row = np.asarray(logits[eng.state.slot(5)])
    eng.flush(5)
    took = np.asarray(took)
    assert took.shape == (2, 37, 4)
    assert (took[:, 30:] == 24).all() and took[:, :30].max() < 24
    assert (took[:, :30] >= 16).any()           # zero-compute ones taken
    want, short = ref.following(params, np.asarray(ids), ref_config(cfg),
                                took[:, :30], last=1)
    assert rel(row, np.asarray(want)[0]) < TOL
    assert short < 1e-4


def test_the_latent_pool_is_a_plain_block_pool(held, served):
    """What the engine resolves for a latent-only model: no state rows,
    the prefix cache on, speculation refused by name."""
    eng, _ = served
    assert eng._recurrent is None and eng.attn_impl == "xla"    # on a CPU
    assert eng.state.cfg.latent_dim == 24 and eng.state.cfg.num_layers == 4
    assert eng.state.cfg.runs.chunk == 16
    assert eng.state.kv.shape == (4, 25, 8, 128)
    assert eng.state.prefix_cache
    cfg, params, axes = held
    model = Model.from_params(cfg, params, param_axes=axes)
    with pytest.raises(ValueError, match="spec_decode"):
        InferenceEngine(model, InferenceConfig(spec_decode="on"))


@pytest.mark.parametrize("engine", ["served", "served_kernel"])
def test_a_prefix_hit_aliases_latent_blocks(held, ref, engine, request):
    cfg, params, _ = held
    served = request.getfixturevalue(engine)
    eng, _ = served
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, 32).tolist()
    a = shared + rng.integers(0, cfg.vocab_size, 5).tolist()
    b = shared + rng.integers(0, cfg.vocab_size, 9).tolist()
    paged(served, {1: a}, {1: len(a)})
    cached = eng.timings["cached_tokens"]
    # (``paged`` empties the prefix cache first: put the first again)
    rows = {}
    eng.state.reset_prefix_cache()
    step = served[1]
    for uid, toks in ((1, a), (2, b)):
        eng.put(uid, list(toks))
        with jax.default_matmul_precision("highest"):
            while True:
                sched = eng._schedule()
                if not sched:
                    break
                batch = eng._stage(eng.state.build_batch(sched, 37))
                logits, eng.state.kv, _ = step(eng.params, eng._quant,
                                               eng.state.kv, batch)
        rows[uid] = np.asarray(logits[eng.state.slot(uid)])
    assert eng.timings["cached_tokens"] - cached == 32
    assert eng.state.seqs[1].blocks[:4] == eng.state.seqs[2].blocks[:4]
    eng.flush(1)
    eng.flush(2)
    want = np.asarray(ref.logits(params, np.asarray(b), ref_config(cfg),
                                 last=1))
    assert rel(rows[2], want[0]) < TOL


def test_the_kernel_serves_the_loop_and_counts_its_tiles(held, served,
                                                         served_kernel):
    """``attn_impl="pallas"`` is served, not refused: the served loop
    (a row launched ahead, thrown away by a hold and fed again among its
    steps) emits the XLA formulation's tokens, and the latent kernel's
    tiles are counted where the paged kernel's are."""
    from deepspeed_tpu.inference import SamplingParams
    greedy = SamplingParams(temperature=0.0, max_new_tokens=1)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 1024, 43).tolist()
    beside = rng.integers(0, 1024, 9).tolist()

    def run(eng):
        eng.state.reset_prefix_cache()
        eng.tracer.clear()
        eng.put(7, prompt, max_new_tokens=12)
        eng.put(8, beside, max_new_tokens=12)
        got = {7: [], 8: []}

        def steps(until):
            while not until():
                for u, t in eng.step(sampling=greedy).items():
                    got[u].append(t)

        steps(lambda: len(got[7]) >= 3)
        assert eng._ahead is not None and 7 in eng._ahead.uids
        eng.hold(7)
        steps(lambda: len(got[8]) >= 6)
        eng.put(7, [got[7][-1]])                  # the held token resumes
        steps(lambda: len(got[7]) >= 8 and len(got[8]) >= 8)
        for u in got:
            eng.hold(u)
        while eng.in_flight:
            eng.step(sampling=greedy)
        stages = [e["args"] for e in eng.tracer.events()
                  if e["name"] == "ds.serve.stage"]
        for u in got:
            eng.flush(u)
        return {u: t[:8] for u, t in got.items()}, stages

    want, plain = run(served[0])
    got, stages = run(served_kernel[0])
    assert got == want
    assert all("n_tiles_one" not in st for st in plain)
    # the first step: 37 rows of the long prompt in tiles of 128 rows
    # (four heads), nothing else
    first = stages[0]
    assert (first["n_tokens"], first["n_tiles_one"], first["n_tiles_run"],
            first["tile_fill"]) == (37, 0, 1, 37 / 128)
    # a decode step of the two: two tiles of one row
    assert any((st["n_tiles_one"], st["n_tiles_run"], st["tile_fill"])
               == (2, 0, 0.0) for st in stages)
    assert all("n_tiles_short" not in st for st in stages)
    snap = served_kernel[0].metrics.snapshot()
    tiles = snap["serving_attn_tiles_total"]
    assert tiles['{height="one"}'] == sum(st["n_tiles_one"] for st in stages)
    assert tiles['{height="run"}'] == sum(st["n_tiles_run"] for st in stages)
    assert 0 < snap["serving_attn_tile_fill"] < 1
    # (an engine that ran the XLA formulation counted none)
    assert not served[0].metrics.snapshot().get("serving_attn_tiles_total")


def test_served_loop_spans_and_counters(held, served):
    """The served step (``step`` / ``generate``: sampled tokens and the
    routing statistics' FOUR rows behind them), its spans and counters,
    and that it emits what feeding the strict step's argmax does."""
    cfg, _, _ = held
    eng, step = served
    eng.state.reset_prefix_cache()
    assert eng._zero_toks.shape == (4 + 4,)
    rng = np.random.default_rng(2)
    prompts = {1: rng.integers(0, 1024, 9).tolist(),
               2: rng.integers(0, 1024, 23).tolist()}
    from deepspeed_tpu.inference import SamplingParams
    got = eng.generate(prompts, SamplingParams(temperature=0.0,
                                               max_new_tokens=5))
    ev = eng.tracer.events()
    stage = [e["args"] for e in ev if e["name"] == "ds.serve.stage"
             and e["args"]["n_tokens"] == 32][0]
    # the cached rows ONE latent sublayer reads, and its (query, row) pairs
    assert stage["latent_tokens"] == 9 + 23
    assert stage["latent_pairs"] == 9 * 10 // 2 + 23 * 24 // 2
    assert stage["preemptions"] == 0 and "state_rows" not in stage
    back = [e["args"] for e in ev if e["name"] == "ds.serve.readback"
            and e["args"].get("sid") == stage["sid"]][0]
    made = 32 * cfg.moe_top_k * 2           # two expert layers
    assert back["moe_assignments_made"] == made
    assert 0 < back["moe_zero_assignments"] < made
    assert 0 < back["moe_assignments"] < made - back["moe_zero_assignments"]
    assert 0 < back["moe_experts_touched"] <= 2 * 8
    snap = eng.metrics.snapshot()
    asg = snap["serving_moe_assignments_total"]
    assert asg['{where="held"}'] + asg['{where="absent"}'] \
        + asg['{where="zero"}'] >= made
    assert asg['{where="zero"}'] >= back["moe_zero_assignments"]
    assert snap["serving_latent_pool_bytes"] == 4 * 25 * 8 * 128 * 4
    assert "serving_state_bytes" not in snap
    # the strict loop: feed the argmax of the logits-returning step
    for uid, prompt in prompts.items():
        toks = list(prompt)
        for _ in range(5):
            rows, _, _ = paged(served, {uid: toks}, {uid: len(toks)})
            toks.append(int(np.argmax(rows[uid][0])))
        assert got[uid] == toks[len(prompt):], uid


# ---- the configuration's file and the benchmark's arithmetic ----------

@pytest.fixture(scope="module")
def d4():
    from benchmarks.lib.drivers import serve_latent_share as D
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "longcat-flash-d4.json")) as f:
        config = json.load(f)
    return config, D.preset_config(config)


def test_configuration_file_says_what_the_shapes_say(d4):
    from benchmarks.lib import arith_mla as A
    config, cfg = d4
    shapes = jax.eval_shape(lambda k: init_params(cfg, k)[0],
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    m = A.model(config)
    assert A.mla_params(m) == 90_570_752
    assert A.dense_mlp_params(m) == 226_492_416
    assert A.expert_params(m) == 37_748_736
    by_hand = (A.fixed_params(m) + 4 * 16 * A.expert_params(m)
               + 2 * 16384 * 6144)
    # the norms (four a layer of 6144, N_q and N_c a sublayer, the last)
    # and the routers' biases stand beside the matrices
    small = 8 * 2 * 6144 + 8 * (1536 + 512) + 6144 + 4 * 768
    assert count == by_hand + small
    assert abs(2 * count / 1e9 - 10.35) < 0.01
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "docqa-closed-48.json")) as f:
        sizes = json.load(f)["engine"]
    pool = 8 * (sizes["num_kv_blocks"] + 1) * sizes["kv_block_size"] \
        * 640 * 2
    assert abs(pool / 1e9 - 3.02) < 0.01
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size", "max_position_embeddings"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Chat")
    assert config["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert config[k] == v or (k in config["reduced"]
                                  and config["published"][k] == v), k


def test_arith_mla_counts_a_step_by_hand(d4):
    from benchmarks.lib import arith_mla as A
    m = A.model(d4[0])
    s = dict(n_tokens=48.0, n_seqs=48.0, latent_tokens=48 * 4000.0,
             latent_pairs=48 * 4000.0, moe_assignments=12.0,
             moe_assignments_made=48 * 12 * 4.0, moe_experts_touched=10.0,
             moe_zero_assignments=700.0)
    assert A.latent_bytes(m, s["latent_tokens"], 48.0) \
        == 8 * 1152 * (192_000 + 48)
    assert A.latent_flops(m, s["latent_pairs"]) \
        == 2.0 * 8 * 64 * 192_000 * 320
    assert A.expert_gemm_bytes(m, s) \
        == 2 * (10 * 37_748_736 + 12 * 3 * (6144 + 2048))
    fixed = 8 * (90_570_752 + 226_492_416) + 4 * 6144 * 768
    assert A.fixed_params(m) == fixed
    assert A.step_bytes(m, s) == (
        2 * (fixed + 6144 * 16384) + A.latent_bytes(m, 192_000.0, 48.0)
        + A.expert_gemm_bytes(m, s) + 48 * 6144 * 2)


def test_benchmark_json_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "serve-mla-docqa")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-d4", "docqa-closed-48", 1)
    mine = [m for m in bench["per_layer"]
            if "serve-mla-docqa" in m.get("workloads", ())]
    # (later cells are appended behind it in an entry's list)
    assert all(m["moves"] == "out_tokens_per_s" for m in mine)
    # BENCHMARK.json holds at most 128 per-layer metrics and held 127:
    # ONE entry is new (the whole step's roofline share); the cell joins
    # the lists of fourteen accepted entries whose readers take nothing
    # from a configuration's name
    assert len(bench["per_layer"]) == 128
    new = [m["name"] for m in mine if m["workloads"] == ["serve-mla-docqa"]]
    assert new == ["docqa_step_roofline"]
    names = {m["name"] for m in mine}
    assert names == {
        "docqa_step_roofline", "latent_attn_share", "moe_expert_gemm_share",
        "moe_route_share", "moe_expert_load_max_over_mean",
        "moe.serve_step_p50_ms", "moe.serve_host_ms_per_step",
        "moe.serve_window_compiles", "moe.serve_step_retries",
        "moe.itl_p95_ms", "moe.itl_p99_ms", "moe.serve_idle_share",
        "moe.sampler_share", "batch_tokens_per_step", "serve_hbm_peak_gb"}
    from benchmarks.lib.common import reader_path
    assert all(os.path.exists(reader_path("layer_metrics", n))
               for n in names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "serve-mla-docqa" in e2e["out_tokens_per_s"]["workloads"]
    assert [w["name"] for w in bench["workloads"]].index(
        "serve-mla-docqa") == 8
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
