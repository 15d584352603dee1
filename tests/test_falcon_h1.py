"""A Mamba-2 mixer beside grouped-query attention in every block
(``falcon-h1-tiny``), against the benchmark's own plain reference
(``benchmarks/reference/falcon-h1-34b-d6.py``, imported by path, which
writes the recurrence token by token): through ``apply``; through the
engine's chunked prefill and decode on both attention formulations;
steps that hold decode rows and several prefill runs; a slot that
changes hands; ``hold`` and resume with a row launched ahead; a
transient failure's re-queue; what the engine refuses for such a model;
every wrong forward the reference knows; the older configurations'
programs."""

import contextlib
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.inference.overload import OverloadConfig
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, apply, init_params
from tests.serving_ref import strict_generate

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # float32 system against the float32 reference
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)


def _load(path, name):
    from benchmarks.lib.common import load_module
    return load_module(os.path.join(ROOT, path), name)


@pytest.fixture(scope="module")
def ref():
    return _load("benchmarks/reference/falcon-h1-34b-d6.py", "falcon_h1_ref")


@pytest.fixture(scope="module")
def tiny():
    cfg = build_config("falcon-h1-tiny")
    axes = {}

    def init(key):          # one compiled call, not an operation at a time
        params, axes["axes"] = init_params(cfg, key)
        return params

    return cfg, jax.jit(init)(jax.random.PRNGKey(3)), axes["axes"]


def ref_config(cfg):
    """What the reference reads of a configuration file, for ``cfg``."""
    return dict(
        num_hidden_layers=cfg.num_layers, rms_norm_eps=cfg.eps,
        rope_theta=cfg.rope_theta, key_multiplier=cfg.key_scale,
        attention_in_multiplier=cfg.attn_in_scale,
        attention_out_multiplier=cfg.attn_out_scale,
        ssm_in_multiplier=cfg.ssm_in_scale,
        ssm_out_multiplier=cfg.ssm_out_scale,
        ssm_multipliers=list(cfg.ssm_col_scales),
        mlp_multipliers=[cfg.mlp_gate_scale, cfg.mlp_out_scale],
        embedding_multiplier=cfg.embed_scale,
        lm_head_multiplier=cfg.head_scale,
        mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim,
        mamba_n_groups=cfg.ssm_groups, mamba_d_state=cfg.ssm_state,
        mamba_d_conv=cfg.ssm_conv, mamba_d_ssm=cfg.ssm_d)


def rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def engine(tiny, **over):
    cfg, params, axes = tiny
    kw = dict(token_budget=20, max_seqs=4, kv_block_size=8,
              num_kv_blocks=64, max_seq_len=128, attn_impl="xla",
              param_dtype=jnp.float32, kv_dtype=jnp.float32)
    kw.update(over)
    return InferenceEngine(Model.from_params(cfg, params, param_axes=axes),
                           InferenceConfig(**kw))


@contextlib.contextmanager
def state_kernels():
    """A step traced in here takes the recurrent mixers' Pallas kernels
    (the one-token update's and the chunked form's) as it does on a TPU;
    off one they run interpreted."""
    from deepspeed_tpu.inference import model
    real = model._ssm_mixer
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(model, "_ssm_mixer",
                   lambda *a, kernel=False, **k: real(*a, kernel=True, **k))
        yield


def paged_logits(eng, seqs, n_prompt):
    """Each sequence's prompt through the engine's ordinary chunks, then
    the rest fed a token at a time → ({uid: [rows]}, row i the logits
    after token ``n_prompt - 1 + i``; the schedules of the steps)."""
    step = eng._build_step(eng.max_blocks_per_seq)
    rows = {u: [] for u in seqs}
    fed = dict(n_prompt)
    for u, s in seqs.items():
        eng.put(u, list(s[:n_prompt[u]]))
    scheds = []
    while True:
        sched = eng._schedule()
        if not sched:
            return rows, scheds
        scheds.append([(u, len(t)) for u, t in sched])
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


def test_tiny_preset_is_the_block(tiny):
    cfg, params, _ = tiny
    assert cfg.has_ssm and not cfg.plain_stack
    assert cfg.layer_plan == (0, 4, 0) and cfg.layer_kinds == ("hybrid",) * 4
    assert cfg.head_dim == 32 != cfg.d_model // cfg.num_heads
    assert cfg.num_heads // cfg.num_kv_heads == 2
    sd = cfg.ssm_dims
    assert (sd.heads, sd.head_dim, sd.groups, sd.state, sd.chunk) \
        == (4, 16, 2, 16, 8)
    assert params["blocks"]["ssm"]["w_in"].shape == (4, 64, sd.in_proj)
    assert sd.in_proj == 64 + (64 + 2 * 2 * 16) + 4
    # all fourteen multipliers away from one
    fourteen = (cfg.embed_scale, cfg.head_scale, cfg.attn_in_scale,
                cfg.attn_out_scale, cfg.key_scale, cfg.ssm_in_scale,
                cfg.ssm_out_scale, cfg.mlp_gate_scale, cfg.mlp_out_scale,
                *cfg.ssm_col_scales)
    assert len(fourteen) == 14 and all(m != 1.0 for m in fourteen)
    # seeded as Mamba-2 starts them: A in 1..16, dt in 0.001..0.1
    a = np.exp(np.asarray(params["blocks"]["ssm"]["A_log"]))
    dt = np.asarray(jax.nn.softplus(params["blocks"]["ssm"]["dt_bias"]))
    assert 1 <= a.min() and a.max() <= 16
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 0.1 * 1.01


def test_published_preset_is_the_catalog_entry():
    cfg = build_config("falcon-h1-34b")
    sd = cfg.ssm_dims
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) \
        == (72, 5120, 20, 4, 128, 21504, 261120)
    assert sd.in_proj == 9248 and sd.conv_channels == 5120
    assert cfg.layer_plan == (0, 72, 0)


@pytest.mark.parametrize("n", [37, 8, 5])
def test_apply_agrees_with_the_reference(tiny, ref, n):
    """Lengths that the mixer's chunk of 8 divides and does not."""
    cfg, params, _ = tiny
    ids = np.random.default_rng(n).integers(0, cfg.vocab_size, n)
    want = np.asarray(ref.logits(params, ids, ref_config(cfg)))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, i: apply(cfg, p, i))(
            params, jnp.asarray(ids)[None]))[0]
    assert rel(got, want) < TOL


@pytest.fixture(scope="module")
def seqs(tiny):
    cfg = tiny[0]
    rng = np.random.default_rng(0)
    # prompts that neither the step's 20 tokens nor the mixer's chunk of
    # 8 divide, then 8 fed tokens
    lens = {1: 53, 2: 31, 3: 9}
    return ({u: rng.integers(0, cfg.vocab_size, n + 8).tolist()
             for u, n in lens.items()}, lens)


@pytest.fixture(scope="module")
def system_rows(tiny, seqs):
    with jax.default_matmul_precision("highest"):
        rows = {impl: paged_logits(engine(tiny, attn_impl=impl), *seqs)
                for impl in ("xla", "pallas")}
        with state_kernels():
            rows["state kernels"] = paged_logits(engine(tiny), *seqs)
    return rows


@pytest.mark.parametrize("impl", ["xla", "pallas", "state kernels"])
def test_chunked_prefill_and_decode_agree_with_the_reference(
        tiny, ref, seqs, system_rows, impl):
    cfg, params, _ = tiny
    tokens, n_prompt = seqs
    rows, scheds = system_rows[impl]
    for u, s in tokens.items():
        want = np.asarray(ref.logits(params, np.asarray(s), ref_config(cfg),
                                     last=9))
        got = np.stack(rows[u])
        assert got.shape == want.shape
        assert rel(got, want) < TOL, (impl, u)
    # a step that held decode rows of several sequences and two prefill
    # runs at once is among them, and a run that starts past position 0
    mixed = [s for s in scheds
             if sum(n == 1 for _, n in s) >= 2 and sum(n > 1 for _, n in s)]
    assert any(sum(n > 1 for _, n in s) >= 2 for s in scheds)
    assert mixed or any(len(s) >= 3 for s in scheds)


def test_step_of_decode_rows_and_two_prefill_runs(tiny, ref):
    """Two sequences decoding while two prompts prefill in one step."""
    cfg, params, _ = tiny
    rng = np.random.default_rng(5)
    toks = {u: rng.integers(0, cfg.vocab_size, n).tolist()
            for u, n in ((1, 12), (2, 7), (3, 6), (4, 5))}
    eng = engine(tiny)
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
    # the control: the first sequence's state kept would not pass
    stale = np.asarray(ref.logits(
        params, np.asarray(second[2]), ref_config(cfg), last=9,
        wrong="stale_state", before=np.asarray(first[1])))
    assert rel(stale, want) > 100 * TOL


def served_engine(tiny, **kw):
    kw.setdefault("overload", OverloadConfig(prefill_chunk=16))
    return engine(tiny, token_budget=32, **kw)


def next_logits(eng, uid, token):
    """The logits after feeding ``token`` to ``uid`` (strict step)."""
    step = eng._build_step(eng.max_blocks_per_seq)
    eng.put(uid, [token])
    batch = eng._stage(eng.state.build_batch(eng._schedule(), 32))
    logits, eng.state.kv = step(eng.params, eng._quant, eng.state.kv, batch)
    return np.asarray(logits[eng.state.slot(uid)])


def test_hold_and_resume_with_a_row_launched_ahead(tiny):
    """The row launched ahead and thrown away has moved the state one
    token: fed again it must leave the state where one pass would."""
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, tiny[0].vocab_size, 21).tolist()
    plain = served_engine(tiny)
    plain.put(7, prompt, max_new_tokens=40)
    want = []
    while len(want) < 9:
        want += list(plain.step(sampling=GREEDY).values())
    eng = served_engine(tiny)
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
    # same stream, same state: settle both, then the logits of one more
    # fed token and the state rows themselves
    for e in (plain, eng):
        e.hold(7)
        while e.in_flight:
            e.step(sampling=GREEDY)
    assert plain.state.seqs[7].seen_tokens == eng.state.seqs[7].seen_tokens
    a, b = (e.state.kv for e in (plain, eng))
    sa, sb = plain.state.slot(7), eng.state.slot(7)
    np.testing.assert_allclose(np.asarray(b["ssm"][:, sb]),
                               np.asarray(a["ssm"][:, sa]), atol=1e-5)
    # each engine's row launched ahead was thrown away by the hold above:
    # both replay it here
    tok = plain.state.seqs[7].tokens[-1]
    np.testing.assert_allclose(next_logits(eng, 7, tok),
                               next_logits(plain, 7, tok), atol=1e-4)


def test_a_transient_failure_requeues_from_position_zero(tiny):
    rng = np.random.default_rng(11)
    prompts = {u: rng.integers(0, tiny[0].vocab_size, n).tolist()
               for u, n in ((1, 19), (2, 7))}
    want = strict_generate(served_engine(tiny), prompts,
                           SamplingParams(max_new_tokens=10))
    eng = served_engine(tiny)
    for u, p in prompts.items():
        eng.put(u, p, max_new_tokens=10)
    got = {u: [] for u in prompts}
    armed = [True]
    run = eng.failures.run

    def guarded(fn, **kw):
        if armed and kw.get("site") == "collect" \
                and eng.timings["generated_tokens"] >= 6:
            armed.clear()
            eng.failures.inject("transient")
        return run(fn, **kw)

    eng.failures.run = guarded
    for _ in range(200):
        for u, t in eng.step(sampling=GREEDY).items():
            got[u].append(t)
        if all(len(g) >= 10 for g in got.values()):
            break
    assert not armed and eng.timings["step_retries"] >= 1
    assert {u: g[:10] for u, g in got.items()} \
        == {u: list(w[:10]) for u, w in want.items()}


@pytest.mark.parametrize("option,value", [
    ("prefix_cache", "on"), ("spec_decode", "on"), ("kv_tier", "on")])
def test_engine_refuses_by_name_what_cannot_hold_a_state(tiny, option,
                                                         value):
    with pytest.raises(ValueError, match=option):
        engine(tiny, **{option: value})


def test_auto_resolves_to_off_and_the_rest_says_one_block_type(tiny):
    eng = engine(tiny)                  # prefix_cache, spec_decode: auto
    assert not eng.state.prefix_cache and eng._spec is None
    assert set(eng.state.kv) == {"kv", "ssm", "conv"}
    sd = tiny[0].ssm_dims
    assert eng.state.kv["ssm"].shape == (4, 5, sd.heads, sd.head_dim,
                                         sd.state)
    assert eng.state.kv["conv"].shape == (4, 5, sd.conv, sd.conv_channels)
    with pytest.raises(NotImplementedError, match="one block type"):
        engine(tiny, weight_quant="int8").generate(
            {1: [1, 2, 3]}, SamplingParams(max_new_tokens=2))


WRONG_AT = {"state_reset": 53, "tail_cut": 40}


def test_every_wrong_forward_fails_the_tolerance(tiny, ref, seqs,
                                                 system_rows):
    """What the chip's comparison has to tell, told here in float32: the
    true forward passes ``TOL`` above, each control reads far over it."""
    cfg, params, _ = tiny
    tokens, n_prompt = seqs
    got = np.stack(system_rows["xla"][0][1])
    s = np.asarray(tokens[1])
    assert sorted(ref.WRONG) == sorted(
        ["no_mixer", "state_reset", "tail_cut", "stale_state",
         "no_col_scales", "no_key_scale", "norm_ungrouped", "int8"])
    for wrong in ref.WRONG:
        bad = np.asarray(ref.logits(
            params, s, ref_config(cfg), wrong=wrong, last=9,
            at=WRONG_AT.get(wrong), before=np.asarray(tokens[2])))
        assert rel(got, bad) > 50 * TOL, wrong


def test_stage_span_counts_and_gauges(tiny):
    eng = engine(tiny, trace=True, token_budget=32)
    rng = np.random.default_rng(2)
    eng.put(3, [5])
    eng.put(1, rng.integers(0, 1024, 9).tolist())
    eng.put(2, rng.integers(0, 1024, 40).tolist())
    out = eng.step(sampling=GREEDY)
    stage = [e["args"] for e in eng.tracer.events()
             if e["name"] == "ds.serve.stage"][-1]
    # uid 3's one token is a run that starts at position 0 and advances
    # its state by one; uid 2 takes what the budget leaves
    assert (stage["state_rows"], stage["scan_tokens"],
            stage["state_starts"], stage["state_replays"]) == (1, 31, 3, 0)
    # the chunks of the table that hold rows: 9 and 22 tokens by eights
    assert stage["scan_chunks"] == 2 + 3
    for u, t in out.items():
        eng.put(u, [t])
    eng.step(sampling=GREEDY)
    stage = [e["args"] for e in eng.tracer.events()
             if e["name"] == "ds.serve.stage"][-1]
    assert (stage["state_rows"], stage["scan_tokens"],
            stage["state_starts"], stage["scan_chunks"]) == (2, 18, 0, 3)
    snap = eng.metrics.snapshot()
    upd = snap["serving_state_updates_total"]
    assert upd['{kind="decode"}'] == 3 and upd['{kind="scan"}'] == 49
    assert snap["serving_scan_chunks_total"] == 8
    assert snap["serving_scan_chunk_fill"] == 49 / (8 * 8)
    assert snap["serving_state_slots_in_use"] == 3
    assert snap["serving_state_bytes"] \
        == 3 * eng._recurrent.bytes_per_seq(4)


def test_scheduler_bounds_the_runs_of_several_tokens(tiny):
    eng = engine(tiny, max_seqs=8, token_budget=32)
    for u in range(1, 8):
        eng.put(u, [3, 4])
    sched = eng._schedule()
    assert len(sched) == eng._recurrent.scan_runs == 4
    eng.state.build_batch(sched, 32)
    assert len(eng._schedule()) == 3


def test_chunk_table_follows_the_rung(tiny):
    """A served step runs at the smallest compiled row count that holds
    it (``ragged/state.step_rows``), and the table of a step's chunks is
    as long as that many rows can need: a prompt of 150 tokens beside a
    stream's row rides the top rung (160 rows, 24 chunks of 8), the
    decode rows the bottom one (128, 20), and the streams are those of
    an engine whose one rung is its budget."""
    rng = np.random.default_rng(4)
    prompts = {1: rng.integers(0, 1024, 150).tolist(),
               2: rng.integers(0, 1024, 12).tolist()}
    sp = SamplingParams(temperature=0.0, max_new_tokens=5)

    def run(max_seqs):
        eng = engine(tiny, token_budget=160, max_seqs=max_seqs,
                     kv_block_size=64, num_kv_blocks=16, max_seq_len=256)
        staged, stage = [], eng._stage

        def noted(tree):
            if hasattr(tree, "rec"):
                staged.append((tree.token_ids.shape[0],
                               tree.rec.chunks.shape[0]))
            return stage(tree)

        eng._stage = noted
        return eng, eng.generate(prompts, sp), set(staged)

    eng, got, staged = run(4)
    rc = eng._recurrent
    assert eng._step_rows == (128, 160)
    assert staged == {(160, 24), (128, 20)}
    assert all(eng.state.blank_batch(r).rec.chunks.shape
               == (rc.n_chunks(r), 5) for r in eng._step_rows)
    one, want, staged = run(160)
    assert one._step_rows == (160,) and staged == {(160, 24)}
    assert got == want


# The five configurations the benchmark had before this one, at the tiny
# sizes their files give for a rehearsal: the lowered programs of the
# training forward and of the serving step, hashed on the parent commit
# (1ef15cd) by this very function; the serving steps' again by PR 45,
# which folds the attention projections they read (the training
# forwards' are as they were); the two expert configurations' serving
# steps again by PR 53, which orders the expert layer's assignments
# choice-major (``moe_serve``; no training forward here calls it, and
# the dense configurations' steps stand).  A change to one of these strings
# means that the configuration no longer compiles to the program it
# compiled to before: say so in CHANGES.md and regenerate
# (``python tests/test_falcon_h1.py``).
OLDER = {
    "pythia-1.4b-d6": ("dc336112cdc65385", "7d7425fdf3a94815"),
    "pythia-1.4b": ("dc336112cdc65385", "7d7425fdf3a94815"),
    "mistral-7b-d16": ("18980891e746029d", "2cbaad6d4c948a6a"),
    "olmoe-1b-7b-d10": ("bc09fa6a96eae42f", "a30a12d8dbb211b1"),
    "trinity-mini-d5": ("cf52af5ab71702e7", "97d30f20966e2341"),
}


def older_programs(name):
    """(sha256 of ``apply``'s lowered text, of the serving step's)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        sysm = json.load(f)["rehearse"]["system"]
    cfg = build_config(sysm["preset"], **sysm.get("overrides", {}))
    params = jax.eval_shape(lambda k: init_params(cfg, k)[0],
                            jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    fwd = jax.jit(lambda p, i: apply(cfg, p, i)).lower(params, ids)
    real, axes = init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(
        Model.from_params(cfg, real, param_axes=axes),
        InferenceConfig(token_budget=16, max_seqs=4, kv_block_size=8,
                        num_kv_blocks=16, max_seq_len=64, attn_impl="xla"))
    eng.put(1, [1, 2, 3])
    batch = eng.state.build_batch(eng._schedule(), 16)
    pstep = eng._build_pstep(None, GREEDY).lower(
        eng.params, eng._quant, eng.state.kv, batch, eng._zero_toks,
        eng._zero_key)
    return tuple(hashlib.sha256(t.as_text().encode()).hexdigest()[:16]
                 for t in (fwd, pstep))


@pytest.mark.parametrize("name", sorted(OLDER))
def test_older_configurations_compile_to_the_programs_they_did(name):
    assert older_programs(name) == OLDER[name]


if __name__ == "__main__":
    for n in sorted(OLDER):
        print(f'    "{n}": {older_programs(n)!r},')
