"""The served step runs at the rows it holds (docs/SERVING.md "The rows
of a step"): two compiled row counts, the decode rows and the token
budget (``ragged/state.step_rows``), a step at the smaller where it
holds what the scheduler gave it, both rungs of a step function
compiled when the function is built.  Padding rows hold no token, so
the ladder may change no token: an engine with two rungs against one
whose bottom rung reaches its budget, on a dense, a sparse-expert and a
recurrent toy model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.inference.ragged.state import step_rows
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, init_params
from tests.test_inference import backend_compiles

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
BUDGET = 320                      # rungs 128 and 320 under 128 sequences
LENGTHS = (310, 5, 150, 40, 9)    # 514 prompt tokens: 320, 194, then decode


@pytest.mark.parametrize("max_seqs,n_verify,budget,want", [
    (64, 1, 512, (128, 512)),               # four of the serving cells
    (128, 1, 512, (128, 512)),              # serve-ssm-chat
    (8, 1, 256, (128, 256)),                # the default engine
    (4, 1, 32, (32,)), (8, 1, 64, (64,)), (1, 1, 1, (1,)),
    (8, 1, 128, (128,)), (8, 1, 129, (128, 129)),
    (129, 1, 512, (256, 512)), (200, 1, 2048, (256, 2048)),
    (64, 5, 512, (384, 512)),               # verify windows of five tokens
    (64, 8, 512, (512,)), (64, 9, 512, (512,)), (8, 0, 256, (128, 256)),
])
def test_ladder_rule(max_seqs, n_verify, budget, want):
    ladder = step_rows(max_seqs, n_verify, budget)
    assert ladder == want
    # smallest first, the budget on top, and the bottom rung holds every
    # step without a prompt chunk unless the budget itself is smaller
    assert list(ladder) == sorted(set(ladder)) and ladder[-1] == budget
    assert ladder[0] >= min(budget, max_seqs * max(1, n_verify))
    assert len(ladder) == 1 or ladder[0] % 128 == 0


def toy(kind):
    name, over = {
        "dense": ("llama-tiny", dict(vocab_size=128, num_layers=2,
                                     d_model=64, num_heads=4,
                                     num_kv_heads=2, d_ff=128)),
        "moe": ("olmoe-tiny", {}),
        # (chunks of 32 tokens: the mixer unrolls over its chunk table,
        # 14 rows at the top rung and not 44)
        "recurrent": ("falcon-h1-tiny", dict(ssm_chunk=32)),
    }[kind]
    cfg = build_config(name, max_seq_len=512, **over)
    params, axes = init_params(cfg, jax.random.PRNGKey(5))
    return Model.from_params(cfg, params, param_axes=axes)


def engine(model, **over):
    kw = dict(token_budget=BUDGET, max_seqs=8, kv_block_size=64,
              num_kv_blocks=32, max_seq_len=512, attn_impl="xla",
              param_dtype=jnp.float32, kv_dtype=jnp.float32)
    kw.update(over)
    return InferenceEngine(model, InferenceConfig(**kw))


def prompts(model, lengths=LENGTHS, seed=0):
    rng = np.random.default_rng(seed)
    return {10 + i: rng.integers(1, model.config.vocab_size, n).tolist()
            for i, n in enumerate(lengths)}


def by_rung(eng):
    v = eng.metrics_snapshot().get("serving_step_rows_total")
    return {int(k.split('"')[1]): int(n) for k, n in v.items()} \
        if isinstance(v, dict) else {}


@pytest.mark.parametrize("kind", ["dense", "moe", "recurrent"])
def test_streams_equal_whatever_the_ladder(kind):
    model = toy(kind)
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    ladder = engine(model)
    assert ladder._step_rows == (128, BUDGET)
    got = ladder.generate(prompts(model), sp)
    rungs = by_rung(ladder)
    # the prompts' two steps at the budget, the decode steps below it
    assert set(rungs) == {128, BUDGET} and rungs[BUDGET] == 2, rungs
    # both rungs of a step function are compiled with the function
    assert ladder.timings["compiles"] == 2 * len(ladder._pstep_fns)
    one = engine(model, max_seqs=BUDGET)
    assert one._step_rows == (BUDGET,)
    want = one.generate(prompts(model), sp)
    assert by_rung(one) == {BUDGET: sum(rungs.values())}
    assert one.timings["compiles"] == len(one._pstep_fns)
    assert got == want


def test_no_rung_compiles_after_its_step_function_is_built():
    """One step function (the Pallas kernel's grid follows the batch),
    two executables, both built by the first launch: the steps that
    follow cross the rungs and compile nothing."""
    model = toy("dense")
    eng = engine(model, attn_impl="pallas")
    ps = prompts(model)
    eng.put(11, ps[11])
    out = dict(eng.step(sampling=GREEDY))
    assert eng.timings["compiles"] == 2 and by_rung(eng) == {128: 1}
    assert sorted(eng.serving_programs) == [
        (r, eng.max_blocks_per_seq, GREEDY.sampler_key)
        for r in (128, BUDGET)]
    with backend_compiles() as compiles:
        for uid in (10, 12, 13, 14):
            eng.put(uid, ps[uid])
        for _ in range(6):
            for u, t in out.items():
                eng.put(u, [t])
            out = dict(eng.step(sampling=GREEDY))
    assert set(by_rung(eng)) == {128, BUDGET}
    assert not compiles and eng.timings["compiles"] == 2
    assert eng.timings["compile_retraces"] == 0
    # a rung is cold until its own first call has returned
    assert eng._warm_keys == {("p", k) for k in eng.serving_programs}


def test_top_rung_is_the_program_of_a_step_padded_to_the_budget():
    """What the engine launches at its top rung is, text for text, what
    ``build_batch(sched, token_budget)`` and ``_build_pstep`` lower to:
    the program every step ran before there was a ladder."""
    model = toy("dense")
    eng = engine(model)
    lowered = {}
    compile_rungs = eng._compile_rungs

    def keep(key, step_fn, batch, prev, rng):
        lowered[batch.token_ids.shape[0]] = step_fn.lower(
            eng.params, eng._quant, eng.state.kv, batch, prev,
            rng).as_text()
        compile_rungs(key, step_fn, batch, prev, rng)

    eng._compile_rungs = keep
    eng.put(1, prompts(model)[10])
    eng.step(sampling=GREEDY)
    assert list(lowered) == [BUDGET]
    plain = engine(model)
    plain.put(1, prompts(model)[10])
    sched = plain._schedule()
    batch = plain._stage(plain.state.build_batch(sched, BUDGET))
    text = plain._build_pstep(
        next(iter(eng._pstep_fns))[0], GREEDY).lower(
        plain.params, plain._quant, plain.state.kv, batch,
        plain._zero_toks, plain._zero_key).as_text()
    assert lowered[BUDGET] == text


def test_series_and_spans_read_what_the_schedule_says():
    model = toy("dense")
    eng = engine(model, trace=True)
    seen = []
    schedule = eng._schedule

    def noted(*a, **k):
        sched = schedule(*a, **k)
        if sched:
            seen.append(sum(len(t) for _, t in sched))
        return sched

    eng._schedule = noted
    assert "serving_step_row_fill" not in eng.metrics_snapshot()
    eng.generate(prompts(model), SamplingParams(temperature=0.0,
                                                max_new_tokens=5))
    rows = [next(r for r in eng._step_rows if r >= n) for n in seen]
    # the prompts' 514 tokens: a full step, the rest beside the first
    # decode rows, then decode rows alone
    assert seen[0] == BUDGET and 128 < seen[1] < BUDGET and seen[2] <= 8
    assert rows[:3] == [BUDGET, BUDGET, 128]
    assert by_rung(eng) == {r: rows.count(r) for r in set(rows)}
    snap = eng.metrics_snapshot()
    assert snap["serving_step_row_fill"] == pytest.approx(
        sum(seen) / sum(rows))
    assert snap["serving_steps_total"] == len(seen)
    for name in ("ds.serve.stage", "ds.serve.dispatch", "ds.serve.compile"):
        args = [e["args"] for e in eng.tracer.events() if e["name"] == name]
        if name == "ds.serve.stage":
            assert [(a["n_tokens"], a["rows"]) for a in args] \
                == list(zip(seen, rows))
        assert all(a["rows"] >= a["n_tokens"] for a in args)
    eng.reset_metrics()
    assert "serving_step_row_fill" not in eng.metrics_snapshot()
    assert by_rung(eng) == {}


def test_transient_fault_is_retried_at_another_rung():
    """A decode-only step (the bottom rung) fails; its sequences are
    re-queued from position zero, so the retry is a prefill at a higher
    rung, and the streams are the unfaulted ones."""
    model = toy("dense")
    ps = prompts(model, lengths=(150, 40, 9))
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    want = engine(model).generate(ps, sp)
    # (with the prefix cache the chains' blocks would be found again and
    # the retry would be a few tokens at the bottom rung)
    eng = engine(model, prefix_cache="off")
    for u, p in ps.items():
        eng.put(u, p, max_new_tokens=8)
    got = {u: [] for u in ps}
    armed, before = [True], {}
    run = eng.failures.run

    def guarded(fn, **kw):
        if armed and kw.get("site") == "dispatch" \
                and eng.timings["generated_tokens"] >= 6:
            armed.clear()
            before.update(by_rung(eng))
            eng.failures.inject("transient")
        return run(fn, **kw)

    eng.failures.run = guarded
    for _ in range(200):
        for u, t in eng.step(sampling=GREEDY).items():
            got[u].append(t)
        if all(len(g) >= 8 for g in got.values()):
            break
    assert not armed and eng.timings["step_retries"] >= 1
    # before the fault: the prompts' one step at the top rung, then
    # decode steps; after it the chains come back as prompts: another
    # step at the top rung
    assert before[BUDGET] == 1 and before[128] >= 1
    after = by_rung(eng)
    assert after[BUDGET] == 2 and after[128] > before[128]
    assert {u: g[:8] for u, g in got.items()} \
        == {u: list(w[:8]) for u, w in want.items()}
