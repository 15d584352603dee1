"""``generate()`` is a loop over the served path: ``put(max_new_tokens=)``
for every prompt, then ``step()`` one launch ahead until each request
of the call is closed (docs/SERVING.md "The served loop").  Its answer
is the strict caller-fed loop's (``tests/serving_ref.py``), token for
token, for every layer kind the engine serves; a stop leaves the stream
exactly at the stop; what the engine closes itself leaves the loop; a
warm engine compiles nothing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.inference.overload import OverloadConfig
from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, init_params
from tests.serving_ref import strict_generate
from tests.test_inference import backend_compiles

# the five layer kinds the engine serves: one block type, routed
# experts, a window-and-full pattern (with experts), a state-space mixer
# beside attention, the delta rule with latent attention
# (each at the least depth that holds its pattern: the test is of the
# loop, and the step compiles a layer's cost a layer)
KINDS = {
    "plain": ("llama-tiny", dict(vocab_size=1024, num_layers=2, d_model=64,
                                 num_heads=4, num_kv_heads=2, d_ff=128,
                                 max_seq_len=256)),
    "experts": ("olmoe-tiny", {}),
    "window": ("trinity-tiny", dict(num_layers=5)),  # dense + one period
    "ssm": ("falcon-h1-tiny", dict(num_layers=2)),
    "kda-mla": ("ling-tiny", dict(num_layers=4)),    # dense + one period
}


def discarded(eng):
    """{label: rows thrown away}; a counter never bumped reads 0."""
    return eng.metrics_snapshot().get(
        "serving_ahead_discarded_rows_total") or {}


@pytest.fixture(scope="module")
def kind_engines():
    """kind -> (engine, prompts), built once a kind: both loops flush
    everything they put and the prefix cache is off, so the engine is
    as new for each, and one context bucket (a block holds a whole
    context) makes it one compiled step a sampler."""
    built = {}

    def get(kind):
        if kind not in built:
            preset, over = KINDS[kind]
            cfg = build_config(preset, **over)
            params, axes = init_params(cfg, jax.random.PRNGKey(3))
            model = Model.from_params(cfg, params, param_axes=axes)
            icfg = InferenceConfig(
                token_budget=32, max_seqs=4, kv_block_size=64,
                num_kv_blocks=8, max_seq_len=64, prefix_cache="off",
                param_dtype=jnp.float32, kv_dtype=jnp.float32,
                overload=OverloadConfig(prefill_chunk=16))
            rng = np.random.default_rng(5)
            # the longest prompt takes several chunks and, with what is
            # generated, passes trinity-tiny's window of 16 twice
            prompts = {u: rng.integers(1, cfg.vocab_size, n).tolist()
                       for u, n in ((1, 37), (2, 5), (3, 18))}
            built[kind] = InferenceEngine(model, icfg), prompts
        return built[kind]

    return get


@pytest.mark.parametrize("mode", ["greedy", "seeded", "stop"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_generate_equals_the_strict_loop(kind_engines, kind, mode):
    eng, prompts = kind_engines(kind)
    rng = None
    sp = SamplingParams(max_new_tokens=12)
    if mode == "seeded":
        sp = SamplingParams(temperature=0.8, top_k=20, max_new_tokens=12)
        rng = jax.random.PRNGKey(11)
    elif mode == "stop":
        # the sixth token of the longest stream: a launch is in flight
        # behind the step that samples it
        base = strict_generate(eng, prompts, sp)
        sp = SamplingParams(max_new_tokens=12, stop_token=base[1][5])
    want = strict_generate(eng, prompts, sp, rng=rng)
    eng.reset_metrics()
    got = eng.generate({u: list(p) for u, p in prompts.items()}, sp,
                       rng=rng)
    assert got == want
    assert all(len(g) <= 12 for g in got.values())
    if mode == "stop":
        assert got[1][-1] == sp.stop_token and len(got[1]) <= 6
        # the token launched behind the stop was thrown away at its read
        assert discarded(eng).get('{reason="finished"}', 0) > 0
    snap = eng.metrics_snapshot()
    assert snap["serving_steps_ahead_total"] > 0
    assert "caller_fed" not in str(snap["serving_strict_steps_total"])
    # everything rolled up: nothing in flight, no marker, slot or block
    assert not eng.in_flight and eng._fb_step == {}
    assert not eng.state.seqs and not eng._cont
    assert eng.state.allocator.free_blocks \
        == eng.state.allocator.total_blocks


def tiny_model(name="llama-tiny", **over):
    kw = dict(vocab_size=128, num_layers=2, d_model=64, num_heads=4,
              num_kv_heads=2, d_ff=128, max_seq_len=128)
    kw.update(over)
    return build_model(name, **kw)


def engine(model, **over):
    # one context bucket: a block holds a whole context
    kw = dict(token_budget=32, max_seqs=4, kv_block_size=64,
              num_kv_blocks=16, max_seq_len=64, kv_dtype=jnp.float32,
              param_dtype=jnp.float32)
    kw.update(over)
    return InferenceEngine(model, InferenceConfig(**kw))


PROMPTS = {0: [5, 17, 99, 3, 42], 1: [7, 7, 1]}
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)


@pytest.mark.parametrize("name,model_kw,engine_kw", [
    ("bloom-tiny", dict(num_kv_heads=4), {}),
    ("llama-tiny", {}, dict(kv_quant="int8")),
    ("gpt2", dict(num_kv_heads=4, d_ff=256), {}),
    ("mixtral-tiny", dict(num_experts=4), {})],
    ids=["alibi", "int8-kv", "learned-positions", "mixtral-experts"])
def test_cache_and_position_variants(name, model_kw, engine_kw):
    """ALiBi slopes, a quantized cache, learned positions and capacity-
    free experts: the strict loop and the loop that runs ahead read the
    same cache through the one served step."""
    m = tiny_model(name, **model_kw)
    want = strict_generate(engine(m, **engine_kw), PROMPTS, GREEDY)
    got = engine(m, **engine_kw).generate(
        {u: list(p) for u, p in PROMPTS.items()}, GREEDY)
    assert got == want and all(len(g) == 8 for g in got.values())


@pytest.mark.parametrize("seed", [None, 7], ids=["greedy", "seeded"])
def test_stop_with_a_launch_in_flight_leaves_the_stream_at_the_stop(seed):
    """Step N samples the stop while N+1 is launched: the stream, the
    context the engine counted and ``generated_tokens`` end exactly at
    the stop, and N+1's row for it is thrown away, not emitted."""
    m = tiny_model()
    rng = None if seed is None else jax.random.PRNGKey(seed)
    sp = SamplingParams(temperature=0.0 if seed is None else 0.9,
                        max_new_tokens=20)
    base = engine(m).generate({0: list(PROMPTS[0])}, sp, rng=rng)[0]
    first = {}
    for i, t in enumerate(base):
        first.setdefault(t, i)
    stop, at = max(((t, i) for t, i in first.items() if i < 19),
                   key=lambda ti: ti[1])
    eng = engine(m)
    seen = []
    finish = eng._finish

    def spy(uid, status):
        if uid in eng.state.seqs:
            seen.append((status, eng.state.seqs[uid].seen_tokens,
                         list(eng.state.seqs[uid].tokens),
                         eng._ahead is not None and uid in eng._ahead.uids))
        finish(uid, status)

    eng._finish = spy
    sps = dataclasses.replace(sp, stop_token=stop)
    got = eng.generate({0: list(PROMPTS[0])}, sps, rng=rng)[0]
    assert got == base[:at + 1] and got[-1] == stop
    (status, seen_tokens, stream, row_ahead), = seen
    assert status == "finished" and stream == got
    # N+1 was in flight with a row of this stream when the stop was read
    # (the context counts that row: prompt + every token fed back)
    assert row_ahead and seen_tokens == len(PROMPTS[0]) + len(got)
    assert eng.timings["generated_tokens"] == len(got)
    assert discarded(eng) == {'{reason="finished"}': 1.0}
    assert not eng.in_flight
    rec, = eng.request_metrics()["requests"]
    assert rec["generated_tokens"] == len(got) and rec["finished"]


@pytest.mark.parametrize("max_new", [1, 2, 5, 7, 12])
def test_speculative_generate_keeps_every_window_token(max_new):
    """A verify window emits several tokens in one step: ``generate()``
    returns all of them (not the last of each step) and never more than
    ``max_new_tokens`` — a draft is capped by what the request may still
    emit, so the engine counts no token the caller does not get."""
    m = tiny_model()
    prompts = {1: [5, 6, 7, 8] * 6, 2: [9, 2, 9, 2, 9, 2, 44]}
    sp = SamplingParams(max_new_tokens=max_new)
    want = strict_generate(engine(m), prompts, sp)
    eng = engine(m, spec_decode="on", spec_max_draft=4)
    got = eng.generate({u: list(p) for u, p in prompts.items()}, sp)
    assert got == want
    assert all(len(g) == max_new for g in got.values())
    tm = eng.timings
    assert tm["generated_tokens"] == sum(len(g) for g in got.values())
    if max_new >= 12:
        # windows were accepted: fewer steps than tokens of a stream
        assert tm["spec_accepted_tokens"] > 0
        assert tm["steps"] < sum(len(g) for g in got.values())
    assert set(eng.metrics_snapshot()["serving_strict_steps_total"]) \
        == {'{reason="spec_decode"}'}


def test_one_token_is_not_speculated_past():
    """``max_new_tokens=1``: the prefill's sample is the last token, so
    nothing is launched behind it and nothing is thrown away."""
    eng = engine(tiny_model())
    got = eng.generate({u: list(p) for u, p in PROMPTS.items()},
                       SamplingParams(max_new_tokens=1))
    assert [len(g) for g in got.values()] == [1, 1]
    assert eng.timings["steps"] == 1 and eng.timings["generated_tokens"] == 2
    assert discarded(eng) == {} and not eng.in_flight


def test_a_prompt_shed_at_admission_keeps_its_empty_row():
    m = tiny_model()
    want = strict_generate(engine(m), PROMPTS, GREEDY)
    eng = engine(m, overload=OverloadConfig(max_queued_requests=2))
    prompts = dict(PROMPTS)
    prompts[2] = [9, 9, 9]
    got = eng.generate({u: list(p) for u, p in prompts.items()}, GREEDY)
    assert got == {**want, 2: []}
    assert eng.query(2)["status"] == "shed"


def test_a_deadline_that_expires_mid_call_ends_that_request_only():
    m = tiny_model()
    sp = SamplingParams(max_new_tokens=40)
    want = strict_generate(engine(m), PROMPTS, sp)
    eng = engine(m)
    # warm the step, so that the deadline times steps and no compile
    eng.generate({u: list(p) for u, p in PROMPTS.items()},
                 SamplingParams(max_new_tokens=2))
    put, step, expired = eng.put, eng._step, []

    def put_with_deadline(uid, tokens, **kw):
        if uid == 1:
            kw["deadline_ms"] = 1e6
        return put(uid, tokens, **kw)

    def step_then_expire(rng, sampling):
        out = step(rng, sampling)
        if eng.timings["generated_tokens"] >= 14 and not expired:
            # the deadline passes HERE, whatever the host's speed
            eng._meta[1].deadline_ms = 0.0
            expired.append(1)
        return out

    eng.reset_metrics()
    eng.put, eng._step = put_with_deadline, step_then_expire
    got = eng.generate({u: list(p) for u, p in PROMPTS.items()}, sp)
    assert got[0] == want[0]
    assert 0 < len(got[1]) < 40 and got[1] == want[1][:len(got[1])]
    assert eng.query(1)["status"] == "deadline_exceeded"
    assert eng.query(0)["status"] == "finished"
    assert not eng.in_flight and not eng.state.seqs


def test_a_request_put_by_another_caller_during_the_call():
    """Another caller's request rides the steps ``generate()`` runs: it
    is neither returned nor closed by the call, its tokens are on its
    stream, and its caller goes on reading ``step()`` afterwards — the
    launch left in flight at the call's end is handed over, not lost."""
    m = tiny_model()
    sp = SamplingParams(max_new_tokens=12)
    want = strict_generate(engine(m), {**PROMPTS, 9: [4, 4, 8, 1]},
                           SamplingParams(max_new_tokens=30))
    eng = engine(m)
    step, calls = eng._step, []

    def step_and_put(rng, sampling):
        calls.append(1)
        if len(calls) == 3:
            assert eng.put(9, [4, 4, 8, 1], max_new_tokens=30)
        return step(rng, sampling)

    eng._step = step_and_put
    got = eng.generate({u: list(p) for u, p in PROMPTS.items()}, sp)
    eng._step = step
    assert got == {u: want[u][:12] for u in PROMPTS}
    assert 9 not in got and eng.query(9)["status"] == "running"
    assert eng._ahead is None                 # settled at the call's end
    at_return = list(eng.query(9)["generated"])
    assert 0 < len(at_return) < 30 and at_return == want[9][:len(at_return)]
    held = len(eng._held.get(9, ()))          # read back, not handed over yet
    after = []
    for _ in range(100):
        out = eng.step(sampling=sp)
        if 9 in out:
            after.append(out[9])
        if len(eng.query(9)["generated"]) >= 30 and not eng.in_flight:
            break
    assert eng.query(9)["generated"] == want[9]
    assert after == want[9][len(at_return) - held:]
    eng.flush(9)
    assert not eng.state.seqs


def test_a_second_generate_on_a_warm_engine_compiles_nothing():
    eng = engine(tiny_model())
    first = eng.generate({u: list(p) for u, p in PROMPTS.items()}, GREEDY)
    with backend_compiles() as compiles:
        again = eng.generate({u + 10: list(p) for u, p in PROMPTS.items()},
                             GREEDY)
    assert compiles == []
    # greedy streams do not depend on the uid
    assert list(again.values()) == list(first.values())


def test_no_option_selects_a_driver():
    """One loop drives the served step: what launches ahead and what
    stays strict is read from the batch, and the configuration has no
    field to say otherwise."""
    names = [f.name for f in dataclasses.fields(InferenceConfig)]
    assert len(names) == 35
    assert not [n for n in names if "burst" in n or "depth" in n
                or "pipeline" in n]
    # one function launches a served step, one loop runs ahead of it
    drivers = [n for n in vars(InferenceEngine)
               if n.startswith("_generate") or "burst" in n]
    assert drivers == []


@pytest.mark.parametrize("backend,mode,donates", [
    ("cpu", "auto", False), ("tpu", "auto", True),
    ("cpu", "on", True), ("tpu", "off", False)])
def test_kv_donate_auto_is_off_on_the_cpu_backend_only(monkeypatch, backend,
                                                       mode, donates):
    eng = engine(tiny_model(), kv_donate=mode, attn_impl="xla")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert eng._donate_kv() is donates
