"""The served step runs one step ahead (docs/SERVING.md "The served
loop"): ``InferenceEngine.step()`` launches step N+1 before it reads
step N back for the requests whose continuation the engine owns
(``put(max_new_tokens=...)``), and stays strict for a caller that feeds
its own tokens.

Most legs drive a real ``Gateway`` synchronously (``_apply_then_pump``
then ``_route_tokens``/``_resume_stalled``, exactly ``_drive``'s round)
so a slow reader, a cancel or an injected fault lands at a chosen
round; the wire legs run a spawned gateway against a fleet of one, whose
streams the driver feeds (the strict loop)."""

import asyncio
import threading
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.gateway import GatewayConfig, spawn_gateway
from deepspeed_tpu.gateway.server import Gateway, _Stream
from deepspeed_tpu.inference import SamplingParams
from deepspeed_tpu.inference.failures import FailureConfig, Watchdog
from deepspeed_tpu.inference.overload import OverloadConfig
from deepspeed_tpu.inference.ragged.state import (FEEDBACK_TOKEN,
                                                  KVCacheConfig,
                                                  StateManager)
from tests.serving_ref import strict_generate
from tools.loadgen import build_engine, build_fleet, http_completion

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
SEEDED = SamplingParams(temperature=0.8, top_k=20, max_new_tokens=1 << 30)
MODES = pytest.mark.parametrize(
    "sampling,seed", [(GREEDY, None), (SEEDED, 7)], ids=["greedy", "seeded"])

_RNG = np.random.default_rng(0)
PROMPTS = {100 + i: _RNG.integers(1, 120, n).tolist()
           for i, n in enumerate((5, 17, 40, 9, 23, 3))}
MAX_TOKENS = dict(zip(PROMPTS, (12, 7, 20, 1, 2, 15)))


@pytest.fixture(scope="module")
def model():
    return build_engine()[1]


def engine(model, **kw):
    kw.setdefault("num_kv_blocks", 48)
    kw.setdefault("overload", OverloadConfig(prefill_chunk=16))
    return build_engine(model=model, **kw)[0]


def gateway(eng, sampling=GREEDY, seed=None, **kw):
    return Gateway(eng, GatewayConfig(sampling=sampling, seed=seed,
                                      check_invariants=True, **kw))


def admit(g, uid, prompt, max_tokens, owned=True):
    s = _Stream(uid=uid, rid=f"t-{uid}", max_tokens=max_tokens,
                want_stream=True, queue=asyncio.Queue(), owned=owned)
    g._streams[uid] = s
    own = {"max_new_tokens": max_tokens} if owned else {}
    assert g.backend.put(uid, list(prompt), **own)
    return s


def one_round(g, fb, fl):
    """``Gateway._drive``'s round, on this thread."""
    outs, reaped, _ = g._apply_then_pump(fb, fl)
    fb, fl = [], []
    g._route_tokens(outs, reaped, fb, fl)
    g._resume_stalled(fb, fl)
    return fb, fl


def drive(g, before_round=None, rounds=400, read=True):
    """Rounds until every stream is closed and nothing is in flight;
    ``before_round(i)`` may act on the engine or the streams first, and
    with ``read`` every client has read all it was sent by then."""
    fb, fl = [], []
    for i in range(rounds):
        if read:
            for s in g._streams.values():
                while not s.queue.empty():
                    s.queue.get_nowait()
        if before_round is not None:
            before_round(i)
        busy = any(not s.finished for s in g._streams.values())
        if not (busy or g._launched or fb or fl):
            break
        fb, fl = one_round(g, fb, fl)
    else:
        raise AssertionError("the driven loop did not end")


def assert_clean(eng):
    """Nothing open, nothing in flight, every block back."""
    assert not eng.in_flight and not eng._inflight_sched
    assert not eng.state.seqs and not eng.requests.open
    assert not any(eng._pending.values())
    assert not eng._cont and not eng._void and not eng._fb_step
    eng.state.allocator.assert_invariants()
    pool = eng.state.pool_stats()
    assert pool["referenced"] == 0


def served(eng):
    """(steps, steps ahead, strict steps by reason, discarded rows by
    reason) from the engine's registry."""
    m = eng.metrics_snapshot()

    def by_reason(name):
        v = m.get(name)
        return {k.split('"')[1]: int(n) for k, n in v.items()} \
            if isinstance(v, dict) else {}
    return int(m["serving_steps_total"]), \
        int(m["serving_steps_ahead_total"]), \
        by_reason("serving_strict_steps_total"), \
        by_reason("serving_ahead_discarded_rows_total")


def run_streams(model, owned, sampling=GREEDY, seed=None, prompts=PROMPTS,
                max_tokens=MAX_TOKENS, **kw):
    eng = engine(model)
    g = gateway(eng, sampling, seed, **kw)
    ss = {u: admit(g, u, p, max_tokens[u], owned)
          for u, p in prompts.items()}
    drive(g)
    g._exec.shutdown()
    return eng, {u: list(s.tokens) for u, s in ss.items()}, ss


# ==========================================================================
# the same tokens, whoever continues the stream
# ==========================================================================

@MODES
def test_ahead_loop_gives_the_strict_loops_tokens(model, sampling, seed):
    strict_eng, want, _ = run_streams(model, False, sampling, seed)
    ahead_eng, got, ss = run_streams(model, True, sampling, seed)
    assert got == want
    assert {u: len(t) for u, t in got.items()} == MAX_TOKENS
    assert all(s.finish_reason == "length" for s in ss.values())
    for eng in (strict_eng, ahead_eng):
        assert_clean(eng)
    steps, ahead, strict, gone = served(ahead_eng)
    assert ahead == steps - 1 and strict == {"idle": 1} and not gone
    steps, ahead, strict, _ = served(strict_eng)
    assert ahead == 0 and strict == {"caller_fed": steps}


@MODES
def test_wire_streams_match_a_fleet_of_ones_strict_loop(model, sampling,
                                                        seed):
    def over_the_wire(backend):
        h = spawn_gateway(backend, GatewayConfig(
            sampling=sampling, seed=seed, install_signals=False,
            check_invariants=True))
        res = {}

        def one(uid):
            res[uid] = http_completion(h.host, h.port, {
                "prompt": PROMPTS[uid], "max_tokens": MAX_TOKENS[uid],
                "stream": True, "uid": uid})
        ths = [threading.Thread(target=one, args=(u,)) for u in PROMPTS]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        h.stop()
        return res

    eng = engine(model)
    got = over_the_wire(eng)
    fleet = build_fleet(1, model=model, num_kv_blocks=48,
                        overload=OverloadConfig(prefill_chunk=16))[0]
    want = over_the_wire(fleet)
    for uid in PROMPTS:
        assert got[uid]["code"] == want[uid]["code"] == 200
        assert got[uid]["tokens"] == want[uid]["tokens"]
        assert len(got[uid]["tokens"]) == MAX_TOKENS[uid]
        assert got[uid]["finish_reason"] == "length"
    steps, ahead, strict, _ = served(eng)
    # every launch but the ones that found nothing in flight ran ahead
    assert ahead + sum(strict.values()) == steps and ahead > steps // 2
    assert set(strict) <= {"idle"}


@MODES
def test_engine_continued_requests_match_generate(model, sampling, seed):
    rng = None if seed is None else jax.random.PRNGKey(seed)
    want = strict_generate(
        engine(model), PROMPTS,
        SamplingParams(temperature=sampling.temperature,
                       top_k=sampling.top_k, max_new_tokens=9), rng=rng)
    eng = engine(model)
    for uid, p in PROMPTS.items():
        eng.put(uid, p, max_new_tokens=9)
    got = {u: [] for u in PROMPTS}
    for _ in range(200):
        out = eng.step(rng=rng, sampling=sampling)
        for uid, tok in out.items():
            got[uid].append(tok)
            if len(got[uid]) == 9:               # frees its slot, as
                assert eng.query(uid)["generated"] == want[uid]
                eng.flush(uid)                   # generate() does
        if not out and not eng.in_flight:
            break
    assert got == want
    assert_clean(eng)


# ==========================================================================
# a stream that ends with a row in flight
# ==========================================================================

@pytest.mark.parametrize("how", ["max_tokens", "stop", "cancel",
                                 "disconnect"])
def test_stream_ending_with_a_row_in_flight(model, how):
    _, want, _ = run_streams(model, False)
    uid = 102                                   # 20 tokens when left alone
    cut = 6
    sampling = GREEDY
    if how == "stop":
        # the token the stream would emit sixth ends it
        first = want[uid].index(want[uid][cut - 1])
        sampling = SamplingParams(temperature=0.0, max_new_tokens=1 << 30,
                                  stop_token=want[uid][cut - 1])
        cut = first + 1
    eng = engine(model)
    g = gateway(eng, sampling)
    ss = {u: admit(g, u, p, MAX_TOKENS[u]) for u, p in PROMPTS.items()}
    if how == "stop":
        for u, s in ss.items():                  # only 102 meets its stop
            if u != uid and sampling.stop_token in want[u]:
                s.max_tokens = want[u].index(sampling.stop_token) + 1

    def act(_i):
        s = ss[uid]
        if how in ("cancel", "disconnect") and s.emitted >= cut \
                and not s.finished and not s.disconnected:
            s.disconnected = how == "disconnect"
            eng.cancel(uid)

    drive(g, act)
    g._exec.shutdown()
    got = ss[uid].tokens
    if how == "max_tokens":
        assert got == want[uid] and ss[uid].finish_reason == "length"
    elif how == "stop":
        assert got == want[uid][:cut] and ss[uid].finish_reason == "stop"
    else:
        assert got == want[uid][:len(got)] and cut <= len(got) <= cut + 1
        assert ss[uid].finish_reason == "cancelled"
    for u, s in ss.items():
        if u != uid:
            assert s.tokens == want[u][:len(s.tokens)]
            assert len(s.tokens) == s.max_tokens
    assert_clean(eng)
    _, _, _, gone = served(eng)
    if how == "max_tokens":
        assert not gone            # the last token is not speculated past
    elif how == "stop":
        assert gone.get("finished", 0) >= 1
    else:
        assert gone == {"cancelled": 1}


def test_wire_disconnect_mid_stream_leaks_nothing(model):
    eng = engine(model)
    h = spawn_gateway(eng, GatewayConfig(
        sampling=GREEDY, install_signals=False, check_invariants=True,
        max_tokens_cap=64))
    gone = http_completion(h.host, h.port, {
        "prompt": PROMPTS[102], "max_tokens": 60, "stream": True,
        "uid": 102}, disconnect_after=3)
    kept = http_completion(h.host, h.port, {
        "prompt": PROMPTS[100], "max_tokens": 5, "stream": True,
        "uid": 100})
    assert gone["disconnected"] and len(gone["tokens"]) == 3
    assert kept["finish_reason"] == "length" and len(kept["tokens"]) == 5
    t0 = time.time()
    while (eng.state.seqs or eng.requests.open) and time.time() - t0 < 10:
        time.sleep(0.01)
    h.stop()
    assert eng.query(102)["status"] == "cancelled"
    assert not eng.state.seqs and not eng.requests.open
    eng.state.allocator.assert_invariants()


# ==========================================================================
# backpressure: a paused stream loses nothing and repeats nothing
# ==========================================================================

@pytest.mark.parametrize("stalls", [1, 3], ids=["once", "thrice"])
def test_stalled_stream_pauses_and_resumes_with_the_right_token(model,
                                                                stalls):
    _, want, _ = run_streams(model, False)
    eng = engine(model)
    g = gateway(eng, stream_queue=2)
    ss = {u: admit(g, u, p, MAX_TOKENS[u]) for u, p in PROMPTS.items()}
    slow, others = ss[102], [s for u, s in ss.items() if u != 102]
    paused_rounds, state = [], {"stalls": 0, "wait": 0}

    def drain(s):
        while not s.queue.empty():
            s.queue.get_nowait()

    def reader(i):
        # the slow client reads nothing until its stream has been
        # paused for three rounds, ``stalls`` times over; then it keeps up
        for s in others:
            drain(s)
        if state["stalls"] >= stalls:
            drain(slow)
        elif slow.stalled is not None:
            paused_rounds.append(i)
            state["wait"] += 1
            if state["wait"] > 1:                 # the pause has been heard
                assert not eng._pending.get(102)  # the engine feeds nothing
            if state["wait"] == 3:
                drain(slow)
                state["wait"] = 0
                state["stalls"] += 1

    drive(g, reader, read=False)
    g._exec.shutdown()
    assert {u: s.tokens for u, s in ss.items()} == want
    assert len(paused_rounds) == 3 * stalls
    _, _, _, gone = served(eng)
    assert gone == {"stalled": stalls}
    assert_clean(eng)


@pytest.mark.parametrize("owned", [True, False],
                         ids=["engine_continued", "caller_fed"])
def test_hold_takes_the_continuation_back(model, owned):
    want = strict_generate(engine(model), {7: PROMPTS[101]},
                           SamplingParams(max_new_tokens=8))[7]
    eng = engine(model)
    eng.put(7, PROMPTS[101], **({"max_new_tokens": 8} if owned else {}))
    got = []
    while len(got) < 3:
        got += list(eng.step(sampling=GREEDY).values())
        if not owned and got:
            eng.put(7, [got[-1]])
    if not owned:
        eng.hold(7)                               # not the engine's: no-op
        assert eng._pending[7] == [got[-1]]
    else:
        eng.hold(7)
        assert eng._pending[7] == [] and 7 in eng._void
        for _ in range(3):                        # nothing comes by itself
            assert eng.step(sampling=GREEDY) == {}
        assert not eng.in_flight and eng.state.seqs[7].resumable
        eng.put(7, [got[-1]])                     # the held token resumes
    for _ in range(40):
        out = eng.step(sampling=GREEDY)
        got += list(out.values())
        if not owned and out:
            eng.put(7, [got[-1]])
        if len(got) >= 8:
            break
    assert got[:8] == want
    eng.flush(7)
    assert_clean(eng)


# ==========================================================================
# consecutive launches at different row counts
# ==========================================================================

def test_a_launch_ahead_may_run_at_another_rung_than_the_one_unread():
    """A stream decodes alone (the bottom rung, 128 rows) when a prompt
    of 140 tokens arrives: the step that takes it runs at the top rung
    (160) and is launched with the 128-row step before it unread, then
    the next 128-row step with the 160-row one unread.  The sampled row
    that feeds a launch and the pool it donates know no rung: the
    tokens are those of an engine whose one rung is its budget."""
    rng = np.random.default_rng(3)
    late = rng.integers(1, 120, 140).tolist()

    def run(max_seqs, model=None):
        eng, model = build_engine(
            token_budget=160, max_seqs=max_seqs, max_seq_len=256,
            num_kv_blocks=64, model=model, trace=True,
            overload=OverloadConfig(prefill_chunk=160))
        eng.put(1, PROMPTS[102], max_new_tokens=14)
        got = {1: [], 2: []}
        for i in range(80):
            if i == 5:
                eng.put(2, late, max_new_tokens=6)
            for u, t in eng.step(sampling=GREEDY).items():
                got[u].append(t)
            if [len(got[1]), len(got[2])] == [14, 6] and not eng.in_flight:
                break
        for uid in got:
            eng.flush(uid)
        assert_clean(eng)
        launches = [(e["args"]["rows"], e["args"]["ahead"])
                    for e in eng.tracer.events()
                    if e["name"] in ("ds.serve.dispatch", "ds.serve.compile")]
        return eng, model, got, launches

    eng, model, got, launches = run(4)
    assert eng._step_rows == (128, 160)
    ahead = {(a[0], b[0]) for a, b in zip(launches, launches[1:]) if b[1]}
    assert {(128, 160), (160, 128), (128, 128)} <= ahead, launches
    one, _, want, plain = run(160, model)
    assert one._step_rows == (160,) and {r for r, _ in plain} == {160}
    assert got == want
    # every rung compiled with the step function, none under the stream
    assert eng.timings["compiles"] == 2 * len(eng._pstep_fns)


# ==========================================================================
# faults with a launch in flight
# ==========================================================================

@pytest.mark.parametrize("site,kind", [("collect", "transient"),
                                       ("collect", "crash"),
                                       ("dispatch", "transient")])
def test_fault_with_a_launch_in_flight_requeues_both_steps(model, site,
                                                           kind):
    _, want, _ = run_streams(model, False)
    eng = engine(model)
    g = gateway(eng)
    ss = {u: admit(g, u, p, MAX_TOKENS[u]) for u, p in PROMPTS.items()}
    armed, hit = [True], []
    run = eng.failures.run

    def guarded(fn, **kw):
        if armed and kw.get("site") == site and eng._ahead is not None \
                and eng.timings["generated_tokens"] >= 12:
            armed.clear()
            hit.append((set(kw["uids"]), set(eng._ahead.uids)))
            eng.failures.inject(kind)
        return run(fn, **kw)

    eng.failures.run = guarded
    drive(g)
    g._exec.shutdown()
    assert hit, "no fault was injected"
    assert {u: s.tokens for u, s in ss.items()} == want
    assert all(s.finish_reason == "length" for s in ss.values())
    assert eng.timings["step_retries"] >= 1
    assert eng.timings["requests_failed"] == 0
    retried = {r["uid"] for r in eng.request_metrics()["requests"]
               if r["retries"]}
    failed_uids, other_uids = hit[0]
    if site == "collect":
        # the step whose read failed AND the one launched behind it
        assert retried >= failed_uids | other_uids
    else:
        # the launch that failed; the one in flight was read back first
        assert retried >= failed_uids
    assert_clean(eng)


# ==========================================================================
# callers that feed their own tokens stay strict
# ==========================================================================

def test_direct_put_step_caller_gets_its_token_from_the_same_call(model):
    eng = engine(model, trace=True)
    eng.put(1, PROMPTS[100])
    out = eng.step(sampling=GREEDY)
    assert list(out) == [1] and not eng.in_flight
    for _ in range(4):
        eng.put(1, [out[1]])
        out = eng.step(sampling=GREEDY)
        assert list(out) == [1] and not eng.in_flight
    steps, ahead, strict, _ = served(eng)
    assert ahead == 0 and strict == {"caller_fed": steps} and steps == 5
    assert [e["args"]["ahead"] for e in eng.tracer.events()
            if e["name"] in ("ds.serve.dispatch", "ds.serve.compile")] \
        == [0] * 5
    eng.flush(1)
    assert_clean(eng)


def test_a_caller_fed_request_among_engine_continued_ones(model):
    want = strict_generate(
        engine(model), {u: PROMPTS[u] for u in (100, 101, 104)},
        SamplingParams(max_new_tokens=10))
    eng = engine(model)
    eng.put(100, PROMPTS[100], max_new_tokens=10)
    eng.put(101, PROMPTS[101], max_new_tokens=10)
    got = {100: [], 101: [], 104: []}
    for i in range(200):
        if i == 4:
            eng.put(104, PROMPTS[104])            # fed by this caller
        out = eng.step(sampling=GREEDY)
        for uid, tok in out.items():
            got[uid].append(tok)
        if 104 in out and len(got[104]) < 10:
            assert not eng.in_flight              # its step was strict
            eng.put(104, [out[104]])
        if all(len(t) >= 10 for t in got.values()):
            break
    assert got == want
    steps, ahead, strict, _ = served(eng)
    assert ahead > 0 and strict.get("caller_fed", 0) >= 10
    for uid in got:
        eng.flush(uid)
    eng.step(sampling=GREEDY)
    assert_clean(eng)


def test_speculative_engine_stays_strict(model):
    eng = engine(model, spec_decode="on")
    prompt = [5, 6, 7, 8] * 6
    eng.put(3, prompt, max_new_tokens=12)
    got = []
    for _ in range(60):
        out = eng.step(sampling=GREEDY)
        assert not eng.in_flight
        got += list(out.values())
        if eng._cont.get(3, 0) <= 0:
            break
    want = strict_generate(engine(model), {3: prompt},
                           SamplingParams(max_new_tokens=12))[3]
    assert eng.query(3)["generated"][:12] == want
    _, ahead, strict, _ = served(eng)
    assert ahead == 0 and set(strict) == {"spec_decode"}
    eng.flush(3)
    assert_clean(eng)


# ==========================================================================
# the counters, the span attribute, the slow round's record
# ==========================================================================

def test_counters_and_span_attribute_say_how_often_it_ran_ahead(model):
    eng = engine(model, trace=True)
    eng.put(1, PROMPTS[100], max_new_tokens=6)
    eng.put(2, PROMPTS[101], max_new_tokens=3)
    n = 0
    while eng.in_flight or any(eng._pending.values()):
        n += len(eng.step(sampling=GREEDY))
    assert n == 9
    steps, ahead, strict, gone = served(eng)
    flags = [e["args"]["ahead"] for e in eng.tracer.events()
             if e["name"] in ("ds.serve.dispatch", "ds.serve.compile")]
    assert flags[0] == 0 and set(flags[1:]) == {1}
    assert len(flags) == steps and sum(flags) == ahead
    assert strict == {"idle": 1} and not gone
    text = eng.metrics.prometheus_text()
    assert f"serving_steps_ahead_total {ahead}" in text
    assert 'serving_strict_steps_total{reason="idle"} 1' in text
    # a stream cancelled with its row in flight is the discarded one
    eng.put(3, PROMPTS[102], max_new_tokens=50)
    while not eng.state.seqs.get(3) or not eng.state.seqs[3].tokens:
        eng.step(sampling=GREEDY)
    assert eng.in_flight
    eng.cancel(3)
    assert eng.step(sampling=GREEDY) == {}
    assert served(eng)[3] == {"cancelled": 1}
    for uid in (1, 2):
        eng.flush(uid)
    assert_clean(eng)


def test_slow_round_record_carries_what_the_watchdog_stamped():
    """A guarded wait that alone outlasts the round's threshold is judged
    as its result is taken back: the watchdog's stamps and what the
    engine adds (``next_ready``) ride in the one ``slow_round`` record."""
    from deepspeed_tpu.inference.failures import FailurePolicy
    from deepspeed_tpu.telemetry import FlightRecorder
    tm = {k: 1.0 for k in ("steps", "schedule_ms", "stage_ms", "device_ms",
                           "wait_ms", "readback_ms")}
    flight = FlightRecorder()
    pol = FailurePolicy(FailureConfig(dispatch_timeout_ms=2000.0,
                                      watchdog_warmup_steps=2), tm,
                        flight=flight)
    rw = pol.rounds
    # what a loaded test machine would add is not what this run is about
    rw.sampler.read = lambda tids=(): {}
    rw.end(True)
    for sid in range(1, 4):                 # three quick rounds: 2 ms mean
        t = time.perf_counter()
        rw.cut_collect(sid, t, t + 1e-3, t + 1e-3, False, {})
        time.sleep(0.002)
        rw.end(True)
    assert rw.limit_us < 60e3
    stamps = {}
    assert pol.run(lambda: time.sleep(0.08) or 5, site="collect", sid=9,
                   stamps=stamps) == 5
    assert stamps["hop_us"] + stamps["fn_us"] > rw.limit_us
    where = rw.judge_wait(9, stamps, True)
    t = time.perf_counter()
    rw.cut_collect(9, t - 0.08, t, t, False, stamps)
    rw.end(True)
    (info,) = [e for e in flight.events() if e["kind"] == "slow_round"]
    assert info["sid"] == 9 and info["where"] == where == "completion"
    assert info["next_ready"] is True and info["fn_ms"] >= 75.0


def test_slow_collect_says_whether_the_next_launch_was_ready(model,
                                                             monkeypatch):
    eng = engine(model, failure=FailureConfig(dispatch_timeout_ms=4000.0))
    eng.put(1, PROMPTS[100], max_new_tokens=40)
    for _ in range(14):                     # past the warm-up's rounds
        eng.step(sampling=GREEDY)
    assert eng.in_flight and eng._round.n >= 8
    eng._round.sampler.read = lambda tids=(): {}    # nor a loaded machine
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: time.sleep(0.2) or real(x))
    eng.step(sampling=GREEDY)
    monkeypatch.undo()
    slow = [e for e in eng.flight.events()
            if e["kind"] == "slow_round"]
    assert slow and slow[-1]["fn_ms"] >= 190.0
    assert slow[-1]["where"] in ("device", "completion")
    assert slow[-1]["next_ready"] in (True, False)
    assert eng.metrics_snapshot()["serving_slow_rounds_total"]
    eng.cancel(1)
    eng.step(sampling=GREEDY)
    assert_clean(eng)


# ==========================================================================
# boundaries that need the streams on the host settle the launch first
# ==========================================================================

def test_snapshot_reads_the_launch_in_flight_back_first(model):
    want = strict_generate(
        engine(model), {u: PROMPTS[u] for u in (100, 101)},
        SamplingParams(max_new_tokens=10))
    eng = engine(model)
    for uid in want:
        eng.put(uid, PROMPTS[uid], max_new_tokens=10)
    got = {u: [] for u in want}
    for i in range(100):
        if i == 5:
            assert eng._ahead is not None
            snap = eng.snapshot()
            assert eng._ahead is None and eng.in_flight     # tokens held
            assert all(r["exact"] and r["tokens"]
                       for r in snap["requests"])
            for r in snap["requests"]:
                # the replayable stream ends at the last token sampled
                assert r["tokens"][-1] == r["generated"][-1]
        out = eng.step(sampling=GREEDY)
        for uid, tok in out.items():
            got[uid].append(tok)
        if not out and not eng.in_flight:
            break
    assert got == want
    for uid in want:
        eng.flush(uid)
    assert_clean(eng)


def test_running_ahead_keeps_every_chain_whole(model):
    eng = engine(model, prefix_cache="on")
    eng.put(1, PROMPTS[102], max_new_tokens=30)
    for _ in range(24):
        eng.step(sampling=GREEDY)
    seq = eng.state.seqs[1]
    # between calls every token fed from the device has been read: the
    # row in flight took the last one emitted
    assert eng.in_flight and eng._inflight_sched == {1: 1}
    assert not seq.chain_broken and not seq.deferred and seq.resumable
    assert seq.chain == PROMPTS[102] + seq.tokens
    eng._settle()
    assert seq.resumable and not eng._inflight_sched
    assert seq.chain == PROMPTS[102] + seq.tokens[:-1]
    # generated blocks are hashed once the host has read their tokens
    bs = eng.icfg.kv_block_size
    assert len(seq.hashes) >= (len(PROMPTS[102]) + len(seq.tokens) - 3) \
        // bs
    eng.step(sampling=GREEDY)                   # hands the held tokens over
    eng.cancel(1)
    assert_clean(eng)


def test_drain_with_a_launch_in_flight_sheds_replayable_records(model):
    eng = engine(model)
    for uid in (100, 101, 102):
        eng.put(uid, PROMPTS[uid], max_new_tokens=40)
    for _ in range(8):
        eng.step(sampling=GREEDY)
    assert eng.in_flight
    snap = eng.drain(sampling=GREEDY)
    assert sorted(snap["shed_uids"]) == [100, 101, 102]
    assert all(r["exact"] for r in snap["requests"])
    assert not eng._ahead and not eng._cont
    eng.state.allocator.assert_invariants()
    assert not eng.state.seqs and not eng.requests.open


# ==========================================================================
# the chain's deferred entries (StateManager)
# ==========================================================================

def _sm():
    return StateManager(KVCacheConfig(
        num_layers=1, num_kv_heads=1, head_dim=8, block_size=4,
        num_blocks=16), max_seqs=2, prefix_cache=True)


def test_deferred_feedback_row_keeps_its_place_in_the_chain():
    sm = _sm()
    sm.build_batch([(0, [1, 2, 3])], token_budget=16)
    sm.build_batch([(0, [FEEDBACK_TOKEN])], token_budget=16,
                   deferred_from={0: 11})
    seq = sm.seqs[0]
    assert not seq.chain_broken and not seq.resumable
    assert seq.chain == [1, 2, 3, FEEDBACK_TOKEN]
    assert seq.deferred == [(11, 3)] and seq.hashes == []
    sm.resolve_feedback(0, 10, 99)              # another step's read: no-op
    assert seq.deferred == [(11, 3)]
    sm.resolve_feedback(0, 11, 42)
    assert seq.chain == [1, 2, 3, 42] and seq.resumable
    sm.build_batch([(0, [7])], token_budget=16)
    assert len(seq.hashes) == 1                 # hashed with the next batch
    assert sm.match_prefix(1, [1, 2, 3, 42, 9]) == 4


def test_rewind_takes_a_launched_row_back():
    sm = _sm()
    sm.build_batch([(0, [1, 2, 3, 4, 5])], token_budget=16)
    sm.build_batch([(0, [FEEDBACK_TOKEN])], token_budget=16,
                   deferred_from={0: 3})
    seq = sm.seqs[0]
    blocks = list(seq.blocks)
    sm.rewind(0)
    assert seq.seen_tokens == 5 and seq.chain == [1, 2, 3, 4, 5]
    assert not seq.deferred and seq.resumable and seq.blocks == blocks
    sm.build_batch([(0, [6])], token_budget=16)
    assert seq.chain == [1, 2, 3, 4, 5, 6] and seq.seen_tokens == 6
    sm.allocator.assert_invariants()


# ==========================================================================
# the driver does not take "launched, nothing to hand over" for idle
# ==========================================================================

def test_a_pump_that_only_launched_is_not_an_idle_round(model):
    eng = engine(model)
    h = spawn_gateway(eng, GatewayConfig(
        sampling=GREEDY, install_signals=False, idle_s=1.0))
    body = {"prompt": PROMPTS[100], "max_tokens": 4, "stream": True}
    http_completion(h.host, h.port, body)       # compiles
    t0 = time.perf_counter()
    res = http_completion(h.host, h.port, body)
    took = time.perf_counter() - t0
    h.stop()
    assert len(res["tokens"]) == 4
    # one idle sleep (the launch of its first step returns no token)
    # would already cost idle_s
    assert took < 1.0, took


def test_a_disconnects_cancel_survives_its_watchers_cancellation():
    """The connection's handler cancels its disconnect watcher as it
    unwinds; a ``cancel`` still queued behind a running pump must reach
    the engine all the same, or the request runs on with nobody to end
    it (seen as a stream left ``running`` until the drain shed it)."""
    from deepspeed_tpu.telemetry import MetricsRegistry

    calls, gate = [], threading.Event()

    class Stub:
        metrics = MetricsRegistry()

        def cancel(self, uid):
            calls.append(uid)

    async def scenario():
        g = Gateway(Stub())
        s = _Stream(uid=5, rid="r", max_tokens=8, want_stream=True,
                    queue=asyncio.Queue())
        busy = asyncio.ensure_future(g._call(gate.wait))   # a long pump
        await asyncio.sleep(0.01)
        gone = asyncio.ensure_future(g._client_gone(s))
        await asyncio.sleep(0.01)
        gone.cancel()                       # the handler's watcher.cancel()
        await asyncio.sleep(0.01)
        gate.set()
        await busy
        await g._call(lambda: None)         # the queue behind it has run
        g._exec.shutdown()
        return s

    s = asyncio.run(scenario())
    assert s.disconnected and calls == [5]


def test_deadline_is_enforced_on_a_stream_that_never_leaves_the_pipeline(
        model):
    """A continuously decoding engine-continued stream has a row in
    flight at every schedule pass; its deadline must still close it."""
    eng = engine(model)
    eng.put(1, PROMPTS[100], max_new_tokens=80, deadline_ms=150.0)
    eng.put(2, PROMPTS[101], max_new_tokens=80)
    t0 = time.perf_counter()
    while eng.query(1)["status"] in ("queued", "running") \
            and time.perf_counter() - t0 < 20.0:
        eng.step(sampling=GREEDY)
    assert eng.query(1)["status"] == "deadline_exceeded"
    # closed within a round or two of the deadline, not at its budget's end
    assert len(eng.query(1)["generated"]) == 0
    done = [r for r in eng.request_metrics()["requests"] if r["uid"] == 1]
    assert done and done[0]["generated_tokens"] < 40
    assert 1 in eng._drain_reaped()
    assert eng.query(2)["status"] == "running"
    eng.cancel(2)
    eng.step(sampling=GREEDY)
    assert_clean(eng)
