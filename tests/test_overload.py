"""Overload-policy tests (docs/SERVING.md "Surviving overload"):
admission verdicts + backpressure shed policies, chunked-prefill
interleaving, preemption-by-eviction, deadline enforcement, client
cancels, the terminal-lifecycle-close-out-on-every-exit-path guarantee
(request_metrics() can never leak an open record), and query()'s
explicit status field.

Most tests are host-only (scheduler + allocator, no device step) and
run in milliseconds; the preempt/resume parity tests dispatch real
steps on the CPU backend.
"""

import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.inference.overload import (AdmissionVerdict,
                                              OverloadConfig,
                                              admission_decision,
                                              effective_priority,
                                              select_victim)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.telemetry import TERMINAL_STATUSES


@pytest.fixture(scope="module")
def model():
    return build_model("llama-tiny", vocab_size=128, num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       max_seq_len=256)


def mk(model, overload=None, **kw):
    cfg = dict(token_budget=16, max_seqs=3, kv_block_size=8,
               num_kv_blocks=6, max_seq_len=48)
    cfg.update(kw)
    return InferenceEngine(model, InferenceConfig(overload=overload, **cfg))


def sched_round(eng):
    """One host-side scheduler round, materialized (the fuzz-test
    idiom: _schedule reserves, build_batch allocates for real)."""
    sched = eng._schedule()
    if sched:
        eng.state.build_batch(sched, eng.icfg.token_budget,
                              stager=eng._stager)
    return sched


def check_allocator(eng):
    al = eng.state.allocator
    al.assert_invariants()
    return al


# --------------------------------------------------------------------------
# pure policy units (inference/overload.py)
# --------------------------------------------------------------------------

class TestPolicyUnits:
    def test_effective_priority_aging(self):
        # waiting aging_ms promotes one whole tier
        assert effective_priority(2, t_arrival=0.0, now=1.0,
                                  aging_ms=1000.0) == pytest.approx(1.0)
        # aging disabled: raw priority
        assert effective_priority(2, 0.0, 99.0, None) == 2.0
        assert effective_priority(2, 0.0, 99.0, 0) == 2.0

    def test_admission_decision_bounds(self):
        cfg = OverloadConfig(max_queued_requests=2)
        q = [(1, 0.0, 4), (2, 0.0, 4)]
        assert admission_decision(cfg, 0, 4, [], 0.0) == ("admit", ())
        assert admission_decision(cfg, 0, 4, q, 0.0) == ("shed", ())
        cfg = OverloadConfig(max_queued_tokens=10)
        assert admission_decision(cfg, 0, 3, q, 0.0) == ("shed", ())
        assert admission_decision(cfg, 0, 2, q, 0.0) == ("admit", ())

    def test_admission_decision_policies(self):
        q = [(1, 2.0, 4), (2, 5.0, 4)]
        cfg = OverloadConfig(max_queued_requests=2,
                             shed_policy="evict-lowest")
        # newcomer outranks the worst queued entry -> evict it
        assert admission_decision(cfg, 0, 4, q, 0.0) == ("evict", (2,))
        # tie (or worse) sheds the newcomer, never churns the backlog
        assert admission_decision(cfg, 5, 4, q, 0.0) == ("shed", ())
        cfg = OverloadConfig(max_queued_requests=2, shed_policy="degrade")
        assert admission_decision(cfg, 0, 4, q, 0.0) == ("degrade", ())

    def test_evict_lowest_holds_token_bound(self):
        """One eviction is not always enough: the token bound must
        actually hold after the evictions, or the 'bounded' queue
        drifts upward without limit."""
        cfg = OverloadConfig(max_queued_tokens=20,
                             shed_policy="evict-lowest")
        q = [(1, 5.0, 6), (2, 5.0, 6), (3, 5.0, 6)]
        # queue holds 18; a 14-token newcomer needs TWO 6-token
        # evictions (12+14 > 20, 6+14 <= 20)
        action, victims = admission_decision(cfg, 0, 14, q, 0.0)
        assert action == "evict" and len(victims) == 2
        assert set(victims) <= {1, 2, 3}
        # one eviction suffices for an 8-token newcomer
        action, victims = admission_decision(cfg, 0, 8, q, 0.0)
        assert action == "evict" and len(victims) == 1
        # even shedding every worse entry cannot fit a 24-token one
        assert admission_decision(cfg, 0, 24, q, 0.0) == ("shed", ())

    def test_select_victim(self):
        cands = [(10, 1.0, 2), (11, 2.0, 3), (12, 2.0, 5)]
        # worst tier wins; ties break toward the most KV blocks
        assert select_victim(cands, better_than=0.0) == 12
        # only STRICTLY worse qualifies
        assert select_victim(cands, better_than=2.0) is None
        assert select_victim([], 0.0) is None

    def test_config_validation(self):
        with pytest.raises(ValueError):
            OverloadConfig(shed_policy="nope")
        with pytest.raises(ValueError):
            OverloadConfig(prefill_chunk=0)
        with pytest.raises(ValueError):
            OverloadConfig(max_preemptions_per_step=-1)


# --------------------------------------------------------------------------
# put() verdicts + backpressure
# --------------------------------------------------------------------------

class TestAdmission:
    def test_default_put_is_legacy(self, model):
        eng = mk(model)
        v = eng.put(0, [1, 2, 3])
        assert isinstance(v, AdmissionVerdict) and bool(v)
        assert v.status == "queued"
        assert eng.put(0, [4]).status == "continued"
        # unbounded default: a pile of requests all admit
        assert all(eng.put(u, [1] * 30) for u in range(1, 20))

    def test_reject_policy(self, model):
        eng = mk(model, OverloadConfig(max_queued_requests=2))
        assert eng.put(0, [1] * 4)
        assert eng.put(1, [1] * 4)
        v = eng.put(2, [1] * 4)
        assert not v and v.status == "shed"
        assert eng.query(2)["status"] == "shed"
        agg = eng.request_metrics()["aggregate"]
        assert agg["statuses"].get("shed") == 1
        assert agg["open"] == 2
        # continuations are never shed, even over the bound
        assert eng.put(0, [9]).status == "continued"

    def test_token_bound(self, model):
        eng = mk(model, OverloadConfig(max_queued_tokens=10))
        assert eng.put(0, [1] * 8)
        assert not eng.put(1, [1] * 8)
        assert eng.put(2, [1] * 2)      # still fits

    def test_evict_lowest(self, model):
        eng = mk(model, OverloadConfig(max_queued_requests=2,
                                       shed_policy="evict-lowest"))
        eng.put(0, [1] * 4, priority=0)
        eng.put(1, [1] * 4, priority=5)
        v = eng.put(2, [1] * 4, priority=1)
        assert v and v.status == "queued" and v.evicted_uids == (1,)
        assert eng.query(1)["status"] == "shed"
        assert 1 not in eng._pending
        # equal priority: the newcomer sheds instead
        v = eng.put(3, [1] * 4, priority=1)
        assert not v and v.status == "shed"

    def test_degrade(self, model):
        eng = mk(model, OverloadConfig(max_queued_requests=1,
                                       shed_policy="degrade"))
        eng.put(0, [1] * 4)
        v = eng.put(1, [1] * 4, priority=3)
        assert v and v.status == "degraded"
        assert eng._meta[1].degraded
        assert eng._meta[1].priority == eng.ocfg.degrade_priority

    def test_shed_never_opens_kv(self, model):
        eng = mk(model, OverloadConfig(max_queued_requests=1))
        eng.put(0, [1] * 4)
        eng.put(1, [1] * 4)
        sched_round(eng)
        assert 1 not in eng.state.seqs
        rec = {r["uid"]: r for r in eng.request_metrics()["requests"]}
        assert rec[1]["status"] == "shed"
        assert rec[1]["prompt_tokens"] == 0
        check_allocator(eng)


# --------------------------------------------------------------------------
# chunked prefill
# --------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_prompt_interleaving(self, model):
        eng = mk(model, OverloadConfig(prefill_chunk=4), num_kv_blocks=12,
                 max_seq_len=96)
        eng.put(0, list(range(1, 21)))
        eng.put(1, list(range(1, 21)))
        sched = sched_round(eng)
        # both prompts share the step, neither takes more than a chunk
        assert {u for u, _ in sched} == {0, 1}
        assert all(len(t) <= 4 for _, t in sched)

    def test_decode_never_queues_behind_prefill(self, model):
        eng = mk(model, OverloadConfig(prefill_chunk=8), num_kv_blocks=12,
                 max_seq_len=96, token_budget=8)
        eng.put(0, [1, 2, 3])
        sched_round(eng)
        eng.put(0, [7])                    # decode continuation
        eng.put(1, list(range(1, 41)))     # monster prompt arrives
        for _ in range(4):
            sched = sched_round(eng)
            if not eng._pending.get(1):
                break
            # the decode token rides EVERY step the prompt is chunking
            assert sched[0][0] == 0 and len(sched[0][1]) == 1
            eng.put(0, [7])

    def test_no_cap_reproduces_legacy(self, model):
        eng = mk(model, num_kv_blocks=12, max_seq_len=96)
        eng.put(0, list(range(1, 41)))
        sched = sched_round(eng)
        assert sum(len(t) for _, t in sched) == eng.icfg.token_budget


# --------------------------------------------------------------------------
# preemption-by-eviction
# --------------------------------------------------------------------------

class TestPreemption:
    def test_starved_high_tier_preempts(self, model):
        # pool exactly fits the low-tier victim: the newcomer starves
        # (prompts are DISJOINT — a shared prefix would admit through
        # the cache without needing blocks, correctly avoiding the
        # preemption this test wants to force)
        eng = mk(model, OverloadConfig(preemption=True), num_kv_blocks=4)
        eng.put(0, list(range(1, 33)), priority=5)   # low tier, 4 blocks
        while eng._pending.get(0):
            sched_round(eng)
        assert len(eng.state.seqs[0].blocks) == 4
        eng.put(1, list(range(40, 64)), priority=0)  # disjoint, free 0
        sched = sched_round(eng)
        assert 0 not in eng.state.seqs          # victim evicted
        assert any(u == 1 for u, _ in sched)    # newcomer admitted
        # the victim re-queued its full host-known stream
        assert eng._pending[0] == list(range(1, 33))
        assert eng.query(0)["status"] == "queued"
        rec = {r["uid"]: r for r in eng.request_metrics()["requests"]}
        assert rec[0]["status"] == "open" and rec[0]["preemptions"] == 1
        assert eng.request_metrics()["aggregate"]["preemptions"] == 1
        check_allocator(eng)

    def test_single_tier_is_inert(self, model):
        """All requests at one priority: preemption can never trigger
        (raw-tier comparison is strict), reproducing legacy behavior."""
        eng = mk(model, OverloadConfig(preemption=True), num_kv_blocks=4)
        eng.put(0, list(range(1, 33)))
        while eng._pending.get(0):
            sched_round(eng)
        eng.put(1, list(range(40, 64)))
        sched_round(eng)
        assert 0 in eng.state.seqs              # untouched
        assert 1 not in eng.state.seqs          # newcomer just waits
        assert eng.request_metrics()["aggregate"]["preemptions"] == 0

    def test_preemption_respects_cap_and_inflight(self, model):
        eng = mk(model, OverloadConfig(preemption=True,
                                       max_preemptions_per_step=1),
                 num_kv_blocks=4)
        eng.put(0, list(range(1, 33)), priority=5)
        while eng._pending.get(0):
            sched_round(eng)
        # a sequence with an uncollected in-flight step is untouchable
        eng._inflight_sched[0] = 1
        eng.put(1, list(range(40, 64)), priority=0)
        sched_round(eng)
        assert 0 in eng.state.seqs
        eng._inflight_sched.pop(0)
        sched_round(eng)
        assert 0 not in eng.state.seqs

    def test_victim_stale_pending_not_readmitted_same_round(self, model):
        """A victim preempted MID-ROUND while its own pending entry is
        still ahead in the iteration: the stale entry (mid-stream
        tokens) must be skipped, not admitted as a fresh prompt at
        position 0 — the requeued full stream waits for the next
        round."""
        eng = mk(model, OverloadConfig(preemption=True), num_kv_blocks=4)
        eng.put(0, list(range(1, 41)), priority=5)   # 40-token prompt
        sched_round(eng)                             # prefill 16
        sched_round(eng)                             # prefill 16 (32 in)
        assert eng.state.seqs[0].seen_tokens == 32
        assert eng._pending[0] == list(range(33, 41))  # 8 left, free 0
        eng.put(1, list(range(60, 68)), priority=0)  # disjoint, starves
        sched = sched_round(eng)
        # uid 1 preempted uid 0 and got the step to itself
        assert {u for u, _ in sched} == {1}
        assert 0 not in eng.state.seqs
        # the victim's pending is the FULL requeued stream, untouched by
        # its stale (pre-preemption) iteration entry
        assert eng._pending[0] == list(range(1, 41))
        # and its mid-stream tokens were not double-counted as a prompt
        assert int(eng.timings["prompt_tokens"]) == 40 + 8
        # once the preemptor releases the pool, the requeue re-prefills
        # from position 0 normally (via the cached chain where it
        # survived uid 1's eviction pressure)
        eng.flush(1)
        sched = sched_round(eng)
        assert any(u == 0 for u, _ in sched)
        check_allocator(eng)

    def test_broken_chain_never_victim(self, model):
        eng = mk(model, OverloadConfig(preemption=True), num_kv_blocks=4)
        eng.put(0, list(range(1, 33)), priority=5)
        while eng._pending.get(0):
            sched_round(eng)
        eng.state.seqs[0].chain_broken = True   # burst-written KV
        eng.put(1, list(range(40, 64)), priority=0)
        sched_round(eng)
        assert 0 in eng.state.seqs


# --------------------------------------------------------------------------
# deadlines, cancels, and the close-out-on-every-exit-path guarantee
# --------------------------------------------------------------------------

class TestTerminalCloseout:
    def test_deadline_queued(self, model):
        eng = mk(model)
        eng.put(0, [1] * 4, deadline_ms=0.01)
        time.sleep(0.002)
        assert sched_round(eng) == []
        assert eng.query(0)["status"] == "deadline_exceeded"
        assert 0 not in eng._pending and 0 not in eng._meta
        assert eng._drain_reaped() == {0}
        assert not eng.requests.open

    def test_deadline_running(self, model):
        eng = mk(model)
        # admitted under a deadline no host is slow enough to miss, cut
        # short once the request runs: the test reads the scheduler's
        # rule, not how fast this host reached its first round
        eng.put(0, [1] * 4, deadline_ms=1e9)
        sched_round(eng)
        assert 0 in eng.state.seqs
        eng._meta[0].deadline_ms = 5.0
        time.sleep(0.01)
        sched_round(eng)
        assert 0 not in eng.state.seqs
        assert eng.query(0)["status"] == "deadline_exceeded"
        al = check_allocator(eng)
        assert al.referenced_blocks == 0

    def test_cancel_queued_and_running(self, model):
        eng = mk(model)
        eng.put(0, [1] * 4)
        eng.cancel(0)
        assert eng.query(0)["status"] == "cancelled"
        eng.put(1, [1] * 4)
        sched_round(eng)
        eng.cancel(1)
        assert 1 not in eng.state.seqs
        assert eng.query(1)["status"] == "cancelled"
        assert eng._drain_reaped() == {0, 1}
        assert not eng.requests.open
        check_allocator(eng)
        eng.cancel(42)                      # unknown uid: no-op

    def test_direct_release_closes_record(self, model):
        """Satellite fix: a mid-flight StateManager.release used to
        leak the open record forever."""
        eng = mk(model)
        eng.put(0, [1] * 4)
        sched_round(eng)
        eng.state.release(0)
        assert eng.query(0)["status"] == "released"
        assert not eng.requests.open

    def test_ctx_exhausted_closes_record(self, model):
        """Satellite fix: context-exhausted requests never closed out in
        RequestTracker under the direct step() API."""
        eng = mk(model, num_kv_blocks=8, max_seq_len=32)
        eng.put(0, [1] * 30)
        while eng._pending.get(0):
            sched_round(eng)
        eng.put(0, [1, 2, 3])               # beyond max context
        # the first rounds still fit tokens into the last block; the
        # round that finds ctx_remaining == 0 marks exhaustion
        for _ in range(4):
            if 0 in eng._ctx_exhausted:
                break
            sched_round(eng)
        assert 0 in eng._ctx_exhausted
        eng._close_ctx_exhausted()
        assert 0 not in eng.state.seqs
        assert eng.query(0)["status"] == "context_exhausted"
        assert not eng.requests.open
        check_allocator(eng)

    def test_flush_is_finished_and_idempotent(self, model):
        eng = mk(model)
        eng.put(0, [1] * 4)
        sched_round(eng)
        eng.flush(0)
        assert eng.query(0)["status"] == "finished"
        eng.flush(0)                        # second close: no-op
        agg = eng.request_metrics()["aggregate"]
        assert agg["finished"] == 1
        assert agg["statuses"] == {"finished": 1}

    def test_statuses_are_documented(self, model):
        eng = mk(model)
        for s in ("finished", "shed", "deadline_exceeded",
                  "context_exhausted", "cancelled", "released"):
            assert s in TERMINAL_STATUSES


# --------------------------------------------------------------------------
# query() status field
# --------------------------------------------------------------------------

class TestQueryStatus:
    def test_full_ladder(self, model):
        eng = mk(model, OverloadConfig(max_queued_requests=1))
        assert eng.query(99)["status"] == "unknown"
        eng.put(0, [1] * 4)
        assert eng.query(0)["status"] == "queued"
        sched_round(eng)
        assert eng.query(0)["status"] == "running"
        eng.flush(0)
        assert eng.query(0)["status"] == "finished"
        eng.put(1, [1] * 4)
        assert not eng.put(2, [1] * 4)
        assert eng.query(2)["status"] == "shed"

    def test_generated_survives_preemption(self, model):
        eng = mk(model, OverloadConfig(preemption=True), num_kv_blocks=4)
        eng.put(0, list(range(1, 33)), priority=5)
        while eng._pending.get(0):
            sched_round(eng)
        eng.state.seqs[0].tokens.extend([7, 8])   # as _collect would
        eng.put(1, list(range(40, 64)), priority=0)
        sched_round(eng)                          # preempts uid 0
        assert eng.query(0)["generated"] == [7, 8]


# --------------------------------------------------------------------------
# end-to-end: real steps through the overloaded engine
# --------------------------------------------------------------------------

def drive(eng, prompts, max_new, rng=None, preempt=None, priorities=None):
    """Minimal direct-API serving loop (what a front-end runs):
    ``preempt=(victim_uid, after_n_steps)`` force-evicts mid-run."""
    for uid, p in prompts.items():
        eng.put(uid, p, priority=(priorities or {}).get(uid, 0))
    done = {u: [] for u in prompts}
    active = set(prompts)
    n = 0
    while active:
        outs = eng.step(rng=rng)
        active -= eng._drain_reaped()
        for uid, tok in outs.items():
            if uid not in active:
                continue
            done[uid].append(tok)
            if len(done[uid]) >= max_new:
                active.discard(uid)
                eng.flush(uid)
            else:
                eng.put(uid, [tok])
        n += 1
        if preempt is not None and n == preempt[1] \
                and preempt[0] in eng.state.seqs:
            eng._preempt(preempt[0])
        assert n < 500, "drive() did not terminate"
    return done


class TestPreemptResumeParity:
    """Evict-and-re-prefill must be invisible in the output stream:
    (uid, position)-folded sampling keys + the host-known chain requeue
    make a preempted-then-resumed request token-identical to an
    undisturbed run."""

    def test_greedy_parity(self, model):
        r = np.random.RandomState(3)
        prompts = {0: list(r.randint(1, 128, 12)),
                   1: list(r.randint(1, 128, 9))}
        kw = dict(num_kv_blocks=16, max_seq_len=96, token_budget=16)
        ref = drive(mk(model, prefix_cache="on", **kw), dict(prompts), 6)
        eng = mk(model, prefix_cache="on", **kw)
        got = drive(eng, dict(prompts), 6, preempt=(1, 3))
        assert got == ref
        assert eng.request_metrics()["aggregate"]["preemptions"] == 1
        check_allocator(eng)

    def test_seeded_parity_cache_off(self, model):
        """Token-identical even when the re-prefill is a full recompute
        (prefix cache off) and sampling is stochastic."""
        r = np.random.RandomState(5)
        prompts = {0: list(r.randint(1, 128, 10)),
                   1: list(r.randint(1, 128, 14))}
        spr = dict(rng=jax.random.PRNGKey(17))
        kw = dict(num_kv_blocks=16, max_seq_len=96, token_budget=16,
                  prefix_cache="off")
        ref = drive(mk(model, **kw), dict(prompts), 5, **spr)
        got = drive(mk(model, **kw), dict(prompts), 5, preempt=(0, 4),
                    **spr)
        assert got == ref

    def test_policy_preemption_end_to_end(self, model):
        """The scheduler's own preemption (not a forced _preempt): a
        high-tier arrival under pool starvation evicts the low-tier
        victim, both still complete, token accounting stays exact."""
        r = np.random.RandomState(9)
        eng = mk(model, OverloadConfig(preemption=True),
                 num_kv_blocks=6, max_seq_len=48, token_budget=16)
        p0 = list(r.randint(1, 128, 30))
        eng.put(0, p0, priority=5)
        done = {0: [], 1: []}
        fed = False
        for _ in range(60):
            outs = eng.step()
            for uid, tok in outs.items():
                done[uid].append(tok)
                if len(done[uid]) < 4:
                    eng.put(uid, [tok])
                else:
                    eng.flush(uid)
            seq0 = eng.state.seqs.get(0)
            if not fed and seq0 is not None \
                    and seq0.seen_tokens >= len(p0):
                eng.put(1, list(r.randint(1, 128, 20)), priority=0)
                fed = True
            if all(len(v) >= 4 for v in done.values()):
                break
        assert all(len(v) >= 4 for v in done.values())
        assert eng.request_metrics()["aggregate"]["preemptions"] >= 1
        rec = {x["uid"]: x for x in eng.request_metrics()["requests"]}
        tm = eng.timings
        assert sum(x["prompt_tokens"] for x in rec.values()) \
            == int(tm["prompt_tokens"])
        assert sum(x["generated_tokens"] for x in rec.values()) \
            == int(tm["generated_tokens"])
        check_allocator(eng)

    def test_generate_with_bounded_queue(self, model):
        """generate() under a shedding config: shed prompts return empty
        rows, admitted ones complete, nothing hangs."""
        eng = mk(model, OverloadConfig(max_queued_requests=2),
                 num_kv_blocks=16, max_seq_len=96)
        r = np.random.RandomState(11)
        prompts = {u: list(r.randint(1, 128, 6)) for u in range(4)}
        out = eng.generate(prompts, SamplingParams(max_new_tokens=3))
        assert set(out) == set(prompts)
        shed = [u for u in prompts if eng.query(u)["status"] == "shed"]
        assert len(shed) == 2 and all(out[u] == [] for u in shed)
        assert all(len(out[u]) == 3 for u in prompts if u not in shed)
