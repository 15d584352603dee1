"""Property-style fuzz of the SplitFuse scheduler's admission
invariants: across randomized put/schedule/flush interleavings,
``_schedule()`` must never over-commit the token budget, the KV block
pool, or the slot pool — and the batch it admits must always build
without tripping ``build_batch``'s own guards (reference analog:
``can_schedule`` engine_v2.py:184 + SchedulingResult).

With the prefix cache in play (identical-prompt traffic drawn from a
small pool of shared prefixes, plus release/re-admit interleavings) the
accounting invariants get sharper: blocks may be ALIASED across live
sequences (refcount = number of holders), released cached blocks rest
on the cached-free LRU pool, and after every op
``referenced + cached_free + free == total`` must hold exactly —
releasing everything must return the pool to fully reclaimable.

Pure host-side: the engine is constructed but no step is ever
dispatched, so hundreds of scheduler rounds run in milliseconds."""

from collections import Counter

import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.inference.ragged.state import FEEDBACK_TOKEN
from deepspeed_tpu.models import build_model


@pytest.fixture(scope="module")
def model():
    return build_model("llama-tiny", vocab_size=128, num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       max_seq_len=256)


def _check_invariants(eng, sched):
    st = eng.state
    budget = eng.icfg.token_budget
    bs = eng.icfg.kv_block_size
    # 1) token budget
    n_toks = sum(len(t) for _, t in sched)
    assert n_toks <= budget, f"budget over-commit: {n_toks} > {budget}"
    # 2) KV block pool: blocks newly needed by the admitted batch fit
    #    the free pool at admission time
    need = 0
    for uid, toks in sched:
        seq = st.seqs.get(uid)
        seen = seq.seen_tokens if seq else 0
        have = len(seq.blocks) if seq else 0
        need += max(0, -(-(seen + len(toks)) // bs) - have)
    assert need <= st.allocator.free_blocks, \
        f"block over-commit: need {need}, free {st.allocator.free_blocks}"
    # 3) slot pool: new sequences admitted fit the free slots
    new_seqs = {uid for uid, _ in sched if uid not in st._slots}
    assert len(new_seqs) <= len(st._free_slots), \
        f"slot over-commit: {len(new_seqs)} new > {len(st._free_slots)}"
    # 4) per-seq context bound
    for uid, toks in sched:
        seq = st.seqs.get(uid)
        seen = seq.seen_tokens if seq else 0
        assert seen + len(toks) <= st.max_context_tokens


def _check_pool_accounting(eng):
    st = eng.state
    al = st.allocator
    held = Counter(b for seq in st.seqs.values() for b in seq.blocks)
    # no sequence lists a block twice; aliasing ACROSS sequences is the
    # prefix cache working as designed — each holder owns one reference
    for seq in st.seqs.values():
        assert len(seq.blocks) == len(set(seq.blocks)), \
            "block repeated within one sequence"
    for b, holders in held.items():
        assert al.refcount(b) == holders, \
            f"block {b}: refcount {al.refcount(b)} != {holders} holders"
    # the allocator's three pools partition the block space exactly:
    # referenced + cached_free + free == total (no leak, no double-free)
    al.assert_invariants()
    assert al.referenced_blocks == len(held)
    assert al.free_blocks + len(held) == al.total_blocks
    # slots unique and consistent
    slots = list(st._slots.values())
    assert len(slots) == len(set(slots))
    assert len(slots) + len(st._free_slots) == st.max_seqs
    # every queued COW copy belongs to a live sequence and targets a
    # block that sequence actually holds
    for uid, src, dst in st.cow_pending:
        assert uid in st.seqs and dst in st.seqs[uid].blocks
    # the device-telemetry pull-gauges (docs/OBSERVABILITY.md "Device &
    # compiler telemetry") read allocator truth at export time — a
    # scrape after ANY op must equal the reality assert_invariants just
    # validated, or the gauges are lying to the router/autotuner
    snap = eng.metrics_snapshot()
    ps = st.pool_stats()
    assert snap["serving_kv_blocks_referenced"] == ps["referenced"] \
        == al.referenced_blocks
    assert snap["serving_kv_blocks_cached_free"] == ps["cached_free"] \
        == al.cached_free_blocks
    assert snap["serving_kv_blocks_free"] == ps["free"] \
        == al.free_blocks - al.cached_free_blocks
    assert snap["serving_kv_blocks_total"] == al.total_blocks
    assert (snap["serving_kv_blocks_free"]
            + snap["serving_kv_blocks_cached_free"]
            + snap["serving_kv_blocks_referenced"]) == al.total_blocks
    assert snap["serving_kv_blocks_peak_referenced"] \
        == al.peak_referenced_blocks >= al.referenced_blocks
    assert snap["serving_prefix_index_entries"] == len(st._hash_index)


@pytest.mark.parametrize("seed", range(4))
def test_schedule_never_overcommits(model, seed):
    r = np.random.RandomState(seed)
    # deliberately tight pools: 6 blocks of 8 tokens, 3 slots, budget 16
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=16, max_seqs=3, kv_block_size=8, num_kv_blocks=6,
        max_seq_len=48))
    next_uid = 0
    for _ in range(250):
        op = r.randint(4)
        live = list(eng.state.seqs)
        if op == 0:                          # new prompt (any length)
            eng.put(next_uid, list(r.randint(1, 128, r.randint(1, 40))))
            next_uid += 1
        elif op == 1 and live:               # decode continuation
            uid = live[r.randint(len(live))]
            if not eng._pending.get(uid):
                eng.put(uid, [int(r.randint(1, 128))])
        elif op == 2 and live:               # flush a random live seq
            eng.flush(live[r.randint(len(live))])
        else:                                # run the scheduler
            sched = eng._schedule()
            _check_invariants(eng, sched)
            if sched:
                # the admitted batch must build cleanly (allocates the
                # reserved blocks for real)
                eng.state.build_batch(sched, eng.icfg.token_budget,
                                      stager=eng._stager)
        _check_pool_accounting(eng)


@pytest.mark.parametrize("seed", range(4))
def test_prefix_cache_fuzz_invariants(model, seed):
    """Identical-prompt / release / re-admit interleavings under a tight
    pool: matches alias live AND cached-free blocks, full-cover matches
    queue COW copies, flushes retire hashed blocks to the cached-free
    pool, and eviction reclaims them — while after EVERY op refcounts
    equal holder counts, nothing leaks or double-frees, and
    ``referenced + cached_free + free == total``.  Finally releasing
    every sequence returns the pool to fully reclaimable."""
    r = np.random.RandomState(100 + seed)
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=16, max_seqs=3, kv_block_size=8, num_kv_blocks=10,
        max_seq_len=48, prefix_cache="on"))
    # a small pool of shared prefixes => identical-prompt traffic with
    # real hit probability; lengths straddle block boundaries (8) so
    # both block-aligned and full-cover (COW) matches occur
    prefixes = [list(r.randint(1, 128, n)) for n in (8, 16, 17, 24, 12)]
    next_uid = 0
    matched_any = False
    for _ in range(300):
        op = r.randint(5)
        live = list(eng.state.seqs)
        if op == 0:                          # identical-prompt admit
            p = prefixes[r.randint(len(prefixes))]
            tail = list(r.randint(1, 128, r.randint(0, 6)))
            eng.put(next_uid, p + tail)
            next_uid += 1
        elif op == 1 and live:               # decode continuation
            uid = live[r.randint(len(live))]
            if not eng._pending.get(uid):
                eng.put(uid, [int(r.randint(1, 128))])
        elif op == 2 and live:               # release a random live seq
            eng.flush(live[r.randint(len(live))])
        elif op == 3:                        # unique prompt (cache miss
            eng.put(next_uid,                # + eviction pressure)
                    list(r.randint(1, 128, r.randint(1, 40))))
            next_uid += 1
        else:
            sched = eng._schedule()
            _check_invariants(eng, sched)
            if sched:
                eng.state.build_batch(sched, eng.icfg.token_budget,
                                      stager=eng._stager)
            matched_any = matched_any or eng.timings["prefix_hits"] > 0
        _check_pool_accounting(eng)
    assert matched_any, "fuzz never exercised a prefix-cache hit"
    # releasing all sequences must leave every block reclaimable
    for uid in list(eng.state.seqs):
        eng.flush(uid)
    al = eng.state.allocator
    al.assert_invariants()
    assert al.referenced_blocks == 0
    assert al.free_blocks == al.total_blocks
    assert eng.state.cow_pending == []


def test_schedule_feedback_markers_admit_like_decodes(model):
    """Deferred-feedback pendings (the continuations ``step()`` launches
    ahead) schedule exactly like concrete decode tokens.  A marker is
    always owned by the most recent dispatch: ``step()`` reads launch N
    back, which patches its markers concrete, in the call that launches
    N+1."""
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=16, max_seqs=3, kv_block_size=8, num_kv_blocks=6,
        max_seq_len=48))
    eng.put(0, [1, 2, 3])
    sched = eng._schedule()
    eng.state.build_batch(sched, eng.icfg.token_budget)
    eng._pending[0] = [FEEDBACK_TOKEN]
    eng._fb_step[0] = eng._dispatch_seq      # _mark_feedback's contract
    sched = eng._schedule()
    assert sched == [(0, [FEEDBACK_TOKEN])]
    b = eng.state.build_batch(sched, eng.icfg.token_budget)
    assert int(b.feedback_src[0]) == eng.state.slot(0)
    assert int(b.token_ids[0]) == 0          # host stages a benign id
    _check_pool_accounting(eng)


@pytest.mark.parametrize("seed", range(4))
def test_overload_fuzz_invariants(model, seed):
    """Overload-policy ops in the mix (docs/SERVING.md "Surviving
    overload"): mixed-priority puts against a bounded admission queue
    (all three shed policies), deadline puts that expire mid-fuzz,
    client cancels, and scheduler rounds whose starvation handling may
    preempt-by-eviction — after EVERY op the allocator partition
    ``referenced + cached_free + free == total`` holds, refcounts equal
    holder counts, and no lifecycle record leaks open once its request
    left the engine."""
    from deepspeed_tpu.inference.overload import (SHED_POLICIES,
                                                  OverloadConfig)
    r = np.random.RandomState(500 + seed)
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=16, max_seqs=3, kv_block_size=8, num_kv_blocks=6,
        max_seq_len=48, prefix_cache="on",
        overload=OverloadConfig(
            max_queued_requests=4,
            shed_policy=SHED_POLICIES[seed % len(SHED_POLICIES)],
            prefill_chunk=6, preemption=True,
            max_preemptions_per_step=2, aging_ms=50.0)))
    prefixes = [list(r.randint(1, 128, n)) for n in (8, 16, 24)]
    next_uid = 0
    for _ in range(300):
        op = r.randint(7)
        live = list(eng.state.seqs)
        if op == 0:                          # mixed-tier prompt
            p = prefixes[r.randint(len(prefixes))] if r.randint(2) \
                else list(r.randint(1, 128, r.randint(1, 40)))
            eng.put(next_uid, list(p), priority=int(r.randint(0, 4)))
            next_uid += 1
        elif op == 1:                        # doomed: deadline expires
            eng.put(next_uid, list(r.randint(1, 128, r.randint(1, 20))),
                    priority=int(r.randint(0, 4)),
                    deadline_ms=0.0 if r.randint(2) else 10_000.0)
            next_uid += 1
        elif op == 2 and live:               # decode continuation
            uid = live[r.randint(len(live))]
            if not eng._pending.get(uid):
                eng.put(uid, [int(r.randint(1, 128))])
        elif op == 3 and live:               # flush a random live seq
            eng.flush(live[r.randint(len(live))])
        elif op == 4 and next_uid:           # client cancel, any state
            eng.cancel(int(r.randint(next_uid)))
        else:                                # scheduler round
            sched = eng._schedule()
            _check_invariants(eng, sched)
            if sched:
                eng.state.build_batch(sched, eng.icfg.token_budget,
                                      stager=eng._stager)
        _check_pool_accounting(eng)
        # no record leaks: every open lifecycle record belongs to a
        # request that is still queued or live in the engine
        for uid in eng.requests.open:
            assert uid in eng.state.seqs or eng._pending.get(uid) \
                or uid in eng._meta, f"leaked open record for uid {uid}"
    # drain: close every remaining request through its exit path
    eng._drain_reaped()
    for uid in list(eng.requests.open):
        eng.flush(uid)
    al = eng.state.allocator
    al.assert_invariants()
    assert al.referenced_blocks == 0
    assert al.free_blocks == al.total_blocks
    assert not eng.requests.open, "open records after full drain"
    assert eng.state.cow_pending == []
    # the fuzz actually walked the paths under test (every seed does)
    agg = eng.request_metrics()["aggregate"]
    assert agg["preemptions"] > 0, "fuzz never triggered preemption"
    assert agg["statuses"].get("deadline_exceeded", 0) > 0
    assert agg["statuses"].get("cancelled", 0) > 0


@pytest.mark.parametrize("seed,spec", [(0, "on"), (1, "on"), (2, "on"),
                                       (3, "off")])
def test_spec_decode_fuzz_invariants(model, seed, spec):
    """Speculative decoding in the op mix (docs/SERVING.md "Speculative
    decoding"): scheduler rounds mine draft windows that consume REAL
    budget/blocks, and every window is then resolved with a RANDOM
    accepted count — exercising the write-cursor rollback against the
    refcounted/COW allocator after every op.  The partition
    ``referenced + cached_free + free == total`` and the
    refcount==holders invariant must survive arbitrary accept/reject
    splits interleaved with prefix-cache hits, flushes, and cancels
    (``spec="off"`` runs the same trace draft-free as the control)."""
    r = np.random.RandomState(900 + seed)
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=16, max_seqs=3, kv_block_size=8, num_kv_blocks=10,
        max_seq_len=48, prefix_cache="on",
        spec_decode=spec, spec_max_draft=3))
    prefixes = [list(r.randint(1, 128, n)) for n in (8, 16, 24)]
    next_uid = 0
    drafted = rolled = 0
    for _ in range(300):
        op = r.randint(6)
        live = list(eng.state.seqs)
        if op == 0:                          # repetitive prompt (the
            p = prefixes[r.randint(len(prefixes))]   # proposer's food)
            eng.put(next_uid, list(p) + list(p[:r.randint(1, 6)]))
            next_uid += 1
        elif op == 1 and live:               # decode continuation
            uid = live[r.randint(len(live))]
            if not eng._pending.get(uid):
                # half the feeds repeat the request's own prefix tokens
                # so the n-gram index actually matches
                seq = eng.state.seqs[uid]
                tok = int(seq.chain[r.randint(len(seq.chain))]) \
                    if seq.chain and r.randint(2) \
                    else int(r.randint(1, 128))
                eng.put(uid, [tok])
        elif op == 2 and live:               # flush a random live seq
            eng.flush(live[r.randint(len(live))])
        elif op == 3 and next_uid:           # client cancel, any state
            eng.cancel(int(r.randint(next_uid)))
        else:                                # scheduler round
            sched = eng._schedule()
            _check_invariants(eng, sched)
            if sched:
                eng.state.build_batch(
                    sched, eng.icfg.token_budget, stager=eng._stager,
                    draft_lens={u: len(d) for u, d
                                in eng._sched_drafts.items()},
                    n_verify=eng._n_verify)
                # host-only fuzz: no step is dispatched, so play the
                # engine collect's role — resolve every draft window
                # with a random accepted prefix length (rollback path)
                for uid, d in eng._sched_drafts.items():
                    if uid in eng.state.seqs:
                        drafted += len(d)
                        rolled += eng.state.resolve_draft(
                            uid, int(r.randint(0, len(d) + 1)))
        _check_pool_accounting(eng)
        for uid, seq in eng.state.seqs.items():
            assert seq.draft_len == 0, \
                f"uid {uid}: unresolved draft window leaked"
    for uid in list(eng.state.seqs):
        eng.flush(uid)
    al = eng.state.allocator
    al.assert_invariants()
    assert al.referenced_blocks == 0
    assert al.free_blocks == al.total_blocks
    if spec == "on":                # the fuzz walked the new path
        assert drafted > 0, "fuzz never scheduled a draft window"
        assert rolled > 0, "fuzz never rolled back a rejected draft"
    else:
        assert drafted == 0


@pytest.mark.parametrize("seed", range(4))
def test_failure_fuzz_invariants(model, seed):
    """Crash/hang ops in the mix (docs/SERVING.md "Failure domains &
    recovery"), injected at the failure classifier seam: scheduler
    rounds build their batch and then FAIL — a synthetic crash
    (poison-for-step: re-queue + bisection quarantine) or a watchdog
    expiry (retry, escalating to engine-dead, which the fuzz answers
    with snapshot() -> restore() and keeps going).  After EVERY op the
    allocator partition ``referenced + cached_free + free == total``
    holds, refcounts equal holder counts, failed-step prefix-index
    registrations are withdrawn (no hash may promise never-written
    KV), and open lifecycle records ⊆ live + queued — no failure path
    leaks."""
    from deepspeed_tpu.inference import (EngineDeadError, FailureConfig,
                                         InferenceConfig, InjectedFault)
    from deepspeed_tpu.inference.failures import DispatchTimeoutError

    def build():
        return InferenceEngine(model, InferenceConfig(
            token_budget=16, max_seqs=3, kv_block_size=8, num_kv_blocks=8,
            max_seq_len=48, prefix_cache="on",
            failure=FailureConfig(dispatch_timeout_ms=None)))

    r = np.random.RandomState(1300 + seed)
    eng = build()
    prefixes = [list(r.randint(1, 128, n)) for n in (8, 16, 24)]
    next_uid = 0
    failures = deaths = 0
    for _ in range(300):
        op = r.randint(7)
        live = list(eng.state.seqs)
        if op == 0:                          # prompt (shared or unique)
            p = prefixes[r.randint(len(prefixes))] if r.randint(2) \
                else list(r.randint(1, 128, r.randint(1, 30)))
            eng.put(next_uid, list(p))
            next_uid += 1
        elif op == 1 and live:               # decode continuation
            uid = live[r.randint(len(live))]
            if not eng._pending.get(uid):
                eng.put(uid, [int(r.randint(1, 128))])
        elif op == 2 and live:               # flush a random live seq
            eng.flush(live[r.randint(len(live))])
        elif op == 3 and next_uid:           # client cancel, any state
            eng.cancel(int(r.randint(next_uid)))
        elif op in (4, 5):                   # FAILING scheduler round
            sched = eng._schedule()
            if sched:
                eng.state.build_batch(sched, eng.icfg.token_budget,
                                      stager=eng._stager)
                exc = InjectedFault("crash") if op == 4 \
                    else DispatchTimeoutError("injected hang")
                try:
                    eng._handle_step_failure(
                        exc, tuple(u for u, _ in sched), "dispatch")
                    failures += 1
                except EngineDeadError:
                    # the warm-restart loop: host truth -> new engine
                    deaths += 1
                    eng = InferenceEngine.restore(model, eng.snapshot(),
                                                  eng.icfg)
        else:                                # clean scheduler round
            sched = eng._schedule()
            _check_invariants(eng, sched)
            if sched:
                eng.state.build_batch(sched, eng.icfg.token_budget,
                                      stager=eng._stager)
                # the fuzz never dispatches, so play collect's success
                # role for the escalation counters (a real step resets
                # them at its readback)
                eng._consec_failures = 0
                eng._consec_timeouts = 0
        _check_pool_accounting(eng)
        # failed-step registrations must be withdrawn: every index
        # entry points at a block some live sequence actually holds or
        # that rests in the cached-free pool
        for h, b in eng.state._hash_index.items():
            assert eng.state.allocator.refcount(b) > 0 \
                or eng.state.allocator.is_cached(b)
        for uid in eng.requests.open:
            assert uid in eng.state.seqs or eng._pending.get(uid) \
                or uid in eng._meta, f"leaked open record for uid {uid}"
    assert failures > 0, "fuzz never exercised the classifier seam"
    if deaths == 0:
        # the random walk produced no two CONSECUTIVE expiries this
        # seed: drive the escalation deterministically so every seed
        # covers timeout -> timeout -> dead -> snapshot -> restore
        eng.put(next_uid, [1, 2, 3])
        next_uid += 1
        rounds = (eng.fcfg.fatal_timeouts + 2) \
            * (eng.fcfg.max_backoff_rounds + 2)
        for _ in range(rounds):
            sched = eng._schedule()
            if not sched:       # backoff rounds admit nothing
                continue
            eng.state.build_batch(sched, eng.icfg.token_budget,
                                  stager=eng._stager)
            try:
                eng._handle_step_failure(
                    DispatchTimeoutError("injected hang"),
                    tuple(u for u, _ in sched), "dispatch")
            except EngineDeadError:
                deaths += 1
                eng = InferenceEngine.restore(model, eng.snapshot(),
                                              eng.icfg)
                break
        _check_pool_accounting(eng)
    assert deaths > 0, "fuzz never exercised the warm-restart path"
    # drain: every remaining request closes through a real exit path
    eng._drain_reaped()
    for uid in list(eng.requests.open):
        eng.flush(uid)
    al = eng.state.allocator
    al.assert_invariants()
    assert al.referenced_blocks == 0
    assert al.free_blocks == al.total_blocks
    assert not eng.requests.open, "open records after full drain"


@pytest.mark.parametrize("seed", range(3))
def test_fleet_fuzz_invariants(model, seed):
    """Fleet-op fuzz (docs/SERVING.md "Fleet: routing, failover,
    migration"), host-only like the other seeds: random puts routed by
    affinity over 3 tiny replicas interleaved with per-replica
    scheduler rounds, replica KILLS (host-marked dead -> failover
    migration) answered by fresh scale-ups, targeted live MIGRATIONS,
    breaker QUARANTINE/probe walks, flushes and cancels — asserting
    after EVERY op that each live replica's allocator partition and
    refcounts hold, no lifecycle record leaks, and every fleet-open
    request is owned by exactly ONE live replica (migration can never
    double-run a request).  At the end everything closes through a
    real exit path: no request the fleet admitted is ever lost."""
    from deepspeed_tpu.serving import FleetConfig, FleetRouter
    from tools.loadgen import check_fleet_invariants

    r = np.random.RandomState(1700 + seed)

    def build():
        return InferenceEngine(model, InferenceConfig(
            token_budget=16, max_seqs=3, kv_block_size=8,
            num_kv_blocks=10, max_seq_len=48, prefix_cache="on"))

    router = FleetRouter({f"r{i}": build() for i in range(3)},
                         FleetConfig(failure_threshold=2,
                                     probe_interval_steps=2,
                                     max_migration_retries=4))
    prefixes = [list(r.randint(1, 128, n)) for n in (8, 16, 24)]
    next_uid = 0
    spawned = 3
    kills = migrations = 0
    admitted: set = set()

    def live_reps():
        return [n for n in router.replica_names
                if not router.replica(n).dead]

    def check():
        # the shared fleet chaos bar (ownership uniqueness, no record
        # leaks, allocator partition, owner map never dead) ...
        check_fleet_invariants(router)
        # ... plus this fuzz's deeper per-engine accounting
        for name in live_reps():
            _check_pool_accounting(router.replica(name).engine)

    for _ in range(250):
        op = r.randint(10)
        router._steps += 1        # host-only: advance the step clock
        if op in (0, 1):                     # routed put (shared/unique)
            p = prefixes[r.randint(len(prefixes))] if r.randint(2) \
                else list(r.randint(1, 128, r.randint(1, 30)))
            v = router.put(next_uid, list(p),
                           priority=int(r.randint(0, 3)))
            if v.admitted:
                admitted.add(next_uid)
            next_uid += 1
        elif op == 2 and router._owner:      # decode continuation
            uid = sorted(router._owner)[r.randint(len(router._owner))]
            owner = router._owner[uid]
            if not router.replica(owner).engine._pending.get(uid):
                router.put(uid, [int(r.randint(1, 128))])
        elif op == 3 and router._owner:      # flush a random open req
            uid = sorted(router._owner)[r.randint(len(router._owner))]
            router.flush(uid)
        elif op == 4 and next_uid:           # cancel, any state
            router.cancel(int(r.randint(next_uid)))
        elif op == 5 and len(live_reps()) > 1 and kills < 4:
            # KILL: host-marked dead (no dispatch in this fuzz), the
            # router fails over its open work, a fresh replica joins
            name = live_reps()[r.randint(len(live_reps()))]
            router.replica(name).engine._health = "dead"
            router._failover(name)
            kills += 1
            router.add_replica(f"s{spawned}", build())
            spawned += 1
        elif op == 6 and router._owner:      # targeted live migration
            uid = sorted(router._owner)[r.randint(len(router._owner))]
            owner = router._owner[uid]
            eng = router.replica(owner).engine
            if uid in eng.state.seqs:
                migrations += router.migrate([uid], owner)
        elif op == 7:                        # breaker quarantine walk
            name = live_reps()[r.randint(len(live_reps()))]
            b = router.replica(name).breaker
            for _ in range(b.threshold):
                b.record_failure(router._steps)
            assert not b.routable
        else:                                # scheduler round, 1 replica
            name = live_reps()[r.randint(len(live_reps()))]
            eng = router.replica(name).engine
            sched = eng._schedule()
            _check_invariants(eng, sched)
            if sched:
                eng.state.build_batch(sched, eng.icfg.token_budget,
                                      stager=eng._stager)
        # probe/re-admit pass + migration pump ride the step clock
        for name in live_reps():
            b = router.replica(name).breaker
            b.tick(router._steps)
            if b.state == "half_open" and r.randint(2):
                b.record_success()           # a clean probe
        router._pump_migrations()
        check()
    assert kills > 0, "fuzz never killed a replica"
    assert migrations > 0, "fuzz never live-migrated a request"
    # close out: every open request finishes through a real exit path,
    # and every admitted request reached exactly one terminal status
    for uid in list(router._owner):
        router.flush(uid)
    deadline = 0
    while router._migrations:
        deadline += 1
        assert deadline < 200, "migration queue never drained"
        router._steps += 1
        for name in live_reps():
            b = router.replica(name).breaker
            b.tick(router._steps)
            if b.state == "half_open":
                b.record_success()
        router._pump_migrations()
    for uid in list(router._owner):
        router.flush(uid)
    router.drain_reaped()
    for name in live_reps():
        eng = router.replica(name).engine
        for uid in list(eng.requests.open):
            eng.flush(uid)
        al = eng.state.allocator
        al.assert_invariants()
        assert al.referenced_blocks == 0
        assert al.free_blocks == al.total_blocks
    for uid in admitted:
        s = router.query(uid)["status"]
        assert s in ("finished", "shed", "cancelled", "released",
                     "failed", "deadline_exceeded",
                     "context_exhausted", "forgotten"), \
            f"uid {uid} lost with status {s!r}"
    # the fleet observability reconciliation bar, one last time after
    # the full drain (check() held it after every op too): the
    # migration-deduped request_metrics token sums equal the
    # per-replica counter sums and the record-derived terminal
    # statuses equal the counter-derived reconciled rollup — the
    # shed/migrated double counting PR 13 documented stays reconciled
    # out through every kill/migrate/quarantine interleaving
    check_fleet_invariants(router)


def test_preempt_resume_prefix_cache_parity(model):
    """Seeded-sampling parity across preemption-by-eviction WITH the
    prefix cache doing the resume: the victim's evicted blocks retire
    to the cached-free pool, the re-prefill aliases them back, and the
    (uid, position)-folded sampling keys make the resumed stream
    token-identical to an undisturbed run — eviction is invisible in
    the output."""
    import jax

    r = np.random.RandomState(41)
    prompts = {0: list(r.randint(1, 128, 13)),
               1: list(r.randint(1, 128, 10))}

    def drive(preempt_at=None):
        eng = InferenceEngine(model, InferenceConfig(
            token_budget=16, max_seqs=3, kv_block_size=8,
            num_kv_blocks=16, max_seq_len=96, prefix_cache="on"))
        for uid, p in prompts.items():
            eng.put(uid, list(p))
        done = {u: [] for u in prompts}
        active = set(prompts)
        rng = jax.random.PRNGKey(23)
        sp = SamplingParams(temperature=0.8, top_k=40)
        n = 0
        while active:
            outs = eng.step(rng=rng, sampling=sp)
            for uid, tok in (outs or {}).items():
                if uid not in active:
                    continue
                done[uid].append(tok)
                if len(done[uid]) >= 6:
                    active.discard(uid)
                    eng.flush(uid)
                else:
                    eng.put(uid, [tok])
            n += 1
            if preempt_at is not None and n == preempt_at \
                    and 0 in eng.state.seqs:
                eng._preempt(0)
            assert n < 200, "parity drive did not terminate"
        return done, eng

    ref, _ = drive()
    got, eng = drive(preempt_at=3)
    assert got == ref, "preempt-then-resume diverged from undisturbed run"
    assert eng.request_metrics()["aggregate"]["preemptions"] == 1
    # the resume really came from the cache, not a cold re-prefill
    rec = {x["uid"]: x for x in eng.request_metrics()["requests"]}
    assert rec[0]["cached_tokens"] > 0
    _check_pool_accounting(eng)


def _check_tier_accounting(eng):
    """The sharper partition with the KV tier in play
    (docs/KV_TIERING.md): every pending-restage destination block is
    referenced at refcount 1 but held by NO sequence, the restage
    bookkeeping mirrors the queue exactly, and the tier counters obey
    their consistency bounds (a revive never outruns a demotion, a
    remote revive never outruns an imported record)."""
    st = eng.state
    al = st.allocator
    held = Counter(b for seq in st.seqs.values() for b in seq.blocks)
    pend = [ent.dst for ent in st.tier_pending_restage]
    assert len(pend) == len(set(pend)), "restage dst handed out twice"
    assert not set(pend) & set(held), "restage dst aliased by a live seq"
    for b in pend:
        assert al.refcount(b) == 1, \
            f"restage dst {b}: refcount {al.refcount(b)} != 1"
    al.assert_invariants()
    assert al.referenced_blocks == len(held) + len(pend)
    per_uid = Counter(ent.uid for ent in st.tier_pending_restage)
    assert dict(per_uid) == st._restaging_uids, \
        "restaging-uid ledger diverged from the restage queue"
    tm = eng.timings
    assert tm["kv_tier_revives_ram"] + tm["kv_tier_revives_nvme"] \
        <= tm["kv_tier_demotions"]
    assert tm["kv_tier_revives_remote"] <= tm["kv_tier_remote_blocks"]


@pytest.mark.parametrize("seed", range(3))
def test_tier_fuzz_invariants(model, seed):
    """The prefix-cache fuzz extended across the tier boundary on a
    PAIR of engines: identical-prompt admits, releases, eviction
    pressure, scheduler rounds, the engine's own demote/restage drains,
    and cross-replica record fetches (``export_tier_chain`` ->
    ``load_snapshot(merge=True)``, the fleet path) interleave randomly
    — and after every op the allocator partition still holds on both
    engines, no block is double-freed or resurrected, a consumed tier
    entry never revives twice, and flushing everything at the end
    returns both pools to fully reclaimable."""
    r = np.random.RandomState(500 + seed)

    def mk():
        return InferenceEngine(model, InferenceConfig(
            token_budget=16, max_seqs=3, kv_block_size=8,
            num_kv_blocks=8, max_seq_len=96, prefix_cache="on",
            kv_tier="on", kv_tier_ram_mb=64.0))

    engs = [mk(), mk()]
    prefixes = [list(r.randint(1, 128, n)) for n in (16, 17, 24, 32)]
    next_uid = 0
    fetched = False
    for _ in range(300):
        eng = engs[r.randint(2)]
        op = r.randint(6)
        live = list(eng.state.seqs)
        if op == 0:                          # identical-prompt admit
            p = prefixes[r.randint(len(prefixes))]
            tail = list(r.randint(1, 128, r.randint(0, 6)))
            eng.put(next_uid, p + tail)
            next_uid += 1
        elif op == 1 and live:               # decode continuation
            uid = live[r.randint(len(live))]
            if not eng._pending.get(uid):
                eng.put(uid, [int(r.randint(1, 128))])
        elif op == 2 and live:               # release a random live seq
            eng.flush(live[r.randint(len(live))])
        elif op == 3:                        # unique prompt => eviction
            eng.put(next_uid,                # pressure => demotions
                    list(r.randint(1, 128, r.randint(1, 40))))
            next_uid += 1
        elif op == 4:                        # scheduler round
            sched = eng._schedule()
            _check_invariants(eng, sched)
            if sched:
                eng.state.build_batch(sched, eng.icfg.token_budget,
                                      stager=eng._stager)
        else:                                # cross-replica tier fetch
            src, dst = engs if r.randint(2) else engs[::-1]
            ds = list(src.state.tier.digests())
            if ds:
                payload = src.export_tier_chain(
                    ds[:1 + r.randint(min(3, len(ds)))])
                if payload is not None:
                    dst.load_snapshot(payload, merge=True)
                    fetched = True
        # mid-flight check (restage dsts referenced but seq-less), then
        # the engine's own idle-path drains, then the stock partition
        _check_tier_accounting(eng)
        for e in engs:
            e._drain_tier_demote()
            e._drain_cow()
            e._drain_tier_restage(dispatching=False)
            _check_tier_accounting(e)
            _check_pool_accounting(e)
    assert any(e.timings["kv_tier_demotions"] > 0 for e in engs), \
        "fuzz never demoted a block into the tier"
    assert any(e.timings["kv_tier_revives_ram"]
               + e.timings["kv_tier_revives_remote"] > 0
               for e in engs), "fuzz never revived a tiered block"
    assert fetched, "fuzz never exercised the cross-replica fetch path"
    for e in engs:
        assert e.timings["kv_tier_verify_failures"] == 0
        for uid in list(e.state.seqs):
            e.flush(uid)
        e._drain_tier_demote()
        e._drain_cow()
        e._drain_tier_restage(dispatching=False)
        al = e.state.allocator
        al.assert_invariants()
        assert al.referenced_blocks == 0
        assert al.free_blocks == al.total_blocks
        assert e.state._restaging_uids == {}
        assert e.state.tier_pending_restage == []
