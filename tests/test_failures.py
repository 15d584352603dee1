"""Failure-domain layer (inference/failures.py + the engine recovery
paths — docs/SERVING.md "Failure domains & recovery"): classifier
units, the watchdog's real deadline thread, crash/poison/timeout
recovery with exact token parity, engine snapshot + warm restart,
health states, graceful drain, and the status-retention satellite.

Everything host-heavy runs on tiny CPU engines; the only real sleeping
happens in the two watchdog deadline tests (sub-second)."""

import threading
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import (DispatchTimeoutError, EngineDeadError,
                                     FailureConfig, InferenceConfig,
                                     InferenceEngine, InjectedFault,
                                     OverloadConfig, SamplingParams,
                                     classify_failure)
from deepspeed_tpu.inference.failures import (FATAL_ENGINE, POISON_STEP,
                                              RETRY_STEP, FailurePolicy,
                                              Watchdog, bisect_groups)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.telemetry.lifecycle import TERMINAL_STATUSES


@pytest.fixture(scope="module")
def model():
    return build_model("llama-tiny", vocab_size=128, num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       max_seq_len=256)


def make_engine(model, **kw):
    icfg = dict(token_budget=32, max_seqs=4, kv_block_size=8,
                num_kv_blocks=24, max_seq_len=96)
    icfg.update(kw)
    return InferenceEngine(model, InferenceConfig(**icfg))


def drive(eng, prompts, n_tok=5, sampling=None, rng=None,
          on_step=None, on_dead=None):
    """step()-API serving loop: feed sampled tokens back, flush at
    ``n_tok``; ``on_step(eng, i)`` fires before each step; ``on_dead``
    maps an EngineDeadError to a replacement engine (warm restart)."""
    sampling = sampling or SamplingParams(max_new_tokens=1 << 30)
    done = {u: [] for u in prompts}
    for u, p in prompts.items():
        eng.put(u, list(p))
    active = set(prompts)
    n = 0
    while active:
        n += 1
        assert n < 500, f"drive wedged with {active}"
        if on_step is not None:
            on_step(eng, n)
        try:
            outs = eng.step(rng=rng, sampling=sampling)
        except EngineDeadError:
            assert on_dead is not None, "engine died without a handler"
            eng = on_dead(eng)
            continue
        active -= eng._drain_reaped()
        for u, t in outs.items():
            if u not in active:
                continue
            done[u].append(t)
            if len(done[u]) >= n_tok:
                active.discard(u)
                eng.flush(u)
            else:
                eng.put(u, [t])
    return done, eng


# --------------------------------------------------------------------------
# classifier units
# --------------------------------------------------------------------------

class TestClassifier:
    def test_injected_kinds(self):
        assert classify_failure(InjectedFault("crash")) == POISON_STEP
        assert classify_failure(InjectedFault("oom")) == POISON_STEP
        assert classify_failure(InjectedFault("transient")) == RETRY_STEP
        assert classify_failure(InjectedFault("fatal")) == FATAL_ENGINE

    def test_timeout_escalates_to_fatal(self):
        cfg = FailureConfig(fatal_timeouts=2)
        e = DispatchTimeoutError("deadline")
        assert classify_failure(e, consecutive_timeouts=1,
                                cfg=cfg) == RETRY_STEP
        assert classify_failure(e, consecutive_timeouts=2,
                                cfg=cfg) == FATAL_ENGINE

    def test_device_errors_classified_by_message(self):
        oom = jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 2.0G")
        assert classify_failure(oom) == POISON_STEP
        dead = jax.errors.JaxRuntimeError("ABORTED: device halted")
        assert classify_failure(dead) == FATAL_ENGINE
        odd = jax.errors.JaxRuntimeError("INTERNAL: something odd")
        assert classify_failure(odd, attempt=0) == RETRY_STEP
        # unrecognized transients escalate to poison after the retry cap
        assert classify_failure(
            odd, attempt=FailureConfig().max_step_retries) == POISON_STEP

    def test_host_bugs_are_not_a_failure_domain(self):
        assert classify_failure(ValueError("bad arg")) is None
        assert classify_failure(KeyError(3)) is None

    def test_config_validation(self):
        with pytest.raises(ValueError):
            FailureConfig(dispatch_timeout_ms=-5)
        with pytest.raises(ValueError):
            FailureConfig(fatal_timeouts=0)
        with pytest.raises(ValueError):
            OverloadConfig(status_retention=0)

    def test_bisect_groups(self):
        assert bisect_groups([1]) == []
        assert bisect_groups([1, 2]) == [[1], [2]]
        assert bisect_groups([1, 2, 3, 4, 5]) == [[1, 2], [3, 4, 5]]


# --------------------------------------------------------------------------
# watchdog
# --------------------------------------------------------------------------

class TestWatchdog:
    def test_inline_when_unbounded(self):
        wd = Watchdog()
        assert wd.run(lambda: 41 + 1, None) == 42
        assert wd._thread is None          # no worker was ever spawned

    def test_fast_call_passes_value_and_exception(self):
        wd = Watchdog()
        assert wd.run(lambda: "ok", 1000.0) == "ok"
        with pytest.raises(ZeroDivisionError):
            wd.run(lambda: 1 // 0, 1000.0)

    def test_deadline_expiry_raises_and_recovers(self):
        wd = Watchdog()
        with pytest.raises(DispatchTimeoutError):
            wd.run(lambda: time.sleep(0.4), 40.0)
        assert wd.abandoned == 1
        # a fresh worker serves the next call; a stale late result from
        # the abandoned one can never be mistaken for this call's
        assert wd.run(lambda: "alive", 1000.0) == "alive"

    def test_hand_off_is_stamped_in_order(self):
        """Four stamps per guarded call: put, taken by the worker, fn
        returned, result taken back, handed over in the caller's dict
        with the worker's usage.  ``hop_us`` is the two hand-overs,
        never the call itself; the watchdog judges none of them (a slow
        ROUND is the engine's to note, ``slow_round``); an inline call
        has no hop."""
        notes = []
        wd = Watchdog(on_note=lambda kind, **info: notes.append((kind, info)))
        stamps = {}
        assert wd.run(lambda: time.sleep(0.05) or "ok", 400.0,
                      site="collect", sid=7, stamps=stamps) == "ok"
        # 50 ms is over a tenth of the 400 ms deadline: no note of the
        # watchdog's own, however long the call
        assert notes == []
        assert sorted(stamps) == ["cpu_us", "fn_us", "hop_us", "ivcsw",
                                  "queued_us", "taken_us", "vcsw"]
        assert stamps["queued_us"] >= 0.0 and stamps["taken_us"] >= 0.0
        assert stamps["fn_us"] >= 50e3
        # the worker slept through fn(): no CPU to speak of, one sleep
        assert stamps["cpu_us"] < 25e3 and stamps["vcsw"] >= 1
        # the hop is the two hand-overs, not the 50 ms of fn()
        assert stamps["queued_us"] + stamps["taken_us"] \
            == pytest.approx(stamps["hop_us"], abs=1.0)
        assert stamps["hop_us"] + stamps["fn_us"] < 400e3
        assert wd.worker_tid > 0
        # the record a slow round takes these into is named slow_round
        from deepspeed_tpu.inference.failures import SLOW_ROUND
        assert SLOW_ROUND == "slow_round"
        assert wd.run(lambda: 1, 60_000.0, stamps=stamps) == 1
        assert notes == [] and stamps["hop_us"] >= 0.0
        inline = {}
        assert wd.run(lambda: 2, None, stamps=inline) == 2
        assert inline == {}

    def test_abandoned_worker_says_when_its_call_came_back(self):
        """Expiry abandons the worker; when the stuck call finally
        returns, the worker leaves one record: how long after the
        deadline, and how long fn() itself took."""
        notes = []
        wd = Watchdog(on_note=lambda kind, **info: notes.append((kind, info)))
        with pytest.raises(DispatchTimeoutError):
            wd.run(lambda: time.sleep(0.25), 50.0, site="dispatch", sid=3)
        assert notes == []                    # nothing came back yet
        deadline = time.time() + 5.0
        while not notes and time.time() < deadline:
            time.sleep(0.01)
        (kind, info), = notes
        assert kind == "guard_late_return"
        assert (info["site"], info["sid"]) == ("dispatch", 3)
        assert info["fn_ms"] >= 240.0 and info["late_ms"] >= 150.0
        assert info["queued_ms"] >= 0.0

    def test_concurrent_guarded_calls_are_serialized(self):
        """Regression (tpulint v3 hardening): two threads sharing one
        watchdog must not interleave tokens on the single (req, res)
        queue pair — the admission lock serializes guarded episodes, so
        every caller gets its own result and no worker is abandoned."""
        wd = Watchdog()
        results: dict = {}

        def guarded(i):
            # a raise here leaves results[i] unset -> the assert fails
            results[i] = wd.run(lambda: i * 10, 1000.0)

        threads = [threading.Thread(target=guarded, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == {i: i * 10 for i in range(8)}
        assert wd.abandoned == 0

    def test_expiry_concurrent_with_fast_call(self):
        """Regression: an expiry racing another guarded call may only
        tear down ITS OWN worker — the racing call still completes and
        exactly one worker is abandoned."""
        wd = Watchdog()

        def slow():
            with pytest.raises(DispatchTimeoutError):
                wd.run(lambda: time.sleep(0.3), 30.0)

        t = threading.Thread(target=slow)
        t.start()
        assert wd.run(lambda: "ok", 1000.0) == "ok"
        t.join()
        assert wd.abandoned == 1
        # and the shared watchdog still serves fresh calls afterwards
        assert wd.run(lambda: "alive", 1000.0) == "alive"

    def test_auto_deadline_warmup_and_scaling(self):
        cfg = FailureConfig(watchdog_warmup_steps=4,
                            auto_timeout_floor_ms=100.0,
                            auto_timeout_scale=3.0)
        tm = {"steps": 0, "device_ms": 0.0, "wait_ms": 0.0}
        pol = FailurePolicy(cfg, tm)
        assert pol.deadline_ms() is None       # calibrating: unguarded
        tm.update(steps=10, device_ms=400.0, wait_ms=100.0)  # 50 ms/step
        assert pol.deadline_ms() == pytest.approx(150.0)
        tm.update(device_ms=40.0, wait_ms=10.0)              # 5 ms/step
        assert pol.deadline_ms() == pytest.approx(100.0)     # floor
        off = FailurePolicy(FailureConfig(dispatch_timeout_ms=None), tm)
        assert off.deadline_ms() is None
        fixed = FailurePolicy(FailureConfig(dispatch_timeout_ms=77.0), tm)
        assert fixed.deadline_ms() == 77.0

    def test_real_hang_caught_end_to_end(self, model):
        """A genuinely stalled dispatch (injected sleep) trips the REAL
        watchdog thread, classifies as retryable, and the requests
        still finish with the right number of tokens.  The engine is
        warmed first so compiles (legitimately slow) never race the
        fixed deadline — only the injected stall outlives it."""
        eng = make_engine(model, failure=FailureConfig(
            dispatch_timeout_ms=150.0))
        prompts = {0: [1, 2, 3, 4], 1: [5, 6, 7]}
        drive(eng, prompts, n_tok=6)          # compile both buckets
        eng.reset_metrics()

        def arm(e, i):
            if i == 2:
                e.failures.inject("hang")
        done, eng = drive(eng, prompts, n_tok=6, on_step=arm)
        assert all(len(v) == 6 for v in done.values())
        assert int(eng.timings["step_retries"]) >= 1
        assert eng.failures.watchdog.abandoned >= 1
        # the abandoned worker's call came back 4 deadlines later: one
        # breadcrumb says so, with the call site and the step
        deadline = time.time() + 5.0
        late = []
        while not late and time.time() < deadline:
            late = [e for e in eng.flight.events()
                    if e["kind"] == "guard_late_return"]
            time.sleep(0.01)
        assert late and late[0]["site"] in ("dispatch", "collect")
        assert late[0]["late_ms"] > 0 and late[0]["fn_ms"] >= 4 * 150.0
        assert late[0]["sid"] is not None
        # and the hops of the guarded calls were counted
        assert eng.metrics.get("serving_guard_hop_ms_total").value() > 0.0


# --------------------------------------------------------------------------
# recovery: crash, poison quarantine, timeout -> dead -> warm restart
# --------------------------------------------------------------------------

class TestRecovery:
    def _prompts(self, n=4):
        r = np.random.RandomState(1)
        return {u: list(r.randint(1, 128, 8 + u)) for u in range(n)}

    def test_crash_recovery_token_parity(self, model):
        prompts = self._prompts()
        ref, _ = drive(make_engine(model), prompts)
        eng = make_engine(model)

        def arm(e, i):
            if i == 3:
                e.failures.inject("crash")
        got, eng = drive(eng, prompts, on_step=arm)
        assert got == ref, "crash re-queue diverged from fault-free run"
        assert int(eng.timings["step_retries"]) >= 1
        assert int(eng.timings["requests_failed"]) == 0
        eng.state.allocator.assert_invariants()
        al = eng.state.allocator
        assert al.free_blocks == al.total_blocks

    @pytest.mark.parametrize("cache", ["on", "off"])
    def test_poison_quarantined_innocents_exact(self, model, cache):
        """A request whose every batch crashes is bisected down to a
        singleton probe and closed ``failed``; every innocent neighbor
        keeps exact greedy parity with a fault-free run."""
        prompts = self._prompts()
        ref, _ = drive(make_engine(model, prefix_cache=cache), prompts)
        eng = make_engine(model, prefix_cache=cache)
        eng.failures.inject("crash", uid=2, n=1 << 20)
        got, eng = drive(eng, prompts)
        assert eng.query(2)["status"] == "failed"
        assert all(got[u] == ref[u] for u in (0, 1, 3))
        assert int(eng.timings["requests_failed"]) == 1
        agg = eng.request_metrics()["aggregate"]
        assert agg["open"] == 0
        assert agg["statuses"] == {"failed": 1, "finished": 3}
        assert agg["retries"] > 0          # innocents rode re-queues
        al = eng.state.allocator
        al.assert_invariants()
        assert al.free_blocks == al.total_blocks

    def test_transient_mid_quarantine_keeps_isolation(self, model):
        """A retryable failure (watchdog expiry) landing DURING the
        bisection quarantine must not dissolve the probe group — the
        poison request still ends ``failed`` and every innocent keeps
        exact parity, with no spurious ``failed`` closures."""
        prompts = self._prompts()
        ref, _ = drive(make_engine(model), prompts)
        eng = make_engine(model)
        eng.failures.inject("crash", uid=2, n=1 << 20)

        def arm(e, i):
            # fire a transient expiry while probes are (or are about
            # to be) in flight (a second consecutive one would
            # legitimately kill the engine — fatal_timeouts=2)
            if i == 3:
                e.failures.inject("timeout")
        got, eng = drive(eng, prompts, on_step=arm)
        assert eng.query(2)["status"] == "failed"
        assert all(got[u] == ref[u] for u in (0, 1, 3))
        assert int(eng.timings["requests_failed"]) == 1
        agg = eng.request_metrics()["aggregate"]
        assert agg["statuses"] == {"failed": 1, "finished": 3}

    def test_timeouts_escalate_to_dead_then_restore_seeded(self, model):
        """Repeated watchdog expiries kill the engine; snapshot() +
        restore() resumes mid-flight work token-identically under
        SEEDED sampling (the (uid, position)-folded keys make resume
        restart-invariant)."""
        prompts = self._prompts(3)
        sp = SamplingParams(temperature=0.8, top_k=40,
                            max_new_tokens=1 << 30)
        key = jax.random.PRNGKey(7)
        fcfg = FailureConfig(fatal_timeouts=1)
        ref, _ = drive(make_engine(model, failure=fcfg), prompts,
                       sampling=sp, rng=key)
        eng = make_engine(model, failure=fcfg)
        deaths = []

        def arm(e, i):
            if i == 3:
                e.failures.inject("timeout")

        def on_dead(old):
            deaths.append(old.health()["state"])
            return InferenceEngine.restore(model, old.snapshot(),
                                           old.icfg)
        got, eng = drive(eng, prompts, sampling=sp, rng=key,
                         on_step=arm, on_dead=on_dead)
        assert deaths == ["dead"]
        assert got == ref, "death + warm restart changed the streams"
        agg = eng.request_metrics()["aggregate"]
        assert agg["open"] == 0

    def test_dead_engine_refuses_work_but_snapshots(self, model):
        eng = make_engine(model, failure=FailureConfig(fatal_timeouts=1))
        eng.put(0, [1, 2, 3])
        eng.failures.inject("timeout")
        with pytest.raises(EngineDeadError):
            eng.step()
        assert eng.health()["state"] == "dead"
        with pytest.raises(EngineDeadError):
            eng.step()
        v = eng.put(99, [4, 5])             # new admissions shed
        assert not v.admitted and v.status == "shed"
        snap = eng.snapshot()               # host truth survives death
        assert {r["uid"] for r in snap["requests"]} == {0}
        assert snap["requests"][0]["exact"]


# --------------------------------------------------------------------------
# snapshot / restore
# --------------------------------------------------------------------------

class TestSnapshotRestore:
    def test_snapshot_schema_and_restore_resumes(self, model):
        eng = make_engine(model)
        eng.put(0, [1, 2, 3, 4, 5], priority=1, deadline_ms=60_000.0)
        eng.put(1, [7, 8, 9])
        eng.step()                           # 0/1 live with output
        snap = eng.snapshot()
        assert snap["version"] == 2 and snap["engine_version"]
        assert isinstance(snap["prefix_index"], list)
        recs = {r["uid"]: r for r in snap["requests"]}
        assert recs[0]["priority"] == 1
        assert recs[0]["deadline_ms"] is not None
        assert recs[0]["exact"] and recs[1]["exact"]
        eng2 = InferenceEngine.restore(model, snap, eng.icfg)
        assert eng2.query(0)["status"] == "queued"
        # restored generated-so-far stays visible through query()
        assert eng2.query(0)["generated"] == eng.query(0)["generated"]
        out = {}
        for _ in range(20):
            out.update(eng2.step())
            if len(out) == 2:
                break
        assert set(out) == {0, 1}

    def test_restore_rejects_wrong_version(self, model):
        """Schema-version gate: v2 engines restore v2 only — a v1
        snapshot predates per-request extraction/merge and a v3 one is
        from the future; half-applying either silently would be worse
        than refusing loudly."""
        for bad in (1, 3, None):
            with pytest.raises(ValueError, match="version"):
                InferenceEngine.restore(model, {"version": bad,
                                                "requests": []})

    def test_inexact_records_close_failed(self, model):
        eng = make_engine(model)
        snap = {"version": 2, "requests": [
            {"uid": 5, "tokens": None, "generated": [3], "exact": False},
            {"uid": 6, "tokens": [1, 2], "generated": [], "exact": True},
        ]}
        eng.load_snapshot(snap)
        assert eng.query(5)["status"] == "failed"
        assert 5 in eng._drain_reaped()
        assert eng.query(6)["status"] == "queued"
        assert int(eng.timings["requests_failed"]) == 1

    def test_terminal_statuses_contains_failed(self):
        assert "failed" in TERMINAL_STATUSES

    def test_terminal_statuses_contains_migrated(self):
        assert "migrated" in TERMINAL_STATUSES

    def test_snapshot_requests_extracts_subset(self, model):
        eng = make_engine(model)
        for uid in (0, 1, 2):
            eng.put(uid, [1 + uid, 2, 3, 4])
        eng.step()
        part = eng.snapshot_requests([1, 2, 777])   # 777: never seen
        assert part["version"] == 2 and part["partial"]
        assert [r["uid"] for r in part["requests"]] == [1, 2]
        # pure extraction: nothing closed, nothing released
        assert eng.query(1)["status"] in ("running", "queued")
        full = {r["uid"]: r for r in eng.snapshot()["requests"]}
        for r in part["requests"]:
            assert r == full[r["uid"]]

    def test_load_snapshot_refuses_nonfresh_without_merge(self, model):
        src = make_engine(model)
        src.put(0, [1, 2, 3])
        snap = src.snapshot()
        dst = make_engine(model)
        dst.put(5, [9, 8, 7])                # dst is already serving
        with pytest.raises(ValueError, match="merge=True"):
            dst.load_snapshot(snap)
        dst.load_snapshot(snap, merge=True)  # the migration mode
        assert dst.query(0)["status"] == "queued"
        assert dst.query(5)["status"] == "queued"

    def test_merge_rejects_uid_collision(self, model):
        src = make_engine(model)
        src.put(0, [1, 2, 3])
        snap = src.snapshot()
        dst = make_engine(model)
        dst.put(0, [4, 5, 6])                # same uid already open
        with pytest.raises(ValueError, match="already open"):
            dst.load_snapshot(snap, merge=True)
        # a duplicate uid WITHIN one payload is the same double-run
        # hazard (both modes) — and snapshot_requests dedups its list
        rec = snap["requests"][0]
        dst2 = make_engine(model)
        with pytest.raises(ValueError, match="repeats"):
            dst2.load_snapshot({"version": 2,
                                "requests": [rec, dict(rec)]},
                               merge=True)
        assert len(src.snapshot_requests([0, 0, 0])["requests"]) == 1
        # rejection is ATOMIC: a payload refused on its second record
        # must not leave its first record half-applied — the caller's
        # retry on another replica would double-run it
        src.put(7, [9, 9, 9])
        two = src.snapshot_requests([7, 0])
        dst3 = make_engine(model)
        dst3.put(0, [4, 5, 6])               # collides with record #2
        with pytest.raises(ValueError, match="already open"):
            dst3.load_snapshot(two, merge=True)
        assert dst3.query(7)["status"] == "unknown"

    def test_migrate_out_skips_non_replayable_streams(self, model):
        """A voluntary migration must never destroy a healthy request:
        a non-resumable stream (broken chain — device-side tokens the
        host never saw) is SKIPPED, not extracted-and-closed (the
        destination could only close it 'failed')."""
        eng = make_engine(model)
        eng.put(0, [1, 2, 3, 4])
        eng.step()
        eng.state.seqs[0].chain_broken = True   # e.g. a decode burst
        part = eng.migrate_out([0])
        assert part["requests"] == []
        assert eng.query(0)["status"] == "running"   # left in place

    def test_migrate_out_moves_open_work_token_identically(self, model):
        """Live subset migration: migrate_out() extracts + closes
        ``migrated`` on the source, load_snapshot(merge=True) re-opens
        on a NON-EMPTY destination, and the moved request's continued
        stream is token-identical to an unmigrated run (the
        (uid, position)-folded keys, as for restore)."""
        rng = jax.random.PRNGKey(7)
        sp = SamplingParams(temperature=0.8, top_k=40,
                            max_new_tokens=1 << 30)
        prompts = {0: [3, 1, 4, 1, 5, 9, 2, 6], 1: [2, 7, 1, 8]}
        ref, _ = drive(make_engine(model), dict(prompts), n_tok=6,
                       sampling=sp, rng=rng)
        src = make_engine(model)
        dst = make_engine(model)
        dst.put(1, list(prompts[1]))         # dst is already serving
        done = {0: [], 1: []}
        src.put(0, list(prompts[0]))
        for _ in range(3):                   # partway through uid 0
            for u, t in src.step(rng=rng, sampling=sp).items():
                done[u].append(t)
                src.put(u, [t])
        part = src.migrate_out([0])
        assert [r["uid"] for r in part["requests"]] == [0]
        assert src.query(0)["status"] == "migrated"
        assert 0 in src._drain_reaped()
        al = src.state.allocator
        al.assert_invariants()
        assert al.free_blocks == al.total_blocks   # KV released on src
        dst.load_snapshot(part, merge=True)
        n = 0
        active = {0, 1}
        while active:
            n += 1
            assert n < 200, "migrated drive wedged"
            for u, t in dst.step(rng=rng, sampling=sp).items():
                if u not in active:
                    continue
                done[u].append(t)
                if len(done[u]) >= 6:
                    active.discard(u)
                    dst.flush(u)
                else:
                    dst.put(u, [t])
        assert done == ref, "migration changed a token stream"


# --------------------------------------------------------------------------
# health + drain
# --------------------------------------------------------------------------

class TestHealthDrain:
    def test_health_degrades_and_recovers(self, model):
        eng = make_engine(model, failure=FailureConfig(
            health_window_steps=3))
        assert eng.health()["state"] == "healthy"
        # two requests: the crash is a non-singleton batch, so both
        # re-queue (a singleton crash would be poison-proof instead)
        prompts = {0: [1, 2, 3, 4], 1: [5, 6, 7]}

        def arm(e, i):
            if i == 3:
                e.failures.inject("crash")
        done, eng = drive(eng, prompts, n_tok=8, on_step=arm)
        # more than health_window_steps clean steps ran since the
        # failure (8 tokens of decode), so the window has closed
        assert eng.health()["state"] == "healthy"
        assert int(eng.timings["step_retries"]) >= 1
        # and the exported gauge follows the state
        assert eng._health_gauge.value() == 0

    def test_degraded_inside_window(self, model):
        eng = make_engine(model, failure=FailureConfig(
            health_window_steps=1000))
        eng.put(0, [1, 2, 3])
        eng.failures.inject("crash")
        eng.step()                           # recovered failure
        assert eng.health()["state"] == "degraded"

    def test_drain_contract(self, model):
        eng = make_engine(model)
        eng.put(0, [1, 2, 3, 4])
        eng.put(1, [5, 6, 7])
        eng.step()
        snap = eng.drain(deadline_ms=30_000.0)
        # admission stopped, backlog ran down, snapshot captured the
        # open work, and everything left closed with ONE terminal
        # status — the replacement replica restores the snapshot
        assert eng.health()["state"] == "draining"
        assert {r["uid"] for r in snap["requests"]} == {0, 1}
        # the drain reports its outcome split: everything still open
        # closed "shed" (the set the router re-places), nothing
        # completed through another exit on this trace
        assert snap["shed_uids"] == [0, 1]
        assert snap["completed_uids"] == []
        assert all(eng.query(u)["status"] == "shed" for u in (0, 1))
        assert eng.request_metrics()["aggregate"]["open"] == 0
        v = eng.put(9, [1])
        assert not v.admitted and "draining" in v.reason
        al = eng.state.allocator
        al.assert_invariants()
        assert al.free_blocks == al.total_blocks
        eng2 = InferenceEngine.restore(model, snap, eng.icfg)
        assert eng2.query(0)["status"] == "queued"

    def test_drain_respects_deadline(self, model):
        eng = make_engine(model)
        eng.put(0, list(range(1, 30)))
        snap = eng.drain(deadline_ms=0.0)    # expired before one step
        assert eng.query(0)["status"] == "shed"
        recs = {r["uid"]: r for r in snap["requests"]}
        assert recs[0]["exact"]              # still fully replayable
        assert snap["shed_uids"] == [0]

    def test_drain_splits_completed_from_shed(self, model):
        """A request that reaches a NON-shed terminal during the drain
        (here: an already-expired deadline reaped by the drain's first
        scheduler round) lands in ``completed_uids``, not in the
        re-place set."""
        eng = make_engine(model)
        eng.put(0, [1, 2, 3, 4])
        eng.put(1, [5, 6, 7], deadline_ms=0.0)   # expires immediately
        snap = eng.drain(deadline_ms=30_000.0)
        assert snap["shed_uids"] == [0]
        assert snap["completed_uids"] == [1]
        assert eng.query(1)["status"] == "deadline_exceeded"
        assert {r["uid"] for r in snap["requests"]} == {0}

    def test_replaced_drained_requests_keep_token_parity(self, model):
        """The router's scale-down drill: drain a replica mid-decode,
        re-place exactly its ``shed_uids`` records onto another LIVE
        replica (merge=True), and the finished streams are token-
        identical to an undisturbed single-engine run — greedy and
        seeded."""
        prompts = {0: [11, 12, 13, 14, 15], 1: [21, 22, 23]}
        for sp, rng in ((SamplingParams(max_new_tokens=1 << 30), None),
                        (SamplingParams(temperature=0.8, top_k=40,
                                        max_new_tokens=1 << 30),
                         jax.random.PRNGKey(13))):
            ref, _ = drive(make_engine(model), dict(prompts), n_tok=5,
                           sampling=sp, rng=rng)
            src = make_engine(model)
            done = {0: [], 1: []}
            for u, p in prompts.items():
                src.put(u, list(p))
            for _ in range(3):               # partway through both
                for u, t in src.step(rng=rng, sampling=sp).items():
                    done[u].append(t)
                    src.put(u, [t])
            snap = src.drain(deadline_ms=30_000.0)
            assert set(snap["shed_uids"]) == {0, 1}
            dst = make_engine(model)
            dst.put(9, [1, 2, 3])            # dst already has traffic
            recs = {r["uid"]: r for r in snap["requests"]}
            dst.load_snapshot(
                {"version": 2,
                 "requests": [recs[u] for u in snap["shed_uids"]]},
                merge=True)
            active = {0, 1}
            n = 0
            while active:
                n += 1
                assert n < 200, "re-placed drive wedged"
                for u, t in dst.step(rng=rng, sampling=sp).items():
                    if u not in active:
                        continue
                    done[u].append(t)
                    if len(done[u]) >= 5:
                        active.discard(u)
                        dst.flush(u)
                    else:
                        dst.put(u, [t])
            assert done == ref, "re-placed drained stream diverged"


# --------------------------------------------------------------------------
# status retention satellite
# --------------------------------------------------------------------------

class TestStatusRetention:
    def test_forgotten_vs_unknown(self, model):
        eng = make_engine(model, overload=OverloadConfig(
            status_retention=2))
        for uid in (0, 1, 2):
            eng.put(uid, [1, 2, 3])
            eng.flush(uid)
        # ring holds 2: uid 0 aged out -> forgotten, not unknown
        assert eng.query(0)["status"] == "forgotten"
        assert eng.query(1)["status"] == "finished"
        assert eng.query(2)["status"] == "finished"
        assert eng.query(777)["status"] == "unknown"
        # a forgotten uid that returns lives a full new life
        eng.put(0, [4, 5])
        assert eng.query(0)["status"] == "queued"
