"""Telemetry subsystem tests (docs/OBSERVABILITY.md): span-tracer units
(nesting, ring wraparound, disabled-mode cost), Chrome-trace schema
validation of an exported file, metrics registry + Prometheus
text-exposition round-trip, monitor fan-out, and request-lifecycle
accounting parity — the sum of per-request prompt/cached/generated
token counts must reconcile EXACTLY with the engine counters across
mixed chunked traffic, prefix cache on/off, pipeline depth 1/2, and
decode bursts (both sides are bumped at the same statements; a drift
means an accounting site was added on one side only)."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.telemetry import (CounterDictView, MetricsRegistry,
                                     RequestTracker, SpanTracer,
                                     parse_prometheus_text)
from tests.serving_ref import strict_generate


def tiny_model(**over):
    kw = dict(vocab_size=128, num_layers=2, d_model=64, num_heads=4,
              num_kv_heads=2, d_ff=128, max_seq_len=128)
    kw.update(over)
    return build_model("llama-tiny", **kw)


def make_engine(m, **over):
    kw = dict(token_budget=32, max_seqs=4, kv_block_size=16,
              num_kv_blocks=64, kv_dtype=jnp.float32,
              param_dtype=jnp.float32)
    kw.update(over)
    return InferenceEngine(m, InferenceConfig(**kw))


@pytest.fixture(scope="module")
def model():
    return tiny_model()


# --------------------------------------------------------------------------
# span tracer units
# --------------------------------------------------------------------------

class TestSpanTracer:
    def test_ring_off_records_nothing_and_reads_no_extra_clock(
            self, monkeypatch):
        """Ring off: a ``span()`` reads no clock at all (it is the
        profiler's TraceMe alone), a ``phase()`` cut reads it exactly
        once — the reading it returns — and nothing reaches the ring."""
        from deepspeed_tpu.telemetry import tracer as tracer_mod

        reads = {"pc": 0, "ns": 0}
        real_pc, real_ns = time.perf_counter, time.perf_counter_ns

        class Clock:
            @staticmethod
            def perf_counter():
                reads["pc"] += 1
                return real_pc()

            @staticmethod
            def perf_counter_ns():
                reads["ns"] += 1
                return real_ns()

        monkeypatch.setattr(tracer_mod, "time", Clock)
        tr = SpanTracer(capacity=8, enabled=False)
        with tr.span("ds.x.a"):
            with tr.span("ds.x.b", track="t", k=1) as sp:
                sp.set_metadata(n=2)
        tr.instant("y")
        assert reads == {"pc": 0, "ns": 0}
        t0 = tr.phase("ds.x.p", sid=1)
        t1 = tr.phase("ds.x.q", sid=1)
        tr.phase_set(hop_us=1.5)
        t2 = tr.phase_end(n=3)
        assert reads == {"pc": 3, "ns": 0}
        assert t0 <= t1 <= t2
        assert len(tr) == 0 and tr.events() == []

    def test_mirror_is_skipped_when_jax_is_not_imported(self):
        """telemetry/ stays JAX-free: in a process that never imported
        JAX the tracer resolves no TraceMe, a ring-off span is the
        shared no-op, and the ring works all the same."""
        import subprocess
        import sys
        import os

        code = (
            "import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location(\n"
            "    'tracer', 'deepspeed_tpu/telemetry/tracer.py')\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "sys.modules['tracer'] = m\n"
            "spec.loader.exec_module(m)\n"
            "tr = m.SpanTracer(capacity=8)\n"
            "assert tr.span('a') is tr.span('b', k=1) is m._NOOP_SPAN\n"
            "tr.phase('ds.x.p', sid=1); tr.phase_end(n=2)\n"
            "tr.enable()\n"
            "with tr.span('ds.x.a', k=1) as sp: sp.set_metadata(n=2)\n"
            "tr.phase('ds.x.p', sid=1); tr.phase_end(n=2)\n"
            "assert m._traceme() is None and 'jax' not in sys.modules\n"
            "evs = tr.events()\n"
            "assert [e['name'] for e in evs] == ['ds.x.a', 'ds.x.p']\n"
            "assert evs[0]['args'] == {'k': 1, 'n': 2}\n"
            "assert evs[1]['args'] == {'sid': 1, 'n': 2}\n")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run([sys.executable, "-c", code], cwd=repo,
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr

    def test_span_nesting_depth(self):
        tr = SpanTracer(capacity=16, enabled=True)
        with tr.span("outer", track="t"):
            with tr.span("inner", track="t"):
                pass
        evs = tr.events()
        # inner exits (and records) first
        assert [e["name"] for e in evs] == ["inner", "outer"]
        assert evs[0]["depth"] == 1 and evs[1]["depth"] == 0
        # containment: outer started before inner and ended after
        assert evs[1]["ts_ns"] <= evs[0]["ts_ns"]
        assert (evs[1]["ts_ns"] + evs[1]["dur_ns"]
                >= evs[0]["ts_ns"] + evs[0]["dur_ns"])

    def test_ring_wraparound(self):
        tr = SpanTracer(capacity=4, enabled=True)
        for i in range(10):
            tr.instant(f"e{i}")
        assert len(tr) == 4
        assert tr.dropped == 6
        # oldest-first, wraparound-corrected: the last 4 recorded
        assert [e["name"] for e in tr.events()] == ["e6", "e7", "e8", "e9"]
        tr.clear()
        assert len(tr) == 0 and tr.dropped == 0

    def test_phase_cuts_keep_sid_depth_and_the_returned_reading(self):
        """Ring on: each cut ends one phase and begins the next at the
        ONE reading it returns; args given at the cut, added under way
        and given at the end all reach the record; a span inside a
        phase nests one deeper."""
        tr = SpanTracer(capacity=8, enabled=True)
        t0 = tr.phase("ds.serve.schedule", track="schedule", sid=3)
        with tr.span("ds.serve.prefix_match", track="schedule", uid=9):
            pass
        t1 = tr.phase("ds.serve.dispatch", track="dispatch", sid=3,
                      n_tokens=5)
        tr.phase_set(mbs=2)
        t2 = tr.phase_end(hop_us=12.5)
        assert tr.phase_end() >= t2          # nothing open: a reading only
        inner, sched, disp = tr.events()
        assert (inner["name"], inner["depth"]) == \
            ("ds.serve.prefix_match", 1)
        assert (sched["name"], sched["track"], sched["depth"]) == \
            ("ds.serve.schedule", "schedule", 0)
        assert sched["ts_ns"] == int(t0 * 1e9)
        assert sched["ts_ns"] + sched["dur_ns"] == int(t1 * 1e9) \
            == disp["ts_ns"]
        assert disp["dur_ns"] == int(t2 * 1e9) - int(t1 * 1e9)
        assert sched["args"] == {"sid": 3}
        assert disp["args"] == {"sid": 3, "n_tokens": 5, "mbs": 2,
                                "hop_us": 12.5}

    def test_phase_left_open_is_closed_by_the_next_cut(self):
        """A phase whose sequence was cut short (an exception between
        two cuts) is ended by the thread's next cut, not leaked."""
        tr = SpanTracer(capacity=8, enabled=True)
        tr.phase("ds.x.lost")
        tr.phase("ds.x.next")
        tr.phase_end()
        assert [e["name"] for e in tr.events()] == ["ds.x.lost",
                                                    "ds.x.next"]
        assert tr._tls_depth() == 0

    def test_phases_of_two_threads_do_not_mix(self):
        """The gateway's event loop and its engine thread share one
        tracer: each thread has its own open phase and depth."""
        import threading

        tr = SpanTracer(capacity=16, enabled=True)
        tr.phase("ds.x.main")

        def other():
            tr.phase("ds.x.other")
            with tr.span("ds.x.inner"):
                pass
            tr.phase_end()

        t = threading.Thread(target=other)
        t.start()
        t.join()
        tr.phase_end()
        evs = {e["name"]: e for e in tr.events()}
        assert set(evs) == {"ds.x.main", "ds.x.other", "ds.x.inner"}
        assert evs["ds.x.inner"]["depth"] == 1
        assert evs["ds.x.main"]["depth"] == evs["ds.x.other"]["depth"] == 0
        # main's phase was open across the other thread's whole life
        assert evs["ds.x.main"]["dur_ns"] >= evs["ds.x.other"]["dur_ns"]

    def test_enable_disable_and_capacity_validation(self):
        tr = SpanTracer(capacity=4)
        assert not tr.enabled
        tr.enable()
        tr.instant("x")
        tr.disable()
        tr.instant("y")
        assert [e["name"] for e in tr.events()] == ["x"]
        with pytest.raises(ValueError, match="capacity"):
            SpanTracer(capacity=0)

    def test_disabled_overhead_smoke(self):
        """Disabled-mode cost: 50k no-op span entries must be ~free (no
        clock reads, no allocation) — generous bound for CI noise."""
        tr = SpanTracer(capacity=8, enabled=False)
        t0 = time.perf_counter()
        for _ in range(50_000):
            with tr.span("hot"):
                pass
        dt = time.perf_counter() - t0
        assert len(tr) == 0
        assert dt < 2.0, f"disabled tracer cost {dt:.3f}s for 50k spans"


class TestChromeTrace:
    def _tracer(self):
        tr = SpanTracer(capacity=64, enabled=True)
        tr.phase("schedule", track="schedule", sid=1)
        tr.phase("dispatch", track="dispatch", sid=1)
        time.sleep(0.002)
        tr.phase("wait", track="wait", sid=1)
        tr.phase_end()
        tr.instant("evict", track="schedule")
        return tr

    def test_chrome_trace_schema(self, tmp_path):
        path = str(tmp_path / "trace.json")
        assert self._tracer().export_chrome_trace(path) == path
        doc = json.load(open(path))
        assert doc["displayTimeUnit"] == "ms"
        assert doc["otherData"]["dropped_spans"] == 0
        evs = doc["traceEvents"]
        assert isinstance(evs, list)
        meta = [e for e in evs if e["ph"] == "M"]
        names = {e["args"]["name"] for e in meta
                 if e["name"] == "thread_name"}
        assert names == {"schedule", "dispatch", "wait"}
        assert any(e["name"] == "process_name" for e in meta)
        # one tid per track, stable sort indices
        sort_meta = [e for e in meta if e["name"] == "thread_sort_index"]
        assert len(sort_meta) == 3
        for e in evs:
            if e["ph"] == "X":
                assert isinstance(e["ts"], float)
                assert isinstance(e["dur"], float) and e["dur"] >= 0
                assert isinstance(e["tid"], int) and e["pid"] == 1
            elif e["ph"] == "i":
                assert e["s"] == "t" and "dur" not in e
        # durations in microseconds
        disp = next(e for e in evs if e.get("name") == "dispatch"
                    and e["ph"] == "X")
        assert 2000.0 <= disp["dur"] < 2e5

    def test_jsonl_export(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        self._tracer().export_jsonl(path)
        lines = [json.loads(ln) for ln in open(path)]
        assert len(lines) == 4
        assert lines[0]["name"] == "schedule"
        assert lines[-1]["instant"] is True


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("toks", int_valued=True)
        c.inc(3)
        c.inc()
        assert c.value() == 4
        assert reg.counter("toks") is c          # get-or-create identity
        g = reg.gauge("depth")
        g.set(2.5)
        g.inc(0.5)
        assert g.value() == 3.0
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("toks")

    def test_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("req")
        c.inc(2, phase="prefill")
        c.inc(1, phase="decode")
        assert c.value(phase="prefill") == 2
        assert c.value(phase="decode") == 1
        assert len(list(c.series())) == 2

    def test_histogram_math(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", (1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 5.0, 50.0, 500.0):
            h.observe(v)
        assert h.count() == 5
        assert h.sum() == 560.5
        assert h.mean() == pytest.approx(112.1)
        bc = h.bucket_counts()
        assert bc == {"1": 1, "10": 3, "100": 4, "+Inf": 5}
        # quantiles: monotone in q, overflow clamps to the last edge
        assert h.percentile(0.2) <= h.percentile(0.5) \
            <= h.percentile(0.9) <= h.percentile(1.0) == 100.0
        with pytest.raises(ValueError, match="sorted"):
            reg.histogram("bad", (3.0, 1.0))

    def test_snapshot_is_jsonable(self):
        reg = MetricsRegistry()
        reg.counter("steps", int_valued=True).inc(7)
        reg.counter("labeled").inc(1, k="v")
        reg.histogram("h", (1.0, 2.0)).observe(1.5)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["steps"] == 7
        assert snap["h"]["count"] == 1
        assert snap["labeled"] == {'{k="v"}': 1}

    def test_prometheus_roundtrip(self):
        reg = MetricsRegistry()
        reg.counter("serving_steps_total", "steps", int_valued=True).inc(5)
        reg.gauge("queue_depth").set(3)
        reg.counter("hits").inc(2, cache="prefix")
        h = reg.histogram("ttft_ms", (10.0, 100.0), "ttft")
        h.observe(7.0)
        h.observe(70.0)
        h.observe(700.0)
        text = reg.prometheus_text()
        assert "# TYPE serving_steps_total counter" in text
        assert "# HELP serving_steps_total steps" in text
        parsed = parse_prometheus_text(text)
        assert parsed["serving_steps_total"]["type"] == "counter"
        assert parsed["serving_steps_total"]["samples"][
            ("serving_steps_total", ())] == 5.0
        assert parsed["hits"]["samples"][
            ("hits", (("cache", "prefix"),))] == 2.0
        hs = parsed["ttft_ms"]["samples"]
        assert hs[("ttft_ms_count", ())] == 3.0
        assert hs[("ttft_ms_sum", ())] == 777.0
        assert hs[("ttft_ms_bucket", (("le", "10"),))] == 1.0
        assert hs[("ttft_ms_bucket", (("le", "100"),))] == 2.0
        assert hs[("ttft_ms_bucket", (("le", "+Inf"),))] == 3.0

    def test_write_jsonl(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        path = str(tmp_path / "metrics.jsonl")
        reg.write_jsonl(path, step=1)
        reg.counter("c").inc()
        reg.write_jsonl(path, step=2)
        lines = [json.loads(ln) for ln in open(path)]
        assert [ln["step"] for ln in lines] == [1, 2]
        assert [ln["metrics"]["c"] for ln in lines] == [1, 2]
        assert all("time" in ln for ln in lines)

    def test_monitor_fanout(self):
        """Registry values ride the monitor/ writer event shape
        ((name, value, step) triples — monitor/monitor.py)."""
        class StubMonitor:
            events = []

            def write_events(self, evs):
                self.events.extend(evs)

        reg = MetricsRegistry()
        reg.counter("steps").inc(4)
        reg.histogram("lat_ms", (1.0, 10.0)).observe(2.0)
        mon = StubMonitor()
        reg.publish(mon, step=9)
        d = {name: (value, step) for name, value, step in mon.events}
        assert d["steps"] == (4.0, 9)
        assert d["lat_ms_count"] == (1.0, 9)
        assert d["lat_ms_sum"] == (2.0, 9)
        assert "lat_ms_p50" in d
        reg.publish(None, step=10)               # no-op without a monitor

    def test_reset_keeps_registrations(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        h = reg.histogram("h", (1.0,))
        c.inc(3)
        h.observe(0.5)
        reg.reset()
        assert reg.counter("c") is c and c.value() == 0
        assert h.count() == 0 and "h" in reg

    def test_counter_dict_view(self):
        reg = MetricsRegistry()
        cs = {"a_ms": reg.counter("a_ms_total"),
              "n": reg.counter("n_total", int_valued=True)}
        tm = CounterDictView(cs)
        tm["a_ms"] += 1.5
        tm["n"] += 2
        assert tm["a_ms"] == 1.5
        assert tm["n"] == 2 and isinstance(tm["n"], int)
        assert sorted(tm) == ["a_ms", "n"]
        assert len(tm) == 2
        assert dict(tm) == {"a_ms": 1.5, "n": 2}
        tm["n"] = 0                              # reset-style assignment
        assert reg.counter("n_total").value() == 0
        with pytest.raises(TypeError):
            del tm["n"]
        with pytest.raises(KeyError):
            tm["unknown"]
        tm["a_ms"] += 1.0
        tm.reset()
        assert tm["a_ms"] == 0.0


# --------------------------------------------------------------------------
# request lifecycle units
# --------------------------------------------------------------------------

class TestRequestTracker:
    def test_lifecycle_math(self):
        reg = MetricsRegistry()
        t = RequestTracker(reg)
        t.on_arrival(7, now=100.0)
        t.on_admitted(7, prompt_tokens=10, cached_tokens=4, now=100.5)
        t.on_prefill_start(7, 100.6)
        t.on_tokens(7, 1, 101.0)
        t.on_tokens(7, 1, 101.2)
        t.on_tokens(7, 1, 101.4)
        t.on_finish(7, now=101.5)
        (rec,) = t.records()
        assert rec.queue_wait_ms == pytest.approx(500.0)
        assert rec.ttft_ms == pytest.approx(1000.0)
        assert rec.tpot_ms == pytest.approx(200.0)   # (101.4-101.0)/2
        assert rec.e2e_ms == pytest.approx(1500.0)
        assert (rec.prompt_tokens, rec.cached_tokens,
                rec.generated_tokens) == (10, 4, 3)
        d = rec.as_dict()
        assert d["finished"] is True and d["uid"] == 7
        agg = t.aggregate()
        assert agg["requests"] == 1 and agg["finished"] == 1
        assert agg["ttft_ms"]["count"] == 1
        assert agg["tpot_ms"]["count"] == 1
        assert agg["queue_wait_ms"]["count"] == 1

    def test_single_token_request_has_no_tpot(self):
        t = RequestTracker(MetricsRegistry())
        t.on_arrival(1, now=0.0)
        t.on_admitted(1, 3, 0, now=0.1)
        t.on_tokens(1, 1, 0.2)
        t.on_finish(1, now=0.3)
        (rec,) = t.records()
        assert rec.tpot_ms is None               # no decode tail
        assert t.aggregate()["tpot_ms"]["count"] == 0

    def test_window_emission_counts_every_token_of_the_tail(self):
        """A resolved verify window lands several tokens at one
        readback instant: all of them count in the decode tail, which
        is anchored at the first token."""
        t = RequestTracker(MetricsRegistry())
        t.on_arrival(1, now=0.0)
        t.on_admitted(1, 2, 0, now=0.1)
        t.on_tokens(1, 1, 0.2)
        t.on_tokens(1, 3, 1.0)                   # one 3-token window
        t.on_finish(1, now=1.1)
        (rec,) = t.records()
        assert rec.ttft_ms == pytest.approx(200.0)
        assert rec.generated_tokens == 4
        assert rec.tpot_ms == pytest.approx((1.0 - 0.2) * 1e3 / 3)
        t.on_arrival(2, now=0.0)
        t.on_tokens(2, 1, 1.0)
        t.on_tokens(2, 1, 1.5)
        t.on_finish(2, now=1.6)
        rec2 = t.records()[-1]
        assert rec2.tpot_ms == pytest.approx(500.0)

    def test_continuation_arrival_is_noop(self):
        t = RequestTracker(MetricsRegistry())
        r1 = t.on_arrival(1, now=0.0)
        r2 = t.on_arrival(1, now=5.0)
        assert r1 is r2 and r1.t_arrival == 0.0
        assert t.aggregate()["requests"] == 1

    def test_finished_ring_is_bounded(self):
        t = RequestTracker(MetricsRegistry(), max_finished=2)
        for uid in range(4):
            t.on_arrival(uid, now=float(uid))
            t.on_finish(uid, now=float(uid) + 1)
        assert [r.uid for r in t.records()] == [2, 3]
        assert t.aggregate()["finished"] == 4    # counter keeps the total


# --------------------------------------------------------------------------
# engine integration: accounting parity + trace export + back-compat
# --------------------------------------------------------------------------

def _assert_parity(eng):
    """Sum of per-request token counts == engine counters, exactly."""
    recs = eng.request_metrics()["requests"]
    tm = eng.timings
    assert sum(r["prompt_tokens"] for r in recs) == tm["prompt_tokens"]
    assert sum(r["cached_tokens"] for r in recs) == tm["cached_tokens"]
    assert sum(r["generated_tokens"] for r in recs) \
        == tm["generated_tokens"]


class TestEngineTelemetry:
    MIXED = {0: list(range(1, 51)), 1: [3, 1, 4], 2: list(range(60, 80))}

    @pytest.mark.parametrize("drive", [
        strict_generate, InferenceEngine.generate], ids=["strict", "ahead"])
    def test_parity_mixed_chunked_traffic(self, model, drive):
        """Prompts straddling the token budget (chunked prefill + decode
        mixed steps), fed by the caller and run a launch ahead."""
        eng = make_engine(model, token_budget=16)
        sp = SamplingParams(max_new_tokens=6)
        out = drive(eng, {u: list(p) for u, p in self.MIXED.items()}, sp)
        _assert_parity(eng)
        tm = eng.timings
        assert tm["prompt_tokens"] == sum(len(p) for p in
                                          self.MIXED.values())
        assert tm["generated_tokens"] >= sum(len(v) for v in out.values())
        agg = eng.request_metrics()["aggregate"]
        assert agg["requests"] == agg["finished"] == len(self.MIXED)
        assert agg["open"] == 0
        # every finished record carries the full latency story
        for r in eng.request_metrics()["requests"]:
            assert r["finished"]
            assert r["queue_wait_ms"] is not None \
                and r["queue_wait_ms"] >= 0
            assert r["ttft_ms"] is not None and r["ttft_ms"] >= 0
            assert r["tpot_ms"] is not None and r["tpot_ms"] >= 0
            assert r["e2e_ms"] >= r["ttft_ms"]
            assert r["generated_tokens"] == len(out[r["uid"]])

    @pytest.mark.parametrize("mode", ["off", "on"])
    def test_parity_prefix_cache(self, model, mode):
        """Shared-prefix traffic arriving sequentially: the cache-on
        engine serves prompt tokens from the cache; per-request
        cached_tokens reconcile with the hit counters either way."""
        shared = list(range(1, 33))              # two full 16-tok blocks
        prompts = {u: shared + [100 + u, 101 + u, 102 + u]
                   for u in range(3)}
        eng = make_engine(model, prefix_cache=mode)
        sp = SamplingParams(max_new_tokens=2)
        for u, p in prompts.items():             # sequential: later
            eng.generate({u: list(p)}, sp)       # requests can hit
        _assert_parity(eng)
        tm = eng.timings
        if mode == "on":
            assert tm["cached_tokens"] > 0 and tm["prefix_hits"] >= 2
        else:
            assert tm["cached_tokens"] == 0 == tm["prefix_hits"]
        assert eng.request_metrics()["aggregate"]["finished"] == 3

    def test_trace_export_has_serving_span_types(self, model, tmp_path):
        """A generate() with tracing on exports a valid Chrome
        trace carrying >= 4 distinct serving-loop span types, one track
        each (the acceptance-criteria artifact)."""
        eng = make_engine(model, trace=True)
        eng.generate({0: list(range(1, 40)), 1: [9, 8, 7]},
                     SamplingParams(max_new_tokens=5))
        path = str(tmp_path / "serving_trace.json")
        eng.tracer.export_chrome_trace(path)
        doc = json.load(open(path))
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        names = {e["name"] for e in spans}
        assert {"ds.serve.schedule", "ds.serve.stage",
                "ds.serve.dispatch", "ds.serve.compile", "ds.serve.wait",
                "ds.serve.readback"} <= names
        tracks = {e["args"]["name"] for e in doc["traceEvents"]
                  if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert len(tracks) >= 4
        # spans carry their dispatch sequence id for cross-track joins
        assert any("sid" in e.get("args", {}) for e in spans)

    def test_profiler_session_holds_the_steps_phases(self, model, tmp_path):
        """The always-armed sink: with the ring OFF, a real jax.profiler
        session around a few engine steps finds the step's phases as
        ``ds.serve.*`` events on the host plane of the session's own
        file — on the clock of the device's events — with their args
        (sid, counts, the watchdog's hop) as stats."""
        import glob

        from deepspeed_tpu.telemetry import profiler_available
        if not profiler_available():
            pytest.skip("THIS BUILD HAS NO jax.profiler: the tracer's "
                        "profiler mirror is untested here")
        eng = make_engine(model)
        eng.put(0, [5, 17, 99, 3])
        sp = SamplingParams(max_new_tokens=1 << 30)
        tok = eng.step(sampling=sp)[0]            # compile outside
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(3):
                eng.put(0, [tok])
                tok = eng.step(sampling=sp)[0]
        finally:
            jax.profiler.stop_trace()
        assert not eng.tracer.enabled and len(eng.tracer) == 0
        (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        found = {}
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("ds."):
                        found.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.duration_ns, dict(ev.stats)))
        assert {"ds.serve.schedule", "ds.serve.stage", "ds.serve.dispatch",
                "ds.serve.wait", "ds.serve.readback"} <= set(found)
        disp = sorted(found["ds.serve.dispatch"])
        assert len(disp) == 3
        sids = [st["sid"] for _, _, st in disp]
        assert sids == list(range(sids[0], sids[0] + 3))
        for _, _, st in disp:
            assert (st["n_tokens"], st["n_seqs"], st["n_decode"]) == (1, 1, 1)
            assert st["mbs"] >= 1 and st["hop_us"] >= 0.0
        assert sorted(st["sid"] for _, _, st in found["ds.serve.wait"]) == sids
        assert all("hop_us" in st for _, _, st in found["ds.serve.wait"])
        # phases of one step follow one another on the one clock
        for name_a, name_b in (("ds.serve.schedule", "ds.serve.stage"),
                               ("ds.serve.stage", "ds.serve.dispatch"),
                               ("ds.serve.wait", "ds.serve.readback")):
            a = {st["sid"]: s0 + d for s0, d, st in found[name_a]}
            b = {st["sid"]: s0 for s0, _, st in found[name_b]}
            assert all(a[k] <= b[k] for k in sids), (a, b)

    def test_trace_disabled_by_default(self, model):
        eng = make_engine(model)
        eng.generate({0: [1, 2, 3]}, SamplingParams(max_new_tokens=3))
        assert not eng.tracer.enabled and len(eng.tracer) == 0

    def test_timings_backcompat_and_resets(self, model):
        """engine.timings stays a dict-shaped accumulator (bench.py and
        older tests read/reset it) while the same numbers live in the
        registry."""
        eng = make_engine(model)
        eng.generate({0: [1, 2, 3, 4]}, SamplingParams(max_new_tokens=4))
        tm = eng.timings
        assert set(tm) == {"schedule_ms", "stage_ms", "device_ms",
                           "wait_ms", "readback_ms", "compile_ms",
                           "steps", "compiles", "compile_retraces",
                           "prompt_tokens", "cached_tokens",
                           "prefix_hits", "generated_tokens",
                           "spec_drafted_tokens", "spec_accepted_tokens",
                           "spec_rejected_tokens", "spec_windows",
                           "step_retries", "requests_failed",
                           "kv_tier_demotions", "kv_tier_spills",
                           "kv_tier_drops", "kv_tier_revives_ram",
                           "kv_tier_revives_nvme",
                           "kv_tier_revives_remote",
                           "kv_tier_restage_overlap_hits",
                           "kv_tier_verify_failures",
                           "kv_tier_demoted_bytes",
                           "kv_tier_spilled_bytes",
                           "kv_tier_remote_blocks"}
        assert tm["steps"] > 0 and isinstance(tm["steps"], int)
        assert dict(tm)["steps"] == tm["steps"]
        # the registry sees the same number
        assert eng.metrics.get("serving_steps_total").value() \
            == tm["steps"]
        eng.reset_timings()
        assert tm["steps"] == 0 and tm["schedule_ms"] == 0.0
        # reset_timings does NOT clear request records ...
        assert eng.request_metrics()["aggregate"]["finished"] == 1
        # ... reset_metrics clears everything
        eng.generate({1: [1, 2]}, SamplingParams(max_new_tokens=2))
        eng.reset_metrics()
        assert eng.timings["steps"] == 0
        assert eng.request_metrics()["requests"] == []
        assert len(eng.tracer) == 0
        assert eng.request_metrics()["aggregate"]["ttft_ms"]["count"] == 0

    def test_prometheus_and_snapshot_from_engine(self, model):
        eng = make_engine(model)
        eng.generate({0: [1, 2, 3, 4, 5]}, SamplingParams(max_new_tokens=4))
        snap = json.loads(json.dumps(eng.metrics_snapshot()))
        assert snap["serving_steps_total"] == eng.timings["steps"]
        assert snap["serving_ttft_ms"]["count"] == 1
        parsed = parse_prometheus_text(eng.metrics.prometheus_text())
        assert parsed["serving_steps_total"]["samples"][
            ("serving_steps_total", ())] == float(eng.timings["steps"])
        assert parsed["serving_ttft_ms"]["samples"][
            ("serving_ttft_ms_count", ())] == 1.0

    def test_engine_monitor_fanout(self, model):
        class StubMonitor:
            def __init__(self):
                self.events = []

            def write_events(self, evs):
                self.events.extend(evs)

        eng = make_engine(model)
        eng.generate({0: [1, 2, 3]}, SamplingParams(max_new_tokens=3))
        mon = StubMonitor()
        eng.publish_metrics(mon, step=1)
        names = {n for n, _, _ in mon.events}
        assert "serving_steps_total" in names
        assert "serving_ttft_ms_count" in names


# --------------------------------------------------------------------------
# training-engine telemetry
# --------------------------------------------------------------------------

class TestTrainingTelemetry:
    def _engine(self, monitor=None, **telemetry):
        import deepspeed_tpu as ds

        m = build_model("gpt2", max_seq_len=32, num_layers=2, d_model=32,
                        num_heads=2, vocab_size=64)
        return ds.initialize(model=m, config={
            "train_micro_batch_size_per_device": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "mesh": {"data": -1},
            "steps_per_print": 1,
            "telemetry": telemetry,
        }, monitor=monitor), m

    def _batch(self, eng):
        from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                      synthetic_lm_data)

        data = synthetic_lm_data(64, eng.train_batch_size * 4, 32)
        return next(iter(DataLoader(data, eng.train_batch_size)))

    def test_step_phases_and_trace(self):
        eng, _ = self._engine(trace=True)
        for _ in range(2):
            eng.train_batch(self._batch(eng))
        snap = eng.metrics_snapshot()
        assert snap["training_steps_total"] == 2
        assert snap["training_step_host_ms"]["count"] == 2
        for k in ("training_pre_step_ms_total", "training_stage_ms_total",
                  "training_dispatch_ms_total"):
            assert snap[k] >= 0.0
        names = {e["name"] for e in eng.tracer.events()}
        assert {"ds.train.pre_step", "ds.train.stage",
                "ds.train.dispatch", "ds.train.fetch"} <= names
        steps = {e["args"]["step"] for e in eng.tracer.events()
                 if e["name"] == "ds.train.dispatch"}
        assert steps == {1, 2}

    def test_registry_rides_monitor_pipeline(self):
        class StubMonitor:
            enabled = True

            def __init__(self):
                self.events = []

            def write_events(self, evs):
                self.events.extend(evs)

            def write_scalars(self, step, scalars):
                self.write_events([(k, float(v), step)
                                   for k, v in scalars.items()])

        mon = StubMonitor()
        eng, _ = self._engine(monitor=mon)
        eng.train_batch(self._batch(eng))
        names = {n for n, _, _ in mon.events}
        # loss scalars AND registry metrics through ONE writer
        assert "Train/loss" in names
        assert "training_steps_total" in names
        assert "training_step_host_ms_count" in names
