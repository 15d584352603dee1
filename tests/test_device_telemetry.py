"""Device & compiler observability (docs/OBSERVABILITY.md "Device &
compiler telemetry"): FnGauge pull semantics, KV-pool gauge truth,
compile/retrace counters, cost-analysis probing + derived MFU/BW
gauges (Prometheus round-trip for every new gauge), memory-stat
degradation on CPU, the flight recorder's schema + auto-dump on
EngineDeadError, and the ZERO-COST bar for the disabled path (no
cost_analysis, no memory polls, no added clock reads in the serving
loop when device telemetry is off)."""

import json
import time

import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference import (FailureConfig, InferenceConfig,
                                     InferenceEngine, SamplingParams)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.telemetry import (DeviceTelemetry, FlightRecorder,
                                     MetricsRegistry, config_fingerprint,
                                     parse_prometheus_text,
                                     validate_flight_dump)
from deepspeed_tpu.telemetry import device as device_mod
from deepspeed_tpu.telemetry.metrics import FnGauge


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


def run_to_first_token(eng, uid=0, n=8):
    sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
    eng.put(uid, list(range(1, n + 1)))
    while True:
        out = eng.step(sampling=sp)
        if uid in out:
            return out[uid]


@pytest.fixture(scope="module")
def model():
    return tiny_model()


# --------------------------------------------------------------------------
# FnGauge: pull-based gauges with an honest "absent" state
# --------------------------------------------------------------------------

class TestFnGauge:
    def test_value_and_series(self):
        reg = MetricsRegistry()
        box = {"v": 3.5}
        g = reg.gauge_fn("serving_test_gauge", lambda: box["v"])
        assert g.value() == 3.5
        assert list(g.series()) == [((), 3.5)]
        box["v"] = 7
        assert reg.snapshot()["serving_test_gauge"] == 7

    def test_none_and_exception_read_as_absent(self):
        reg = MetricsRegistry()
        reg.gauge_fn("serving_absent_gauge", lambda: None)
        def boom():
            raise RuntimeError("probe died")
        reg.gauge_fn("serving_broken_gauge", boom)
        snap = reg.snapshot()
        assert "serving_absent_gauge" not in snap
        assert "serving_broken_gauge" not in snap
        text = reg.prometheus_text()      # export must not crash
        # TYPE declared, no sample line (absent, not zero)
        assert "# TYPE serving_absent_gauge gauge" in text
        assert "\nserving_absent_gauge " not in text

    def test_set_raises_and_reset_is_noop(self):
        reg = MetricsRegistry()
        g = reg.gauge_fn("serving_pull_gauge", lambda: 1.0)
        with pytest.raises(TypeError):
            g.set(5.0)
        with pytest.raises(TypeError):
            g.inc()
        reg.reset()
        assert g.value() == 1.0           # source owns the state

    def test_reregistration_rebinds_callable(self):
        reg = MetricsRegistry()
        reg.gauge_fn("serving_rebound_gauge", lambda: 1.0)
        g2 = reg.gauge_fn("serving_rebound_gauge", lambda: 2.0)
        assert g2.value() == 2.0
        assert isinstance(reg.get("serving_rebound_gauge"), FnGauge)

    def test_prometheus_round_trip_when_present(self):
        reg = MetricsRegistry()
        reg.gauge_fn("serving_rt_gauge", lambda: 0.25)
        parsed = parse_prometheus_text(reg.prometheus_text())
        assert parsed["serving_rt_gauge"]["samples"][
            ("serving_rt_gauge", ())] == 0.25


# --------------------------------------------------------------------------
# peak tables + cost extraction
# --------------------------------------------------------------------------

class _FakeDev:
    def __init__(self, kind):
        self.device_kind = kind


class TestPeaksAndCost:
    def test_peak_tables_by_device_kind(self):
        assert device_mod.peak_flops(_FakeDev("TPU v5e")) == 197e12
        assert device_mod.peak_flops(_FakeDev("TPU v4")) == 275e12
        assert device_mod.peak_flops(_FakeDev("cpu")) is None
        assert device_mod.peak_hbm_bw(_FakeDev("TPU v6e")) == 1.64e12
        assert device_mod.peak_hbm_bw(_FakeDev("weird")) is None

    def test_cost_analysis_of_real_program(self):
        import jax

        f = jax.jit(lambda x: x @ x)
        c = f.lower(jnp.ones((32, 32))).compile()
        cost = device_mod.cost_analysis_of(c)
        assert cost.get("flops", 0) > 0
        assert cost.get("hlo_bytes", 0) > 0

    def test_cost_analysis_of_broken_object_is_empty(self):
        class Broken:
            def cost_analysis(self):
                raise RuntimeError("no")
            def memory_analysis(self):
                raise RuntimeError("no")
            def as_text(self):
                raise RuntimeError("no")
        assert device_mod.cost_analysis_of(Broken()) == {}

    def test_poll_memory_stats_cpu_is_empty_not_crash(self):
        # CPU devices answer memory_stats() with None — the probe
        # degrades to an empty dict, and the gauges stay absent
        assert device_mod.poll_memory_stats() == {}


# --------------------------------------------------------------------------
# compile observatory: counters, spans, retraces
# --------------------------------------------------------------------------

class TestCompileObservatory:
    def test_compiles_counted_and_compile_ms_recorded(self, model):
        eng = make_engine(model, trace=True)
        run_to_first_token(eng)
        tm = eng.timings
        assert tm["compiles"] >= 1
        assert tm["compile_retraces"] == 0
        assert tm["compile_ms"] > 0
        names = [e["name"] for e in eng.tracer.events()]
        assert "ds.serve.compile" in names

    def test_forced_respecialization_bumps_retrace_exactly_once(
            self, model):
        eng = make_engine(model)
        tok = run_to_first_token(eng)
        c0 = eng.timings["compiles"]
        assert eng.timings["compile_retraces"] == 0
        # force a re-specialization of an already-compiled key: drop
        # the executable cache (what LRU thrash / a stray cache
        # invalidation does at runtime)
        eng._pstep_fns.clear()
        sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
        eng.put(0, [int(tok)])
        eng.step(sampling=sp)
        assert eng.timings["compiles"] == c0 + 1
        assert eng.timings["compile_retraces"] == 1   # exactly once
        # steady state afterwards: no further fills, no further bumps
        eng.put(0, [3])
        eng.step(sampling=sp)
        assert eng.timings["compile_retraces"] == 1

    def test_prometheus_exposes_compile_counters(self, model):
        eng = make_engine(model)
        run_to_first_token(eng)
        parsed = parse_prometheus_text(eng.metrics.prometheus_text())
        assert parsed["serving_compiles_total"]["samples"][
            ("serving_compiles_total", ())] >= 1
        assert ("serving_compile_retraces_total", ()) in \
            parsed["serving_compile_retraces_total"]["samples"]


# --------------------------------------------------------------------------
# KV-pool pull-gauges: truth + round-trip
# --------------------------------------------------------------------------

class TestPoolGauges:
    def test_gauges_match_allocator_truth_and_round_trip(self, model):
        eng = make_engine(model, prefix_cache="on")
        run_to_first_token(eng, uid=0, n=40)
        al = eng.state.allocator
        al.assert_invariants()
        parsed = parse_prometheus_text(eng.metrics.prometheus_text())

        def val(name):
            return parsed[name]["samples"][(name, ())]

        assert val("serving_kv_blocks_referenced") \
            == al.referenced_blocks
        assert val("serving_kv_blocks_cached_free") \
            == al.cached_free_blocks
        assert val("serving_kv_blocks_free") \
            == al.free_blocks - al.cached_free_blocks
        assert val("serving_kv_blocks_total") == al.total_blocks
        assert val("serving_kv_blocks_peak_referenced") \
            == al.peak_referenced_blocks >= al.referenced_blocks
        assert val("serving_prefix_index_entries") \
            == len(eng.state._hash_index)
        # a release moves blocks: the NEXT scrape sees it (pull-based)
        eng.flush(0)
        parsed = parse_prometheus_text(eng.metrics.prometheus_text())
        assert val("serving_kv_blocks_referenced") == 0

    def test_hit_rate_gauge_absent_before_traffic(self, model):
        eng = make_engine(model)
        assert "serving_prefix_hit_rate" not in eng.metrics_snapshot()
        run_to_first_token(eng)
        snap = eng.metrics_snapshot()
        assert snap["serving_prefix_hit_rate"] == pytest.approx(
            eng.timings["cached_tokens"]
            / max(eng.timings["prompt_tokens"], 1))

    def test_reset_metrics_rearms_peak(self, model):
        eng = make_engine(model)
        run_to_first_token(eng, n=40)
        eng.flush(0)
        assert eng.state.allocator.peak_referenced_blocks > 0
        eng.reset_metrics()
        assert eng.state.allocator.peak_referenced_blocks == 0


# --------------------------------------------------------------------------
# gated device telemetry: cost probe, derived gauges, memory polling
# --------------------------------------------------------------------------

class TestDeviceTelemetryOn:
    def test_cost_probe_and_flop_attribution(self, model):
        eng = make_engine(model, device_telemetry="on")
        run_to_first_token(eng)
        assert eng.devtel is not None
        assert len(eng.devtel.program_costs) >= 1
        cost = next(iter(eng.devtel.program_costs.values()))
        assert cost.get("flops", 0) > 0          # CPU reports flops
        assert cost.get("compile_ms", 0) > 0
        snap = eng.metrics_snapshot()
        assert snap["serving_model_flops_total"] > 0
        assert snap["serving_hbm_bytes_total"] > 0
        # flops grow per dispatched step
        before = snap["serving_model_flops_total"]
        eng.put(0, [5])
        eng.step(sampling=SamplingParams(temperature=0.0,
                                         max_new_tokens=1 << 30))
        assert eng.metrics_snapshot()["serving_model_flops_total"] \
            > before

    def test_mfu_gauges_absent_without_peak_present_with(self, model):
        eng = make_engine(model, device_telemetry="on")
        run_to_first_token(eng)
        # CPU: no published peak -> honest absence
        snap = eng.metrics_snapshot()
        assert "serving_mfu" not in snap
        assert "serving_hbm_bw_util" not in snap
        # inject a peak (what a TPU device_kind resolves): the SAME
        # run's numbers now derive a utilization, and it round-trips
        eng.devtel.peak_flops = 1e12
        eng.devtel.peak_hbm_bw = 1e12
        snap = eng.metrics_snapshot()
        assert "serving_mfu" in snap and "serving_hbm_bw_util" in snap
        busy_s = (eng.timings["device_ms"] + eng.timings["wait_ms"]) / 1e3
        flops = eng.metrics.get("serving_model_flops_total").value()
        mfu = eng.metrics.get("serving_mfu").value()
        assert mfu == pytest.approx(flops / busy_s / 1e12, rel=1e-6)
        parsed = parse_prometheus_text(eng.metrics.prometheus_text())
        assert parsed["serving_mfu"]["samples"][("serving_mfu", ())] \
            == pytest.approx(mfu, rel=1e-4)
        assert ("serving_hbm_bw_util", ()) in \
            parsed["serving_hbm_bw_util"]["samples"]

    def test_memory_gauges_from_polled_stats(self, model, monkeypatch):
        eng = make_engine(model, device_telemetry="on")
        fake = {"0": {"bytes_in_use": 1 << 20,
                      "peak_bytes_in_use": 1 << 21,
                      "bytes_limit": 1 << 30}}
        monkeypatch.setattr(device_mod, "poll_memory_stats", lambda: fake)
        # health() is a phase boundary: it polls and publishes
        eng.health()
        parsed = parse_prometheus_text(eng.metrics.prometheus_text())
        key = ("serving_hbm_bytes_in_use", (("device", "0"),))
        assert parsed["serving_hbm_bytes_in_use"]["samples"][key] \
            == 1 << 20
        key = ("serving_hbm_peak_bytes_in_use", (("device", "0"),))
        assert parsed["serving_hbm_peak_bytes_in_use"]["samples"][key] \
            == 1 << 21

    def test_memory_gauges_absent_on_cpu(self, model):
        eng = make_engine(model, device_telemetry="on")
        eng.health()                      # polls; CPU answers nothing
        snap = eng.metrics_snapshot()
        assert "serving_hbm_bytes_in_use" not in snap

    def test_device_snapshot_shape(self, model):
        eng = make_engine(model, device_telemetry="on")
        run_to_first_token(eng)
        ds = eng.device_snapshot()
        assert set(ds) >= {"programs", "model_flops_total", "mfu",
                           "hbm_bw_util", "memory", "peak_flops"}
        assert ds["mfu"] is None          # CPU: no peak
        json.dumps(ds)                    # JSON-able by contract

    def test_invalid_mode_rejected(self, model):
        with pytest.raises(ValueError, match="device_telemetry"):
            make_engine(model, device_telemetry="sometimes")


# --------------------------------------------------------------------------
# the zero-cost bar for the disabled path
# --------------------------------------------------------------------------

class TestDisabledPathZeroCost:
    def test_off_engine_never_touches_device_probes(self, model,
                                                    monkeypatch):
        def forbidden(*a, **k):
            raise AssertionError("device-telemetry probe ran with "
                                 "device_telemetry off")
        monkeypatch.setattr(DeviceTelemetry, "probe_program", forbidden)
        monkeypatch.setattr(DeviceTelemetry, "poll_memory", forbidden)
        monkeypatch.setattr(device_mod, "poll_memory_stats", forbidden)
        monkeypatch.setattr(device_mod, "cost_analysis_of", forbidden)
        eng = make_engine(model)          # default "auto" == off today
        assert eng.devtel is None
        assert eng.device_snapshot() is None
        run_to_first_token(eng)
        eng.health()                      # the phase boundary polls are
        eng.metrics_snapshot()            # gated too

    def test_on_adds_no_clock_reads_per_warm_step(self, model):
        """device_telemetry='on' must add NO clock reads to the warmed
        serving loop relative to 'off' — the probes run at compile time
        and phase boundaries only.  Counted by instrumenting
        time.perf_counter over one identical put+step on each."""
        sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
        counts = {}
        for mode in ("off", "on"):
            eng = make_engine(model, device_telemetry=mode)
            tok = run_to_first_token(eng)       # warm: probes done
            eng.put(0, [int(tok)])
            real = time.perf_counter
            n = [0]

            def counting():
                n[0] += 1
                return real()
            time.perf_counter = counting
            try:
                eng.step(sampling=sp)
            finally:
                time.perf_counter = real
            counts[mode] = n[0]
        assert counts["on"] == counts["off"], counts

    def test_anomaly_off_never_observes_or_captures(self, model,
                                                    monkeypatch):
        """The PR-10 extension of the bar: with anomaly detection off
        (the default), no detector hook and no capture hook may run —
        the engine holds no monitor and no capture manager at all."""
        from deepspeed_tpu.telemetry import anomaly as anomaly_mod
        from deepspeed_tpu.telemetry import profiler as profiler_mod

        def forbidden(*a, **k):
            raise AssertionError("anomaly/capture hook ran with the "
                                 "feature off")
        monkeypatch.setattr(anomaly_mod.AnomalyMonitor, "observe",
                            forbidden)
        monkeypatch.setattr(profiler_mod.ProfilerCapture, "begin",
                            forbidden)
        eng = make_engine(model)          # anomaly "auto" == off today
        assert eng._anom is None and eng._cap is None
        run_to_first_token(eng)
        eng.health()
        eng.metrics_snapshot()
        eng.flush(0)
        assert eng.capture_dirs == []

    def test_anomaly_on_adds_no_clock_reads_per_warm_step(self, model):
        """anomaly='on' must add NO clock reads to the warmed serving
        loop relative to off: every detector is fed from the
        timestamps and counters the loop already takes."""
        sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
        counts = {}
        for mode in ("off", "on"):
            eng = make_engine(model, anomaly=mode)
            tok = run_to_first_token(eng)
            eng.put(0, [int(tok)])
            real = time.perf_counter
            n = [0]

            def counting():
                n[0] += 1
                return real()
            time.perf_counter = counting
            try:
                eng.step(sampling=sp)
            finally:
                time.perf_counter = real
            counts[mode] = n[0]
        assert counts["on"] == counts["off"], counts


# --------------------------------------------------------------------------
# flight recorder
# --------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_bounded_and_validator(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.note("step_failure", step=i)
        evs = fr.events()
        assert len(evs) == 4 and evs[-1]["step"] == 9
        snap = fr.snapshot("unit")
        assert validate_flight_dump(snap) == []
        assert snap["fingerprint"]["config_hash"] \
            == config_fingerprint()["config_hash"]
        bad = dict(snap)
        del bad["spans"]
        bad["version"] = 99
        problems = validate_flight_dump(bad)
        assert any("spans" in p for p in problems)
        assert any("version" in p for p in problems)

    def test_auto_dump_on_engine_dead(self, model, tmp_path):
        from deepspeed_tpu.inference import EngineDeadError

        eng = make_engine(
            model, trace=True,
            failure=FailureConfig(dispatch_timeout_ms=None,
                                  flight_dir=str(tmp_path)))
        tok = run_to_first_token(eng)
        eng.put(0, [int(tok)])
        eng.failures.inject("fatal")
        with pytest.raises(EngineDeadError):
            eng.step(sampling=SamplingParams(temperature=0.0,
                                             max_new_tokens=1 << 30))
        dumps = sorted(tmp_path.glob("flight_engine_dead_*.json"))
        assert dumps, "engine death left no black box"
        snap = json.loads(dumps[0].read_text())
        assert validate_flight_dump(snap) == []
        assert snap["reason"] == "engine_dead"
        assert snap["health"]["state"] == "dead"
        # spans + metrics + fingerprint + breadcrumbs all present
        assert snap["spans"], "tracer spans missing from the dump"
        assert snap["metrics"]["serving_steps_total"] >= 1
        assert snap["fingerprint"]["engine_version"]
        kinds = {e["kind"] for e in snap["events"]}
        assert {"step_failure", "engine_dead"} <= kinds

    def test_debug_dump_on_demand(self, model, tmp_path):
        eng = make_engine(model)
        run_to_first_token(eng)
        p = tmp_path / "box.json"
        snap = eng.debug_dump(str(p))
        assert validate_flight_dump(snap) == []
        assert validate_flight_dump(json.loads(p.read_text())) == []
        assert snap["reason"] == "debug"
        assert snap["device"] is None     # telemetry off -> honest None

    def test_watchdog_expiry_auto_dumps(self, model, tmp_path):
        eng = make_engine(
            model,
            failure=FailureConfig(dispatch_timeout_ms=None,
                                  flight_dir=str(tmp_path)))
        tok = run_to_first_token(eng)
        eng.put(0, [int(tok)])
        eng.failures.inject("timeout")
        eng.step(sampling=SamplingParams(temperature=0.0,
                                         max_new_tokens=1 << 30))
        assert sorted(tmp_path.glob("flight_watchdog_expiry_*.json"))

    def test_no_flight_dir_means_no_files(self, model, tmp_path,
                                          monkeypatch):
        monkeypatch.chdir(tmp_path)       # any stray write would land here
        eng = make_engine(model)
        run_to_first_token(eng)
        eng.put(0, [5])
        eng.failures.inject("transient")
        eng.step(sampling=SamplingParams(temperature=0.0,
                                         max_new_tokens=1 << 30))
        assert list(tmp_path.glob("*.json")) == []
        # ...but the breadcrumb is in the ring for a later debug_dump
        assert any(e["kind"] == "step_failure"
                   for e in eng.flight.events())


# --------------------------------------------------------------------------
# training-engine compile observatory
# --------------------------------------------------------------------------

class TestTrainingCompileObservatory:
    def _engine(self, **telemetry):
        import deepspeed_tpu as ds

        m = build_model("gpt2", max_seq_len=32, num_layers=2, d_model=32,
                        num_heads=2, vocab_size=64)
        return ds.initialize(model=m, config={
            "train_micro_batch_size_per_device": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "mesh": {"data": -1},
            "steps_per_print": 1000,
            "telemetry": telemetry,
        }), m

    def _batch(self, eng):
        from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                      synthetic_lm_data)

        data = synthetic_lm_data(64, eng.train_batch_size * 4, 32)
        return next(iter(DataLoader(data, eng.train_batch_size)))

    def test_compile_and_retrace_counters(self):
        eng, _ = self._engine()
        assert eng.devtel is None         # device off by default
        for _ in range(2):
            eng.train_batch(self._batch(eng))
        snap = eng.metrics_snapshot()
        assert snap["training_compiles_total"] == 1
        assert snap["training_compile_retraces_total"] == 0
        # an invalidated step executable rebuilt at runtime is a
        # retrace, counted exactly once
        eng._train_step_fn = None
        eng.train_batch(self._batch(eng))
        snap = eng.metrics_snapshot()
        assert snap["training_compiles_total"] == 2
        assert snap["training_compile_retraces_total"] == 1

    def test_device_telemetry_gated_and_probing(self):
        eng, _ = self._engine(device=True)
        assert eng.devtel is not None
        eng.train_batch(self._batch(eng))
        assert "train_step" in eng.devtel.program_costs
        snap = eng.metrics_snapshot()
        assert snap["training_model_flops_total"] > 0
        assert "training_mfu" not in snap      # CPU: no peak
