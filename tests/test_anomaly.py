"""Anomaly detection & deep capture (docs/OBSERVABILITY.md "Anomaly
detection & deep capture"): detector math under fake step clocks
(warmup, cooldown, budget exhaustion, reset rearm), the engine wiring
(counter + flight breadcrumbs + health degradation on sustained
fires), capture-window lifecycle on CPU (artifact layout, absent-
profiler degradation, budget), merged-trace schema validation of a
real exported file, and the xplane fallback decoder in
tools/tracemerge.py."""

import json
import os

import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     SamplingParams)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.telemetry import (AnomalyConfig, AnomalyMonitor,
                                     EwmaMadDetector, MetricsRegistry,
                                     ProfilerCapture,
                                     RollingPercentileDetector,
                                     ThresholdDetector,
                                     default_serving_detectors,
                                     default_training_detectors)
from tools.tracemerge import (decode_xspace, merge_capture,
                              validate_merged_trace,
                              xplane_chrome_events)


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


SP = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)


def run_steps(eng, n, uid=0, prompt=8):
    eng.put(uid, list(range(1, prompt + 1)))
    done = 0
    while done < n:
        out = eng.step(sampling=SP)
        done += 1
        if uid in out:
            eng.put(uid, [out[uid]])
    return eng


@pytest.fixture(scope="module")
def model():
    return tiny_model()


# --------------------------------------------------------------------------
# detector math — pure value streams + integer steps, no clocks
# --------------------------------------------------------------------------

class TestEwmaMad:
    def test_warmup_suppresses_even_huge_spikes(self):
        det = EwmaMadDetector(warmup=8, z_threshold=3.0)
        for _ in range(7):
            assert det.observe(10.0) is None
        # 8th sample: still inside warmup — a 100x spike must not fire
        assert det.observe(1000.0) is None

    def test_fires_after_warmup_with_robust_z(self):
        det = EwmaMadDetector(warmup=8, z_threshold=8.0)
        for _ in range(20):
            assert det.observe(10.0) is None
        fired = det.observe(100.0)
        assert fired is not None
        baseline, z = fired
        assert baseline == pytest.approx(10.0)
        # constant stream -> MAD 0 -> scale floored at 5% of baseline
        assert z == pytest.approx((100.0 - 10.0) / 0.5)

    def test_scale_floor_absorbs_noise(self):
        det = EwmaMadDetector(warmup=8, z_threshold=8.0)
        for i in range(30):
            det.observe(10.0 + 0.1 * (i % 2))
        # +8% is inside the floored band
        assert det.observe(10.9) is None

    def test_direction_low_and_both(self):
        low = EwmaMadDetector(warmup=4, z_threshold=4.0,
                              direction="low")
        both = EwmaMadDetector(warmup=4, z_threshold=4.0,
                               direction="both")
        for _ in range(10):
            low.observe(10.0)
            both.observe(10.0)
        assert low.observe(100.0) is None       # high spike: wrong side
        assert low.observe(0.1) is not None
        assert both.observe(100.0) is not None

    def test_deterministic(self):
        a = EwmaMadDetector(warmup=4, z_threshold=5.0)
        b = EwmaMadDetector(warmup=4, z_threshold=5.0)
        stream = [5.0, 5.5, 4.5, 5.0, 5.2, 40.0, 5.1, 60.0]
        assert [a.observe(v) for v in stream] \
            == [b.observe(v) for v in stream]

    def test_reset_restarts_warmup(self):
        det = EwmaMadDetector(warmup=4, z_threshold=4.0)
        for _ in range(10):
            det.observe(1.0)
        det.reset()
        assert det.observe(100.0) is None       # warming up again
        assert det.baseline == pytest.approx(100.0)

    def test_rejects_bad_direction(self):
        with pytest.raises(ValueError):
            EwmaMadDetector(direction="sideways")


class TestThresholdAndPercentile:
    def test_threshold_zero_limit_is_the_retrace_detector(self):
        det = ThresholdDetector(limit=0.0, warmup=1)
        assert det.observe(1.0) is None         # the first compile wave
        assert det.observe(0.0) is None
        fired = det.observe(2.0)
        assert fired == (0.0, 2.0)

    def test_percentile_low_side_collapse(self):
        det = RollingPercentileDetector(warmup=8, window=32, q=0.95,
                                        ratio=2.0, direction="low")
        for i in range(20):
            assert det.observe(0.5 + 0.01 * (i % 3)) is None
        fired = det.observe(0.1)                # 0.1 * 2 < ~0.5
        assert fired is not None
        assert fired[1] > 1.0                   # band-exceedance ratio

    def test_percentile_high_side(self):
        det = RollingPercentileDetector(warmup=8, window=32, q=0.95,
                                        ratio=2.0, direction="high")
        for _ in range(10):
            det.observe(1.0)
        assert det.observe(1.5) is None
        assert det.observe(3.0) is not None


# --------------------------------------------------------------------------
# monitor: cooldown, sustained window, counter, reset — fake step clock
# --------------------------------------------------------------------------

class TestMonitor:
    def _monitor(self, **cfg):
        reg = MetricsRegistry()
        mon = AnomalyMonitor(AnomalyConfig(**cfg), reg, "serving")
        mon.watch("sig", ThresholdDetector(limit=0.0, warmup=0))
        return mon, reg

    def test_cooldown_suppresses_but_keeps_learning(self):
        mon, _ = self._monitor(cooldown=5)
        fires = [mon.observe("sig", 1.0, step) for step in range(11)]
        assert [f is not None for f in fires] == \
            [s in (0, 5, 10) for s in range(11)]
        assert mon.counts["sig"] == 3

    def test_counter_labeled_by_signal(self):
        mon, reg = self._monitor(cooldown=0)
        mon.watch("other", ThresholdDetector(limit=0.0, warmup=0))
        mon.observe("sig", 1.0, 0)
        mon.observe("other", 1.0, 0)
        mon.observe("sig", 1.0, 1)
        c = reg.get("serving_anomalies_total")
        assert c.value(signal="sig") == 2
        assert c.value(signal="other") == 1
        text = reg.prometheus_text()
        assert 'serving_anomalies_total{signal="sig"} 2' in text

    def test_unwatched_signal_is_ignored(self):
        mon, _ = self._monitor()
        assert mon.observe("nope", 1e9, 0) is None

    def test_sustained_window(self):
        mon, _ = self._monitor(cooldown=0, sustained_count=2,
                               sustained_window=10)
        assert not mon.sustained(0)
        mon.observe("sig", 1.0, 3)
        assert not mon.sustained(3)             # one fire < count
        mon.observe("sig", 1.0, 5)
        assert mon.sustained(5)
        assert mon.sustained(13)                # 5 + window still in
        assert not mon.sustained(50)            # both fires aged out

    def test_event_shape_and_summary(self):
        mon, _ = self._monitor(cooldown=0)
        ev = mon.observe("sig", 2.5, 7)
        d = ev.as_dict()
        assert d["signal"] == "sig" and d["step"] == 7
        assert d["observed"] == 2.5 and d["detector"] == "threshold"
        s = mon.summary()
        assert s["total"] == 1 and s["by_signal"] == {"sig": 1}
        assert s["recent"][-1]["signal"] == "sig"
        json.dumps(s)

    def test_reset_rearms_everything(self):
        mon, _ = self._monitor(cooldown=100, sustained_count=1,
                               sustained_window=1000)
        mon.observe("sig", 1.0, 0)
        assert mon.total() == 1 and mon.sustained(1)
        mon.reset()
        assert mon.total() == 0 and not mon.sustained(1)
        # cooldown ledger cleared too: an immediate re-fire lands
        assert mon.observe("sig", 1.0, 1) is not None

    def test_default_catalogs_cover_the_issue_signals(self):
        cfg = AnomalyConfig()
        serving = default_serving_detectors(cfg)
        for sig in ("step_interval_ms", "step_device_ms",
                    "step_wait_ms", "step_host_ms", "ttft_ms",
                    "tpot_ms", "retrace", "kv_referenced_delta",
                    "prefix_hit_rate", "spec_acceptance"):
            assert sig in serving, sig
        training = default_training_detectors(cfg)
        assert {"step_interval_ms", "step_host_ms",
                "retrace"} <= set(training)


# --------------------------------------------------------------------------
# engine wiring: counter + flight + health + reset rearm
# --------------------------------------------------------------------------

class TestEngineWiring:
    def test_default_engine_has_no_monitor_or_capture(self, model):
        eng = make_engine(model)                # anomaly "auto" == off
        assert eng._anom is None and eng._cap is None
        assert eng.anomaly_summary() is None
        assert eng.capture_dirs == []
        assert eng.health()["anomalies"] == 0

    def test_invalid_mode_rejected(self, model):
        with pytest.raises(ValueError, match="anomaly"):
            make_engine(model, anomaly="loud")

    def _forced_anomaly_engine(self, model, **acfg):
        cfg = AnomalyConfig(cooldown=0, sustained_count=2,
                            sustained_window=1000, **acfg)
        eng = make_engine(model, anomaly="on", anomaly_cfg=cfg)
        # deterministic forcing: every dispatched step fires this
        eng._anom.watch("step_device_ms",
                        ThresholdDetector(limit=-1.0, warmup=0))
        return eng

    def test_sustained_anomalies_degrade_health_and_gauge(self, model):
        eng = self._forced_anomaly_engine(model)
        run_steps(eng, 4)
        h = eng.health()
        assert h["anomalies"] >= 2
        assert h["state"] == "degraded"
        assert eng.metrics.get("serving_health_state").value() == 1
        # the labeled counter is scrape-visible
        c = eng.metrics.get("serving_anomalies_total")
        assert c is not None \
            and c.value(signal="step_device_ms") >= 2

    def test_anomaly_lands_in_flight_dump(self, model):
        eng = self._forced_anomaly_engine(model)
        run_steps(eng, 3)
        snap = eng.debug_dump()
        evs = [e for e in snap["events"] if e["kind"] == "anomaly"]
        assert evs, snap["events"]
        e = evs[0]
        assert e["signal"] == "step_device_ms"
        assert {"observed", "baseline", "score", "step",
                "detector"} <= set(e)
        assert snap["anomalies"]["total"] >= 1

    def test_no_capture_dir_fires_but_skips_capture(self, model):
        eng = self._forced_anomaly_engine(model)
        run_steps(eng, 3)
        assert eng._anom.total() >= 1
        assert eng.capture_dirs == []           # nowhere to write

    def test_reset_metrics_rearms_detectors_and_budget(self, model,
                                                       tmp_path):
        eng = self._forced_anomaly_engine(model)
        eng._cap = ProfilerCapture(str(tmp_path), tracer=eng.tracer,
                                   max_captures=1)
        eng._cap._budget_used = 1
        run_steps(eng, 3)
        assert eng._anom.total() >= 1
        eng.reset_metrics()
        assert eng._anom.total() == 0
        assert eng._cap.budget_left() == 1
        c = eng.metrics.get("serving_anomalies_total")
        assert c.value(signal="step_device_ms") == 0

    def test_explicit_capture_without_dir_raises(self, model):
        eng = make_engine(model)
        with pytest.raises(ValueError, match="capture directory"):
            eng.capture(steps=1)


# --------------------------------------------------------------------------
# capture-window lifecycle on CPU
# --------------------------------------------------------------------------

class TestCaptureWindow:
    def test_profile_config_arms_and_completes(self, model, tmp_path):
        d = str(tmp_path / "prof")
        eng = make_engine(model, profile=d, profile_steps=2)
        assert eng._cap is not None and eng._cap.armed
        run_steps(eng, 4)
        assert len(eng.capture_dirs) == 1
        cdir = eng.capture_dirs[0]
        names = set(os.listdir(cdir))
        assert {"meta.json", "host_trace.json",
                "flight.json"} <= names
        with open(os.path.join(cdir, "meta.json")) as f:
            meta = json.load(f)
        assert meta["reason"] == "config" and meta["steps"] == 2
        assert meta["t_stop_perf_ns"] > meta["t_start_perf_ns"]
        assert meta["t_start_epoch_ns"] > 0
        # the host trace is a loadable Chrome trace of the window only
        with open(os.path.join(cdir, "host_trace.json")) as f:
            host = json.load(f)
        tracks = {e["args"]["name"] for e in host["traceEvents"]
                  if e.get("name") == "thread_name"}
        assert "dispatch" in tracks
        # the flight dump rode along
        with open(os.path.join(cdir, "flight.json")) as f:
            flight = json.load(f)
        assert flight["reason"] == "capture"
        # the tracer was force-enabled for the window, then restored
        assert eng.tracer.enabled is False

    def test_absent_profiler_degrades_loudly_but_completes(
            self, model, tmp_path, monkeypatch):
        import jax.profiler

        def broken(*a, **k):
            raise RuntimeError("no profiler in this build")
        monkeypatch.setattr(jax.profiler, "start_trace", broken)
        d = str(tmp_path / "prof")
        eng = make_engine(model, profile=d, profile_steps=1)
        run_steps(eng, 3)
        assert len(eng.capture_dirs) == 1
        cdir = eng.capture_dirs[0]
        with open(os.path.join(cdir, "meta.json")) as f:
            meta = json.load(f)
        assert meta["profiler"] is False
        assert meta["device_dir"] is None
        # merge still works, host-only, and says the device is absent
        out = merge_capture(cdir)
        with open(out) as f:
            merged = json.load(f)
        assert merged["otherData"]["device_absent"] is True
        assert validate_merged_trace(merged, require_device=False) == []
        assert validate_merged_trace(merged)  # device required -> fails
        # the host-only fallback: spans from the ring, placed by the
        # anchor, which states its own uncertainty (both sides of
        # start_trace were stamped)
        clock = merged["otherData"]["clock"]
        assert clock["host_spans_from"] == "host_trace"
        assert clock["offset_measured"] is False
        assert clock["anchor_uncertainty_us"] >= 0.0

    def test_budget_and_one_window_at_a_time(self, tmp_path):
        cap = ProfilerCapture(str(tmp_path), max_captures=1)
        assert cap.arm(2, "a", budgeted=True) is not None
        assert cap.arm(2, "b", budgeted=True) is None   # already armed
        cap._armed = None
        assert cap.arm(2, "c", budgeted=True) is None   # budget spent
        assert cap.arm(2, "d", budgeted=False) is not None  # explicit ok
        cap._armed = None
        cap.reset_budget()
        assert cap.arm(2, "e", budgeted=True) is not None

    def test_end_step_without_begin_is_noop(self, tmp_path):
        cap = ProfilerCapture(str(tmp_path))
        assert cap.end_step() is None
        assert cap.finish_now() is None

    def test_oversized_window_closes_when_generate_ends(
            self, model, tmp_path):
        """A window armed for more steps than the workload will run
        must not strand the process-wide profiler session: generate()
        closes it with the steps it has, the artifact is written, and
        a later capture can own the session again."""
        from deepspeed_tpu.telemetry import profiler as profiler_mod

        eng = make_engine(model)
        d = eng.capture(steps=1000, reason="oversized",
                        out_dir=str(tmp_path))
        out = eng.generate({0: [1, 2, 3, 4]},
                           SamplingParams(temperature=0.0,
                                          max_new_tokens=4))
        assert out[0]
        assert not eng._cap.active
        assert profiler_mod._TRACE_OWNER == []      # session released
        assert d in eng.capture_dirs                # artifact written
        assert eng.tracer.enabled is False          # tracer restored
        d2 = eng.capture(steps=1, reason="again")
        eng.generate({1: [1, 2, 3]},
                     SamplingParams(temperature=0.0, max_new_tokens=2))
        with open(os.path.join(d2, "meta.json")) as f:
            assert json.load(f)["profiler"] is True

    def test_unusable_dir_drops_window_and_refunds_budget(self,
                                                          tmp_path):
        blocker = tmp_path / "blocker"
        blocker.write_text("")       # a FILE where the dir must go
        cap = ProfilerCapture(str(blocker), max_captures=1)
        assert cap.arm(1, "a", budgeted=True) is not None
        cap.begin()                  # makedirs fails -> window dropped
        assert not cap.active and not cap.armed
        assert cap.budget_left() == 1      # nothing produced: refunded
        assert cap.captures == []


# --------------------------------------------------------------------------
# merged-trace schema validation of a real exported file (CPU backend)
# --------------------------------------------------------------------------

class TestMergedTrace:
    def test_real_capture_merges_with_host_and_device_events(
            self, model, tmp_path):
        d = str(tmp_path / "prof")
        eng = make_engine(model, profile=d, profile_steps=2)
        run_steps(eng, 4)
        assert eng.capture_dirs
        out = merge_capture(eng.capture_dirs[0])
        with open(out) as f:
            merged = json.load(f)
        # the acceptance bar: valid Chrome-trace JSON with BOTH host
        # SpanTracer tracks and device-derived events on one timeline
        assert validate_merged_trace(merged) == []
        assert merged["otherData"]["device_absent"] is False
        assert merged["otherData"]["host_events"] > 0
        assert merged["otherData"]["device_events"] > 0
        # host spans still carry their step sid for the cross-join
        sids = [e["args"]["sid"] for e in merged["traceEvents"]
                if e.get("pid") == 1 and e.get("ph") == "X"
                and isinstance(e.get("args"), dict)
                and "sid" in e["args"]]
        assert sids and all(isinstance(s, int) for s in sids)
        # ... and they were taken from the device artifact, where the
        # tracer's profiler mirror wrote them on the device's clock;
        # the offset to perf_counter is measured from the spans that
        # are in the ring too, and the old anchor's error is stated
        clock = merged["otherData"]["clock"]
        assert clock["host_spans_from"] == "device_artifact"
        assert clock["offset_measured"] is True
        assert abs(clock["anchor_error_us"]) < 60e6
        ring = json.load(open(os.path.join(eng.capture_dirs[0],
                                           "host_trace.json")))
        ring_ts = {(e["name"], e["args"]["sid"]): e["ts"]
                   for e in ring["traceEvents"] if e.get("ph") == "X"
                   and "sid" in e.get("args", {})}
        both = [(e["ts"], ring_ts[(e["name"], e["args"]["sid"])])
                for e in merged["traceEvents"]
                if e.get("pid") == 1 and e.get("ph") == "X"
                and (e["name"], e.get("args", {}).get("sid")) in ring_ts]
        assert both
        # one clock: once the one offset is applied the matched spans
        # land on their perf_counter stamps (the typical one within a
        # millisecond; a preempted thread may stretch a single one)
        diffs = sorted(abs(a - b) for a, b in both)
        assert diffs[len(diffs) // 2] < 1000.0 and diffs[-1] < 1e6
        with open(os.path.join(eng.capture_dirs[0], "meta.json")) as f:
            meta = json.load(f)
        assert meta["t_before_start_perf_ns"] <= meta["t_start_perf_ns"]

    def test_validator_rejects_junk(self):
        assert validate_merged_trace({}) \
            == ["traceEvents missing or empty"]
        assert validate_merged_trace({"traceEvents": [{"x": 1}]})


# --------------------------------------------------------------------------
# training engine wiring (config {"telemetry": {"anomaly"/"profile"}})
# --------------------------------------------------------------------------

class TestTrainingEngine:
    def _engine(self, **telemetry):
        import deepspeed_tpu as ds

        m = build_model("gpt2", max_seq_len=32, num_layers=2,
                        d_model=32, num_heads=2, vocab_size=64)
        return ds.initialize(model=m, config={
            "train_micro_batch_size_per_device": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "mesh": {"data": -1},
            "steps_per_print": 10_000,
            "telemetry": telemetry,
        }), m

    def _batch(self, eng):
        from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                      synthetic_lm_data)

        data = synthetic_lm_data(64, eng.train_batch_size * 4, 32)
        return next(iter(DataLoader(data, eng.train_batch_size)))

    def test_default_off_and_anomaly_counter(self):
        eng, _ = self._engine()
        assert eng._anom is None and eng._cap is None
        assert eng.anomaly_summary() is None
        eng2, _ = self._engine(anomaly=True)
        # deterministic forcing, as on the serving side
        eng2._anom.watch("step_host_ms",
                         ThresholdDetector(limit=-1.0, warmup=0))
        eng2._anom.cfg.cooldown = 0
        b = self._batch(eng2)
        for _ in range(3):
            eng2.train_batch(b)
        s = eng2.anomaly_summary()
        assert s["by_signal"].get("step_host_ms", 0) >= 2
        c = eng2.metrics.get("training_anomalies_total")
        assert c.value(signal="step_host_ms") >= 2

    def test_profile_config_captures_and_merges(self, tmp_path):
        d = str(tmp_path / "train_prof")
        eng, _ = self._engine(profile=d, profile_steps=2)
        assert eng._cap is not None and eng._cap.armed
        b = self._batch(eng)
        for _ in range(3):
            eng.train_batch(b)
        assert len(eng.capture_dirs) == 1
        out = merge_capture(eng.capture_dirs[0])
        with open(out) as f:
            merged = json.load(f)
        assert validate_merged_trace(merged) == []
        # the step's phases come from the device artifact itself (the
        # tracer's profiler mirror), one host track per thread
        assert merged["otherData"]["clock"]["host_spans_from"] \
            == "device_artifact"
        host = [e for e in merged["traceEvents"]
                if e.get("pid") == 1 and e.get("ph") == "X"]
        assert {"ds.train.pre_step", "ds.train.stage",
                "ds.train.dispatch"} <= {e["name"] for e in host}
        assert {e["args"]["step"] for e in host
                if e["name"] == "ds.train.dispatch"} == {1, 2}


# --------------------------------------------------------------------------
# xplane fallback decoder (tools/tracemerge.py) — synthetic protobuf
# --------------------------------------------------------------------------

def _vint(n):
    out = b""
    while True:
        b7 = n & 0x7F
        n >>= 7
        out += bytes([b7 | (0x80 if n else 0)])
        if not n:
            return out


def _lenf(fno, payload):
    return _vint((fno << 3) | 2) + _vint(len(payload)) + payload


def _intf(fno, v):
    return _vint(fno << 3) + _vint(v)


class TestXplaneDecoder:
    def _space(self):
        event = _intf(1, 7) + _intf(2, 2_000_000) + _intf(3, 5_000_000)
        evmeta = _intf(1, 7) + _lenf(2, b"fusion.42")
        map_entry = _intf(1, 7) + _lenf(2, evmeta)
        line = (_intf(1, 3) + _lenf(2, b"XLA Ops") + _intf(3, 1_000)
                + _lenf(4, event))
        plane = (_lenf(2, b"/device:TPU:0") + _lenf(3, line)
                 + _lenf(4, map_entry))
        return _lenf(1, plane)

    def test_decode_xspace_structure(self):
        planes = decode_xspace(self._space())
        assert len(planes) == 1
        p = planes[0]
        assert p["name"] == "/device:TPU:0"
        assert p["event_metadata"] == {7: "fusion.42"}
        (line,) = p["lines"]
        assert line["name"] == "XLA Ops" and line["timestamp_ns"] == 1000
        (ev,) = line["events"]
        assert ev == {"metadata_id": 7, "offset_ps": 2_000_000,
                      "duration_ps": 5_000_000}

    def _host_space(self):
        """A host plane with one ``ds.serve.dispatch`` TraceMe carrying
        its args as stats (sid: int64, hop_us: double), one foreign
        event, and a device plane."""
        import struct

        def dblf(fno, v):
            return _vint((fno << 3) | 1) + struct.pack("<d", v)
        stats = (_lenf(4, _intf(1, 1) + _intf(4, 17))
                 + _lenf(4, _intf(1, 2) + dblf(2, 12.5)))
        ev = _intf(1, 7) + _intf(2, 2_000_000) + _intf(3, 5_000_000) + stats
        other = _intf(1, 8) + _intf(2, 0) + _intf(3, 1_000_000)
        line = (_intf(1, 3) + _lenf(2, b"gateway-engine_0") + _intf(3, 1_000)
                + _lenf(4, ev) + _lenf(4, other))
        emeta = [_lenf(4, _intf(1, i) + _lenf(2, _intf(1, i) + _lenf(2, n)))
                 for i, n in ((7, b"ds.serve.dispatch"), (8, b"not.ours"))]
        smeta = [_lenf(5, _intf(1, i) + _lenf(2, _intf(1, i) + _lenf(2, n)))
                 for i, n in ((1, b"sid"), (2, b"hop_us"))]
        host = (_lenf(2, b"/host:CPU") + _lenf(3, line)
                + b"".join(emeta) + b"".join(smeta))
        return _lenf(1, host) + self._space()

    @pytest.mark.parametrize("reader", ["ProfileData", "pure_python"])
    def test_program_spans_come_from_the_xplane_with_their_stats(
            self, tmp_path, monkeypatch, reader):
        """The merge takes the program's ``ds.*`` spans from the xplane
        itself, args included — through jax's ProfileData, or through
        the built-in decoder where that is absent."""
        import jax.profiler

        from tools.tracemerge import program_span_events
        if reader == "pure_python":
            monkeypatch.delattr(jax.profiler, "ProfileData")
        p = tmp_path / "t.xplane.pb"
        p.write_bytes(self._host_space())
        evs = program_span_events(str(p))
        (x,) = [e for e in evs if e["ph"] == "X"]
        assert (x["name"], x["pid"]) == ("ds.serve.dispatch", 1)
        assert x["args"] == {"sid": 17, "hop_us": 12.5}
        assert x["ts"] == pytest.approx(3.0) and x["dur"] == pytest.approx(5.0)
        (track,) = [e for e in evs if e.get("name") == "thread_name"]
        assert track["tid"] == x["tid"]
        assert track["args"]["name"].startswith("gateway-engine_0")
        # an artifact without a ds.* event has no host tracks to give
        q = tmp_path / "d.xplane.pb"
        q.write_bytes(self._space())
        assert program_span_events(str(q)) == []

    def test_chrome_events_from_xplane(self, tmp_path):
        p = tmp_path / "t.xplane.pb"
        p.write_bytes(self._space())
        evs = xplane_chrome_events(str(p), t_session_epoch_ns=0)
        xs = [e for e in evs if e["ph"] == "X"]
        assert len(xs) == 1
        x = xs[0]
        assert x["name"] == "fusion.42"
        # 1000 ns line base + 2e6 ps offset = 3 us
        assert x["ts"] == pytest.approx(3.0)
        assert x["dur"] == pytest.approx(5.0)
        names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert {"/device:TPU:0", "XLA Ops"} <= names
