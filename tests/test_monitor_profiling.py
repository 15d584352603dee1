"""Monitor + flops profiler + env report tests (reference analogs:
tests/unit/monitor/test_monitor.py, profiling tests)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from tests.simple_model import make_batch, make_mlp


class TestMonitor:
    def test_csv_monitor_writes(self, tmp_path):
        from deepspeed_tpu.monitor import CSVMonitor
        from deepspeed_tpu.config.config import CSVConfig

        mon = CSVMonitor(CSVConfig(enabled=True, output_path=str(tmp_path),
                                   job_name="job"))
        mon.write_scalars(1, {"Train/loss": 0.5, "Train/lr": 1e-3})
        mon.write_scalars(2, {"Train/loss": 0.4})
        mon.flush()
        path = tmp_path / "job" / "Train_loss.csv"
        rows = [l.split(",") for l in path.read_text().splitlines()]
        assert [r[0] for r in rows] == ["1", "2"]
        assert float(rows[1][1]) == 0.4
        mon.close()

    def test_tensorboard_monitor(self, tmp_path):
        pytest.importorskip("torch.utils.tensorboard")
        from deepspeed_tpu.monitor import TensorBoardMonitor
        from deepspeed_tpu.config.config import TensorBoardConfig

        mon = TensorBoardMonitor(TensorBoardConfig(
            enabled=True, output_path=str(tmp_path), job_name="tb"))
        mon.write_scalars(1, {"loss": 1.0})
        mon.flush()
        files = list((tmp_path / "tb").iterdir())
        assert any("tfevents" in f.name for f in files)
        mon.close()

    def test_master_fans_out(self, tmp_path):
        from deepspeed_tpu.monitor import MonitorMaster

        cfg = ds.load_config({
            "train_micro_batch_size_per_device": 1,
            "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                            "job_name": "m"}})
        mon = MonitorMaster(cfg)
        assert mon.enabled
        mon.write_scalars(3, {"x": 1.5})
        mon.flush()
        assert (tmp_path / "m" / "x.csv").read_text().startswith("3,1.5")

    def test_engine_autobuilds_monitor(self, tmp_path):
        p, ax, loss_fn = make_mlp()
        eng = ds.initialize(loss_fn=loss_fn, params=p, param_axes=ax, config={
            "train_micro_batch_size_per_device": 4,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "mesh": {"data": 8}, "steps_per_print": 1000,
            "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                            "job_name": "run"}})
        assert eng.monitor is not None
        eng.train_batch(make_batch(eng.train_batch_size))
        eng.monitor.flush()
        assert (tmp_path / "run" / "Train_loss.csv").exists()


class TestFlopsProfiler:
    def test_analyze_matmul_flops(self):
        from deepspeed_tpu.profiling import FlopsProfiler

        a = jnp.ones((128, 256), jnp.float32)
        b = jnp.ones((256, 64), jnp.float32)
        prof = FlopsProfiler()
        stats = prof.profile(lambda x, y: x @ y, a, b)
        # 2*M*N*K flops expected from the compiler's cost model
        assert stats.get("flops", 0) >= 2 * 128 * 256 * 64 * 0.9
        assert stats["latency_s"] > 0

    def test_report_and_strings(self):
        from deepspeed_tpu.profiling import (FlopsProfiler, flops_to_string,
                                             params_to_string)

        assert flops_to_string(2.5e12).startswith("2.50 T")
        assert params_to_string(7e9).startswith("7.00 G")
        rep = FlopsProfiler.report({"flops": 1e9, "latency_s": 0.1,
                                    "params": 1e6, "tflops_per_s": 0.01},
                                   batch_size=8)
        assert "Flops Profiler" in rep and "samples/second" in rep

    def test_engine_profile_step(self, tmp_path, capsys):
        out = tmp_path / "prof.txt"
        p, ax, loss_fn = make_mlp()
        eng = ds.initialize(loss_fn=loss_fn, params=p, param_axes=ax, config={
            "train_micro_batch_size_per_device": 4,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "mesh": {"data": 8}, "steps_per_print": 1000,
            "flops_profiler": {"enabled": True, "profile_step": 2,
                               "output_file": str(out)}})
        for i in range(3):
            eng.train_batch(make_batch(eng.train_batch_size, seed=i))
        assert out.exists()
        assert "flops per step" in out.read_text()

    def test_get_model_profile(self):
        from deepspeed_tpu.profiling import get_model_profile

        flops, macs, params = get_model_profile(
            lambda x: (x @ jnp.ones((64, 64))).sum(),
            args=(jnp.ones((8, 64)),), print_profile=False)
        assert "FLOPs" in flops and "MACs" in macs


class TestStepInterval:
    def test_step_time_is_the_interval_between_entries(self):
        """The engine's own speed figures divide by the interval between
        consecutive ``train_batch`` entries, not by the asynchronous
        dispatch call: a fake clock on which a step lasts 0.5 s and the
        whole of ``train_batch`` 3 ms."""
        p, ax, loss_fn = make_mlp()
        eng = ds.initialize(loss_fn=loss_fn, params=p, param_axes=ax, config={
            "train_micro_batch_size_per_device": 4,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "mesh": {"data": 8}, "steps_per_print": 1000})

        class Clock:
            """The tracer's three calls, each a millisecond after the
            last; ``t`` is set by the test before every step."""
            t = 0.0

            def phase(self, name, **kw):
                self.t += 1e-3
                return self.t

            def phase_end(self, **kw):
                return self.phase(None)

        clock = eng.tracer = Clock()
        steps = 7
        for i in range(steps):
            clock.t = 100.0 + 0.5 * i
            eng.train_batch(make_batch(eng.train_batch_size, seed=i))
        snap = eng.metrics_snapshot()
        gaps = snap["training_step_interval_ms"]
        assert gaps["count"] == steps - 1
        assert gaps["sum"] == pytest.approx(500.0 * (steps - 1))
        assert snap["training_step_host_ms"]["sum"] == pytest.approx(
            3.0 * steps)
        # the first two intervals (compile, warm-up) are left out
        counted = steps - 1 - eng.tput.start_step
        assert eng.tput.total_elapsed_time == pytest.approx(0.5 * counted)
        assert eng.tput.avg_samples_per_sec() == pytest.approx(
            eng.train_batch_size / 0.5)


class TestEnvReport:
    def test_env_report_runs(self, capsys):
        from deepspeed_tpu.env_report import main

        assert main() == 0
        out = capsys.readouterr().out
        assert "jax" in out and "environment report" in out


class TestCometMonitor:
    def test_missing_dep_degrades(self):
        """comet enabled without comet_ml: MonitorMaster warns and keeps
        the other writers (same contract as wandb)."""
        from deepspeed_tpu.config.config import load_config
        from deepspeed_tpu.monitor import MonitorMaster

        cfg = load_config({"train_micro_batch_size_per_device": 1,
                           "comet": {"enabled": True}})
        mm = MonitorMaster(cfg)
        assert not any(type(w).__name__ == "CometMonitor"
                       for w in mm.writers)

    def test_logs_with_fake_comet(self, monkeypatch):
        import sys
        import types

        logged = []

        class FakeExperiment:
            def __init__(self, **kw):
                self.kw = kw

            def set_name(self, n):
                self.name = n

            def log_metric(self, name, value, step=None):
                logged.append((name, value, step))

            def end(self):
                pass

        fake = types.ModuleType("comet_ml")
        fake.Experiment = FakeExperiment
        fake.OfflineExperiment = FakeExperiment
        monkeypatch.setitem(sys.modules, "comet_ml", fake)

        from deepspeed_tpu.config.config import load_config
        from deepspeed_tpu.monitor import MonitorMaster

        cfg = load_config({"train_micro_batch_size_per_device": 1,
                           "comet": {"enabled": True,
                                     "samples_log_interval": 2,
                                     "experiment_name": "t"}})
        mm = MonitorMaster(cfg)
        assert mm.enabled
        mm.write_events([("Train/loss", 1.0, 1), ("Train/loss", 2.0, 2)])
        mm.close()
        # interval=2: only the step-2 event lands
        assert logged == [("Train/loss", 2.0, 2)]
