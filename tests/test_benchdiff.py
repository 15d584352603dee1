"""tools/benchdiff regression sentinel — the tier-1 gate that turns the
BENCH_r* trajectory from an eyeballed log into a guarded one: same
config fingerprint => hard per-leg thresholds (nonzero exit on
regression), changed fingerprint => report-only.  Pure host JSON work,
no JAX."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.benchdiff import (compare, diff_files, main,  # noqa: E402
                             metric_direction, smoke)


def test_smoke_is_the_acceptance_check():
    out = smoke()
    assert out["ok"] and len(out["checks"]) == 9
    assert "anomaly_delta_reports_not_gates" in out["checks"]
    assert "slo_delta_reports_not_gates" in out["checks"]


def test_anomaly_deltas_report_only():
    """``<leg>_anomalies`` totals (PR 10) are listed as deltas but never
    gate — detector fires are rig-noise sensitive."""
    base = {"engine_version": "1.0", "config_hash": "aaaa",
            "value": 100.0,
            "pipe2_anomalies": {"total": 0, "by_signal": {}}}
    noisy = dict(base, pipe2_anomalies={"total": 12,
                                        "by_signal": {"ttft_ms": 12}})
    v = compare(base, noisy)
    assert v["ok"]
    assert v["anomaly_deltas"] == [
        {"metric": "pipe2_anomalies", "old": 0, "new": 12}]
    # a leg whose anomaly subtree is None (anomaly off) stays silent
    off = dict(base, pipe2_anomalies=None)
    assert compare(off, off)["anomaly_deltas"] == []


def test_fleet_anomaly_deltas_report_only():
    """``fleet_*_anomalies`` subtrees (PR 14: {"fleet": ...,
    "replicas": {name: ...}}) report fleet-total and per-replica
    deltas under ``fleet_anomaly_deltas`` but can never fail a run —
    even under a matching fingerprint."""
    base = {"engine_version": "1.0", "config_hash": "aaaa",
            "value": 100.0,
            "fleet_serving_anomalies": {
                "fleet": {"total": 0, "by_signal": {}},
                "replicas": {"r0": {"total": 0}}}}
    stormy = dict(base, fleet_serving_anomalies={
        "fleet": {"total": 9, "by_signal": {"failover_migration_storm": 9}},
        "replicas": {"r0": {"total": 9}}})
    v = compare(base, stormy)
    assert v["ok"], "fleet anomaly deltas must never gate"
    assert v["fleet_anomaly_deltas"] == [
        {"metric": "fleet_serving_anomalies.fleet", "old": 0, "new": 9},
        {"metric": "fleet_serving_anomalies.replicas.r0",
         "old": 0, "new": 9}]
    # not double-counted into the flat anomaly deltas
    assert v["anomaly_deltas"] == []
    assert compare(base, base)["fleet_anomaly_deltas"] == []


def test_metric_direction_classification():
    assert metric_direction("pipe2_decode_tok_s") == 1
    assert metric_direction("value") == 1
    assert metric_direction("shared_prefix_speedup") == 1
    assert metric_direction("goodput_qps_sla4") == 1
    assert metric_direction("mfu") == 1
    assert metric_direction("serving_ttft_p50_ms") == -1
    assert metric_direction("llama8b_int8_decode_ms_per_tok_ema") == -1
    assert metric_direction("platform") is None
    assert metric_direction("steps") is None
    assert metric_direction("config_hash") is None


def test_fleet_leg_metrics_are_gated():
    """The fleet_serving_bench leg's headline metrics (PR 13) land
    top-level under names the EXISTING direction rules gate: goodput /
    hit-rate up-is-better, TTFT ms down-is-better — a fleet goodput or
    affinity regression fails a same-fingerprint benchdiff run."""
    assert metric_direction("fleet_goodput_tok_s") == 1
    assert metric_direction("fleet_single_goodput_tok_s") == 1
    assert metric_direction("fleet_affinity_hit_rate") == 1
    assert metric_direction("fleet_round_robin_hit_rate") == 1
    assert metric_direction("fleet_ttft_p95_prekill_ms") == -1
    assert metric_direction("fleet_ttft_p95_postkill_ms") == -1
    # and a regression actually trips the gate
    base = {"engine_version": "1", "config_hash": "aaaa",
            "value": 100.0, "fleet_goodput_tok_s": 500.0,
            "fleet_affinity_hit_rate": 0.7}
    worse = dict(base, fleet_goodput_tok_s=300.0)
    v = compare(base, worse)
    assert not v["ok"]
    assert any(r["metric"] == "fleet_goodput_tok_s"
               for r in v["regressions"])


def test_http_leg_metrics_are_gated():
    """The http_serving_bench leg (PR 15, the network gateway): its
    headline metrics land top-level under names the EXISTING direction
    rules gate — goodput up-is-better for both columns, TTFT ms
    down-is-better, and the wire-overhead ratio (client-wall TTFT p95
    over in-process engine-record p95) is gated down-is-better via its
    ``ttft`` stem, so a gateway that gets relatively slower fails a
    same-fingerprint compare even when both legs improved."""
    assert metric_direction("http_goodput_tok_s") == 1
    assert metric_direction("inproc_goodput_tok_s") == 1
    assert metric_direction("http_ttft_p95_ms") == -1
    assert metric_direction("inproc_ttft_p95_ms") == -1
    assert metric_direction("http_ttft_overhead_ratio") == -1
    # and an overhead regression actually trips the gate
    base = {"engine_version": "1", "config_hash": "aaaa",
            "value": 100.0, "http_goodput_tok_s": 50.0,
            "http_ttft_overhead_ratio": 1.1}
    worse = dict(base, http_ttft_overhead_ratio=1.6)
    v = compare(base, worse)
    assert not v["ok"]
    assert any(r["metric"] == "http_ttft_overhead_ratio"
               for r in v["regressions"])


def test_tiered_kv_leg_metrics_are_gated():
    """The tiered_kv_serving_bench leg (docs/KV_TIERING.md): its
    headline metrics land top-level under names the EXISTING direction
    rules gate — hit rate up-is-better, the TTFT columns down-is-better
    including ``tiered_kv_ttft_vs_allhbm`` (the 1.25x acceptance bar:
    tiered p95 over the all-HBM ceiling, gated via its ``ttft`` stem),
    and the fleet remote-restage speedup up-is-better — so a tier that
    drifts away from the all-HBM curve or loses to re-prefill fails a
    same-fingerprint compare."""
    assert metric_direction("tiered_kv_hit_rate") == 1
    assert metric_direction("tiered_kv_ttft_p95_ms") == -1
    assert metric_direction("tiered_kv_baseline_ttft_p95_ms") == -1
    assert metric_direction("tiered_kv_allhbm_ttft_p95_ms") == -1
    assert metric_direction("tiered_kv_ttft_vs_allhbm") == -1
    assert metric_direction("tiered_kv_remote_restage_speedup") == 1
    # and drifting off the all-HBM curve actually trips the gate
    base = {"engine_version": "1", "config_hash": "aaaa",
            "value": 100.0, "tiered_kv_hit_rate": 0.6,
            "tiered_kv_ttft_vs_allhbm": 1.2,
            "tiered_kv_remote_restage_speedup": 1.1}
    worse = dict(base, tiered_kv_ttft_vs_allhbm=1.7)
    v = compare(base, worse)
    assert not v["ok"]
    assert any(r["metric"] == "tiered_kv_ttft_vs_allhbm"
               for r in v["regressions"])


def test_disagg_autoscale_leg_metrics_are_gated():
    """The disagg_serving_bench / autoscale_serving_bench legs
    (docs/SERVING.md "Disaggregated pools & elasticity"): their
    headline metrics land top-level under names the EXISTING direction
    rules gate — ``disagg_interactive_speedup`` (colocated TTFT p95
    rounds over disaggregated: the >1.0 acceptance bar) up-is-better
    via its ``speedup`` stem, both TTFT ms columns down-is-better,
    goodput up-is-better — so a PR that erodes the disaggregation win
    fails a same-fingerprint compare."""
    assert metric_direction("disagg_interactive_speedup") == 1
    assert metric_direction("disagg_ttft_p95_interactive_ms") == -1
    assert metric_direction(
        "disagg_colocated_ttft_p95_interactive_ms") == -1
    assert metric_direction("disagg_goodput_tok_s") == 1
    assert metric_direction("disagg_colocated_goodput_tok_s") == 1
    # a speedup erosion actually trips the gate...
    base = {"engine_version": "1", "config_hash": "aaaa",
            "value": 100.0, "disagg_interactive_speedup": 2.0,
            "disagg_ttft_p95_interactive_ms": 40.0}
    worse = dict(base, disagg_interactive_speedup=1.0)
    v = compare(base, worse)
    assert not v["ok"]
    assert any(r["metric"] == "disagg_interactive_speedup"
               for r in v["regressions"])
    # ...and so does the leg disappearing from the capture entirely
    gone = {k: v2 for k, v2 in base.items()
            if not k.startswith("disagg_")}
    v = compare(base, gone)
    assert not v["ok"]
    assert set(v["only_old"]) == {"disagg_interactive_speedup",
                                  "disagg_ttft_p95_interactive_ms"}


def test_matching_fingerprint_enforces_and_exits_nonzero(tmp_path):
    old = {"engine_version": "1", "config_hash": "aaaa",
           "value": 100.0, "serving_decode_tok_s": 700.0}
    new = dict(old, serving_decode_tok_s=400.0)
    po, pn = tmp_path / "old.json", tmp_path / "new.json"
    po.write_text(json.dumps(old))
    pn.write_text(json.dumps(new))
    assert main([str(po), str(pn), "--json"]) == 1
    v = diff_files(str(po), str(pn))
    assert v["enforced"] and not v["ok"]
    assert v["regressions"][0]["metric"] == "serving_decode_tok_s"
    # same capture against itself is green
    assert main([str(po), str(po)]) == 0


def test_mismatched_fingerprint_is_report_only(tmp_path):
    old = {"engine_version": "1", "config_hash": "aaaa",
           "value": 100.0, "serving_decode_tok_s": 700.0}
    new = {"engine_version": "2", "config_hash": "bbbb",
           "value": 100.0, "serving_decode_tok_s": 400.0}
    po, pn = tmp_path / "old.json", tmp_path / "new.json"
    po.write_text(json.dumps(old))
    pn.write_text(json.dumps(new))
    assert main([str(po), str(pn)]) == 0          # reported, not gated
    v = diff_files(str(po), str(pn))
    assert not v["enforced"] and v["ok"] and v["regressions"]


def test_missing_fingerprint_never_enforces():
    # captures from before PR 8 carry no config_hash: nothing
    # to anchor comparability, so the gate must not fire
    v = compare({"value": 100.0}, {"value": 10.0})
    assert not v["enforced"] and v["ok"] and v["regressions"]


def test_diagnostic_subtrees_and_directionless_keys_skipped():
    old = {"config_hash": "x", "engine_version": "1", "value": 10.0,
           "steps": 100, "platform": "cpu",
           "serving_request_metrics": {"ttft_ms": {"p50": 5.0}}}
    new = dict(old, steps=1, platform="tpu",
               serving_request_metrics={"ttft_ms": {"p50": 500.0}})
    assert compare(old, new)["ok"]


def test_latency_direction_and_threshold_boundary():
    base = {"config_hash": "x", "engine_version": "1",
            "serving_ttft_p50_ms": 100.0}
    assert compare(base, dict(base, serving_ttft_p50_ms=114.0))["ok"]
    assert not compare(base, dict(base, serving_ttft_p50_ms=120.0))["ok"]
    # looser threshold clears it
    assert compare(base, dict(base, serving_ttft_p50_ms=120.0),
                   threshold=0.3)["ok"]


def test_dropped_leg_is_a_regression():
    base = {"config_hash": "x", "engine_version": "1",
            "value": 10.0, "spec_decode_speedup": 1.5}
    v = compare(base, {"config_hash": "x", "engine_version": "1",
                       "value": 10.0})
    assert not v["ok"] and v["only_old"] == ["spec_decode_speedup"]


def test_cli_smoke_leg():
    """The wired tier-1 leg: ``python -m tools.benchdiff --smoke``."""
    r = subprocess.run([sys.executable, "-m", "tools.benchdiff",
                        "--smoke"], cwd=REPO, capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["ok"]


def test_capture_files_without_fingerprint_parse_report_only(tmp_path):
    """benchdiff parses bench.py captures from disk; captures with no
    fingerprint (anything older than PR 8) are report-only."""
    old, new = tmp_path / "old.json", tmp_path / "new.json"
    old.write_text(json.dumps({"metric": "gpt2s_train_tokens_per_sec_chip",
                               "value": 100.0, "serving_decode_tok_s": 50.0}))
    new.write_text(json.dumps({"metric": "gpt2s_train_tokens_per_sec_chip",
                               "value": 10.0, "serving_decode_tok_s": 55.0}))
    v = diff_files(str(old), str(new))
    assert not v["enforced"] and v["ok"]
    assert [e["metric"] for e in v["regressions"]] == ["value"]
