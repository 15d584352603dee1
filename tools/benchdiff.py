"""BENCH JSON regression sentinel (docs/OBSERVABILITY.md "Device &
compiler telemetry" — the benchdiff workflow).

The bench trajectory (one ``bench.py`` JSON capture per round) had been
guarded by eyeballs: a PR that quietly cost 20% of decode throughput would land
green.  ``benchdiff`` compares two BENCH captures **fingerprint-aware**
(the ``bench_fingerprint()`` PR 8 put in every capture):

* **same ``config_hash``** — the two runs measured the same default
  engine, so the numbers are comparable: every top-level leg metric is
  held to a hard relative threshold and any regression exits nonzero
  (the CI contract).
* **different ``config_hash``** — a PR changed engine defaults, so
  every leg moved for config reasons; the comparison is REPORT-ONLY
  (printed, exit 0) because a hard gate would either mask real
  regressions behind "the hash changed" or block every default-changing
  PR on noise.

Only **top-level numeric leg metrics** with a recognizable direction
are compared — ``*_tok_s`` / ``*_speedup`` / ``goodput_qps_*`` / ``mfu``
up-is-better, ``*_ttft*`` / ``*_ms*`` / ``*_ema`` down-is-better.
Nested diagnostic subtrees (``*_request_metrics``, ``train_metrics``,
SLO curves, chaos variant tallies) are deliberately skipped: they are
post-mortem material, not gateable headline numbers.

CLI::

    python -m tools.benchdiff OLD.json NEW.json [--threshold 0.15]
    python -m tools.benchdiff --smoke       # tier-1 self-check (asserts)
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

# direction markers matched against the (lowercased) metric name;
# first match wins, unmatched names are skipped as directionless
_HIGHER_BETTER = ("tok_s", "speedup", "goodput", "mfu", "hit_rate",
                  "acceptance_rate", "bw_util", "vs_baseline")
_LOWER_BETTER = ("ttft", "tpot", "_ms", "ms_per", "ema", "latency")


def metric_direction(name: str) -> Optional[int]:
    """+1 up-is-better, -1 down-is-better, None not gateable.  The
    headline ``value`` key (the gpt2s tokens/s number) is up-is-better
    by definition of the bench."""
    low = name.lower()
    if low == "value" or any(m in low for m in _HIGHER_BETTER):
        return 1
    if any(m in low for m in _LOWER_BETTER):
        return -1
    return None


def _leg_metrics(bench: Dict[str, Any]) -> Dict[str, float]:
    """Top-level numeric leg metrics with a direction (bools are not
    metrics; nested dicts are diagnostics and skipped)."""
    out = {}
    for k, v in bench.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        if metric_direction(k) is not None:
            out[k] = float(v)
    return out


def compare(old: Dict[str, Any], new: Dict[str, Any],
            threshold: float = 0.15) -> Dict[str, Any]:
    """Compare two BENCH captures; returns the verdict dict::

        {"fingerprint_match": bool, "enforced": bool,
         "regressions": [...], "improvements": [...], "unchanged": n,
         "only_old": [...], "only_new": [...], "ok": bool}

    ``ok`` is False only for an ENFORCED (matching-fingerprint) run
    with regressions; a mismatched fingerprint reports but never
    fails.  A leg metric present in ``old`` but absent from ``new``
    counts as a regression too — a silently dropped bench leg must not
    read as green (error keys like ``<leg>_error`` mark the drop)."""
    old_fp = (old.get("config_hash"), old.get("engine_version"))
    new_fp = (new.get("config_hash"), new.get("engine_version"))
    match = old_fp[0] is not None and old_fp[0] == new_fp[0]
    om, nm = _leg_metrics(old), _leg_metrics(new)
    regressions: List[Dict[str, Any]] = []
    improvements: List[Dict[str, Any]] = []
    unchanged = 0
    for k in sorted(set(om) & set(nm)):
        d = metric_direction(k)
        o, n = om[k], nm[k]
        if o == 0:
            unchanged += 1
            continue
        rel = (n - o) / abs(o)
        entry = {"metric": k, "old": o, "new": n,
                 "rel_change": round(rel, 4)}
        if d * rel < -threshold:
            regressions.append(entry)
        elif d * rel > threshold:
            improvements.append(entry)
        else:
            unchanged += 1
    only_old = sorted(set(om) - set(nm))
    only_new = sorted(set(nm) - set(om))
    for k in only_old:
        regressions.append({"metric": k, "old": om[k], "new": None,
                            "rel_change": None,
                            "note": "leg metric disappeared"})
    # anomaly-count deltas (``<leg>_anomalies`` subtrees, PR 10):
    # REPORTED, never gated — detector fires are workload/rig-noise
    # sensitive, but a leg that suddenly fires 40 latency anomalies is
    # exactly what a reviewer should look at next to a green diff
    anomaly_deltas: List[Dict[str, Any]] = []
    # fleet anomaly subtrees ({"fleet": {...}, "replicas": {name:
    # {...}}}, PR 14) report fleet-total AND per-replica deltas —
    # REPORTED like the flat anomaly deltas, never gated (detector
    # fires are workload/rig-noise sensitive; a replica suddenly
    # firing 40 latency anomalies is reviewer material, not a gate)
    fleet_anomaly_deltas: List[Dict[str, Any]] = []
    for k in sorted(set(old) | set(new)):
        if not k.endswith("_anomalies"):
            continue
        ov, nv = old.get(k), new.get(k)
        if any(isinstance(v, dict) and "fleet" in v for v in (ov, nv)):
            of = (ov or {}).get("fleet") if isinstance(ov, dict) else None
            nf = (nv or {}).get("fleet") if isinstance(nv, dict) else None
            o = of.get("total") if isinstance(of, dict) else None
            n = nf.get("total") if isinstance(nf, dict) else None
            if (o or 0) != (n or 0):
                fleet_anomaly_deltas.append(
                    {"metric": f"{k}.fleet", "old": o, "new": n})
            oreps = (ov or {}).get("replicas") \
                if isinstance(ov, dict) else None
            nreps = (nv or {}).get("replicas") \
                if isinstance(nv, dict) else None
            oreps = oreps if isinstance(oreps, dict) else {}
            nreps = nreps if isinstance(nreps, dict) else {}
            for rep in sorted(set(oreps) | set(nreps)):
                ro = (oreps.get(rep) or {}).get("total")
                rn = (nreps.get(rep) or {}).get("total")
                if (ro or 0) != (rn or 0):
                    fleet_anomaly_deltas.append(
                        {"metric": f"{k}.replicas.{rep}",
                         "old": ro, "new": rn})
            continue
        o = ov.get("total") if isinstance(ov, dict) else None
        n = nv.get("total") if isinstance(nv, dict) else None
        if o is None and n is None:
            continue
        if (o or 0) != (n or 0):
            anomaly_deltas.append({"metric": k, "old": o, "new": n})
    # SLO scorecard deltas (``<leg>_slo`` subtrees, the scorecard
    # bench legs embed): per-class composite attainment and remaining
    # error budget — REPORTED, never gated, exactly like the anomaly
    # deltas (attainment moves with rig noise; a class suddenly
    # burning its budget is reviewer material next to a green diff)
    slo_deltas: List[Dict[str, Any]] = []
    for k in sorted(set(old) | set(new)):
        if not k.endswith("_slo"):
            continue
        ov, nv = old.get(k), new.get(k)
        ocl = ov.get("classes") if isinstance(ov, dict) else None
        ncl = nv.get("classes") if isinstance(nv, dict) else None
        ocl = ocl if isinstance(ocl, dict) else {}
        ncl = ncl if isinstance(ncl, dict) else {}
        for cls in sorted(set(ocl) | set(ncl)):
            for path, leaf in ((("objectives", "requests", "attainment"),
                                "attainment"),
                               (("error_budget", "remaining"),
                                "budget_remaining")):
                def _dig(tree):
                    node = tree.get(cls)
                    for part in path:
                        if not isinstance(node, dict):
                            return None
                        node = node.get(part)
                    return node
                o, n = _dig(ocl), _dig(ncl)
                if o != n:
                    slo_deltas.append(
                        {"metric": f"{k}.{cls}.{leaf}",
                         "old": o, "new": n})
    return {
        "fingerprint_match": match,
        "old_fingerprint": {"config_hash": old_fp[0],
                            "engine_version": old_fp[1]},
        "new_fingerprint": {"config_hash": new_fp[0],
                            "engine_version": new_fp[1]},
        "enforced": match,
        "threshold": threshold,
        "regressions": regressions,
        "improvements": improvements,
        "unchanged": unchanged,
        "only_old": only_old,
        "only_new": only_new,
        "anomaly_deltas": anomaly_deltas,
        "fleet_anomaly_deltas": fleet_anomaly_deltas,
        "slo_deltas": slo_deltas,
        "ok": match is False or not regressions,
    }


def diff_files(old_path: str, new_path: str,
               threshold: float = 0.15) -> Dict[str, Any]:
    with open(old_path) as f:
        old = json.load(f)
    with open(new_path) as f:
        new = json.load(f)
    return compare(old, new, threshold)


def _render(v: Dict[str, Any]) -> str:
    lines = []
    mode = "ENFORCED (same config_hash)" if v["enforced"] else \
        "REPORT-ONLY (config_hash changed — defaults moved, legs " \
        "are not comparable as regressions)"
    lines.append(f"benchdiff: {mode}, threshold ±{v['threshold']:.0%}")
    for e in v["regressions"]:
        if e.get("new") is None:
            lines.append(f"  REGRESSION {e['metric']}: "
                         f"{e['old']} -> MISSING")
        else:
            lines.append(f"  REGRESSION {e['metric']}: {e['old']} -> "
                         f"{e['new']} ({e['rel_change']:+.1%})")
    for e in v["improvements"]:
        lines.append(f"  improved   {e['metric']}: {e['old']} -> "
                     f"{e['new']} ({e['rel_change']:+.1%})")
    for e in v.get("anomaly_deltas", []):
        lines.append(f"  anomalies  {e['metric']}: {e['old']} -> "
                     f"{e['new']} (report-only, never gates)")
    for e in v.get("fleet_anomaly_deltas", []):
        lines.append(f"  fleet-anom {e['metric']}: {e['old']} -> "
                     f"{e['new']} (report-only, never gates)")
    for e in v.get("slo_deltas", []):
        lines.append(f"  slo        {e['metric']}: {e['old']} -> "
                     f"{e['new']} (report-only, never gates)")
    lines.append(f"  unchanged: {v['unchanged']}, "
                 f"new-only legs: {len(v['only_new'])}")
    lines.append("benchdiff: " + ("OK" if v["ok"] else "REGRESSED"))
    return "\n".join(lines)


# --------------------------------------------------------------------------
# smoke: the tier-1 self-check (synthetic captures, asserts)
# --------------------------------------------------------------------------

def smoke() -> Dict[str, Any]:
    """Deterministic self-check on synthetic BENCH captures: one
    regressed leg under a MATCHING fingerprint must fail; the same
    regression under a MISMATCHED fingerprint must report-only; an
    improvement must never flag; a disappeared leg must fail."""
    base = {"engine_version": "1.0", "config_hash": "aaaa",
            "value": 1000.0,                       # headline tok/s
            "pipe2_decode_tok_s": 500.0,
            "serving_ttft_p50_ms": 100.0,
            "spec_decode_speedup": 1.8,
            "goodput_qps_sla2": 2.0,
            "platform": "cpu", "steps": 40,        # directionless: skipped
            "serving_request_metrics": {"ttft_ms": {"p50": 1.0}}}

    regressed = dict(base, pipe2_decode_tok_s=350.0)       # -30% tok/s
    v = compare(base, regressed)
    assert v["enforced"] and not v["ok"], v
    assert [e["metric"] for e in v["regressions"]] \
        == ["pipe2_decode_tok_s"], v["regressions"]

    lat_regressed = dict(base, serving_ttft_p50_ms=140.0)  # +40% latency
    v = compare(base, lat_regressed)
    assert not v["ok"] and v["regressions"][0]["metric"] \
        == "serving_ttft_p50_ms", v

    mismatched = dict(regressed, config_hash="bbbb")
    v_mm = compare(base, mismatched)
    assert not v_mm["enforced"] and v_mm["ok"], v_mm       # report-only
    assert v_mm["regressions"], "mismatch must still REPORT the delta"

    improved = dict(base, pipe2_decode_tok_s=800.0,
                    serving_ttft_p50_ms=50.0)
    v_up = compare(base, improved)
    assert v_up["ok"] and len(v_up["improvements"]) == 2, v_up

    dropped = {k: v2 for k, v2 in base.items()
               if k != "spec_decode_speedup"}
    v_drop = compare(base, dropped)
    assert not v_drop["ok"] and any(
        e.get("note") == "leg metric disappeared"
        for e in v_drop["regressions"]), v_drop

    within = dict(base, pipe2_decode_tok_s=460.0)          # -8% < 15%
    assert compare(base, within)["ok"]

    # anomaly-count deltas REPORT and never gate (PR 10): a 40x fire
    # jump under a matching fingerprint stays ok=True but is listed
    noisy_base = dict(base, pipe2_anomalies={"total": 1,
                                             "by_signal": {"x": 1}})
    noisy_new = dict(base, pipe2_anomalies={"total": 40,
                                            "by_signal": {"x": 40}})
    v_an = compare(noisy_base, noisy_new)
    assert v_an["ok"], v_an
    assert v_an["anomaly_deltas"] == [
        {"metric": "pipe2_anomalies", "old": 1, "new": 40}], v_an
    assert compare(noisy_base, noisy_base)["anomaly_deltas"] == []

    # fleet anomaly subtrees (PR 14): fleet-total and per-replica
    # deltas REPORT under fleet_anomaly_deltas and CANNOT fail a run
    # even under a matching fingerprint
    fl_base = dict(base, fleet_serving_anomalies={
        "fleet": {"total": 0, "by_signal": {}},
        "replicas": {"r0": {"total": 0}, "r1": {"total": 1}}})
    fl_new = dict(base, fleet_serving_anomalies={
        "fleet": {"total": 7, "by_signal": {"storm": 7}},
        "replicas": {"r0": {"total": 40}, "r1": {"total": 1}}})
    v_fl = compare(fl_base, fl_new)
    assert v_fl["ok"], v_fl                    # reports, never gates
    assert v_fl["fleet_anomaly_deltas"] == [
        {"metric": "fleet_serving_anomalies.fleet", "old": 0, "new": 7},
        {"metric": "fleet_serving_anomalies.replicas.r0",
         "old": 0, "new": 40}], v_fl
    assert v_fl["anomaly_deltas"] == [], v_fl  # not double-reported
    assert compare(fl_base, fl_base)["fleet_anomaly_deltas"] == []

    # SLO scorecard deltas (``<leg>_slo``): per-class composite
    # attainment and budget drops REPORT under slo_deltas and CANNOT
    # fail a run even under a matching fingerprint
    def _card(att, remaining):
        return {"enabled": True, "classes": {"interactive": {
            "objectives": {"requests": {"attainment": att,
                                        "target": 0.95}},
            "error_budget": {"remaining": remaining}}}}
    slo_base = dict(base, serving_slo=_card(1.0, 25))
    slo_new = dict(base, serving_slo=_card(0.5, 0))
    v_slo = compare(slo_base, slo_new)
    assert v_slo["ok"], v_slo                  # reports, never gates
    assert v_slo["slo_deltas"] == [
        {"metric": "serving_slo.interactive.attainment",
         "old": 1.0, "new": 0.5},
        {"metric": "serving_slo.interactive.budget_remaining",
         "old": 25, "new": 0}], v_slo
    assert compare(slo_base, slo_base)["slo_deltas"] == []

    return {"ok": True,
            "checks": ["enforced_regression_fails",
                       "latency_regression_fails",
                       "fingerprint_mismatch_report_only",
                       "improvement_passes",
                       "dropped_leg_fails",
                       "within_threshold_passes",
                       "anomaly_delta_reports_not_gates",
                       "fleet_anomaly_delta_reports_not_gates",
                       "slo_delta_reports_not_gates"]}


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old", nargs="?", help="baseline BENCH JSON")
    ap.add_argument("new", nargs="?", help="candidate BENCH JSON")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="relative regression threshold per leg "
                    "(default 0.15)")
    ap.add_argument("--json", action="store_true",
                    help="emit the verdict dict as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="run the deterministic self-check (asserts; "
                    "the tier-1 leg)")
    args = ap.parse_args(argv)

    if args.smoke:
        out = smoke()
        print(json.dumps(out))  # tpulint: disable=print — CLI output
        return 0
    if not args.old or not args.new:
        ap.error("OLD and NEW BENCH JSONs required (or --smoke)")
    verdict = diff_files(args.old, args.new, args.threshold)
    if args.json:
        print(json.dumps(verdict))  # tpulint: disable=print — CLI output
    else:
        print(_render(verdict))  # tpulint: disable=print — CLI output
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
