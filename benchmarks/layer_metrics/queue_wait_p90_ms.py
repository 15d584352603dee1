"""Scheduler (inference/engine.py ``_schedule``): time a request waited
between ``put`` and its first scheduled step, from ``request_metrics()``;
90th percentile over the requests due in the window."""

from benchmarks.lib.common import quantile


def read(rec):
    if rec["kind"] != "serve":
        return None
    vals = [e["queue_wait_ms"] for r in rec["requests_in_window"]
            for e in [rec["engine_requests"].get(r["uid"])]
            if e and e.get("queue_wait_ms") is not None]
    return quantile(vals, 0.9) if vals else None
