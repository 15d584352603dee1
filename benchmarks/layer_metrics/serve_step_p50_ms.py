"""Serving step: ``engine.timings`` device_ms + wait_ms of each step that
ended in the window (the jitted call and the wait for its tokens; the
engine's ``block_until_ready``), median."""

from benchmarks.lib.common import quantile


def read(rec):
    if rec["kind"] != "serve" or not rec["window_steps"]:
        return None
    return quantile([s["device_ms"] for s in rec["window_steps"]], 0.5)
