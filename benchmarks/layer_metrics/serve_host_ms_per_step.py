"""Serving step: host milliseconds per step over the window, from
``engine.timings``: schedule + stage + readback."""


def read(rec):
    d = rec.get("engine_delta") or {}
    if rec["kind"] != "serve" or not d.get("steps"):
        return None
    return (d["schedule_ms"] + d["stage_ms"] + d["readback_ms"]) / d["steps"]
