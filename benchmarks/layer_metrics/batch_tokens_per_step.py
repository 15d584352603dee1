"""Scheduler: tokens per dispatched step over the window, from
``engine.timings``: (prompt tokens admitted - tokens served from the
prefix cache + tokens generated) / steps."""


def read(rec):
    d = rec.get("engine_delta") or {}
    if rec["kind"] != "serve" or not d.get("steps"):
        return None
    return (d["prompt_tokens"] - d["cached_tokens"]
            + d["generated_tokens"]) / d["steps"]
