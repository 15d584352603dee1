"""Serving steps the engine retried inside the window
(``serving_step_retries_total`` after minus before): a guarded device
call that outlived the watchdog's deadline, or a transient failure.
Reads 0 unless the window held a stall; every request is still answered,
so the run stays correct and the lost seconds show in its metrics."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    return rec["engine_delta"].get("step_retries")
