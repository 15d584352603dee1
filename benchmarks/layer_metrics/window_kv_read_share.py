"""Serving step: the cached tokens a window layer has to read over those
a full layer has to read, summed over the steps staged in the traced
window (``kv_tokens_window`` over ``kv_tokens_full`` of the program's
``ds.serve.stage`` spans): what the traffic lets the window save."""

from benchmarks.lib import arith_hybrid as A


def read(rec):
    steps = A.traced_steps(rec)
    full = sum(s["kv_tokens_full"] for s in steps)
    if not full:
        return None
    return 100.0 * sum(s["kv_tokens_window"] for s in steps) / full
