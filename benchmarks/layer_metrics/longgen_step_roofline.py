"""Kernels: the least time the chip could take for the steps of the
traced window of a model with a layer pattern (for each step the larger
of its required FLOPs over the bf16 peak and its required bytes over the
HBM peak, from shapes and the program's own ``ds.serve.stage`` counts,
by benchmarks/lib/arith_hybrid.py: dense and expert layers apart, a
window layer's keys from ``kv_tokens_window``, the experts that took a
row from ``moe_experts_touched``) over the device-busy time
of that window.  The share of the whole step's peak."""

from benchmarks.lib import arith_hybrid as A
from benchmarks.lib.common import note


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["busy_s"]:
        return None
    found = A.least_seconds(rec, lambda m, s: (
        A.step_flops(m, s["n_tokens"], s["kv_tokens_full"],
                     s["kv_tokens_window"], s["n_seqs"]),
        A.step_bytes(m, s["n_tokens"], s["kv_tokens_full"],
                     s["kv_tokens_window"], s[A.TOUCHED])))
    if not found:
        return None
    steps, least, bounds = found
    # the busy time of the whole window holds a little more than these
    # steps (the last staged one, the two cut at its edges), which can
    # only lower the share
    note("longgen_step_roofline", steps=steps, least_s=least,
         busy_s=t["busy_s"], bound_by=bounds)
    return 100.0 * least / t["busy_s"]
