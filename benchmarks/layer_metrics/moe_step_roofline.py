"""Kernels: the least time the chip could take for the steps of the
traced window of a sparse-expert model (for each step the larger of its
required FLOPs over the bf16 peak and its required bytes over the HBM
peak, from shapes, by benchmarks/lib/arith_moe.py: k experts a token,
every touched expert's weights read once) over the device-busy time of
that window.  The whole step's roofline share, as ``serve_step_roofline``
is for a dense model."""

from benchmarks.lib import arith_moe
from benchmarks.lib.common import note


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["busy_s"] \
            or "peaks" not in rec:
        return None
    found = arith_moe.traced_least_seconds(rec, lambda m, s: (
        arith_moe.moe_step_flops(m, s["n_tokens"], s["qk_pairs"],
                                 s["n_seqs"]),
        arith_moe.moe_step_bytes(m, s["n_tokens"], s["ctx_tokens"])))
    if not found:
        return None
    steps, least, bounds = found
    # busy time of the whole traced window holds a little more than these
    # whole steps (the two cut at its edges), which can only lower the share
    note("moe_step_roofline", steps=steps, least_s=least,
         busy_s=t["busy_s"], bound_by=bounds)
    return 100.0 * least / t["busy_s"]
