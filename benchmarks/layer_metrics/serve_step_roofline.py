"""Kernels: the least time the chip could take for the steps of the
traced window (for each step the larger of its required FLOPs over the
bf16 peak and its required bytes over the HBM peak, from shapes, by
benchmarks/lib/arith.py) over the device-busy time of that window."""

from benchmarks.lib import arith


def least_seconds(rec, steps):
    m = {**rec["config"], **rec["config"].get("arith", {})}
    total, bounds = 0.0, {"compute": 0, "memory": 0}
    for s in steps:
        f = arith.serve_step_flops(m, s["n_tokens"], s["qk_pairs"], s["n_seqs"])
        b = arith.serve_step_bytes(m, s["n_tokens"], s["ctx_tokens"])
        t, which = arith.roofline_seconds(f, b, rec["peaks"])
        total += t
        bounds[which] += 1
    return total, bounds


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or "peaks" not in rec:
        return None
    t0, t1 = rec["trace_window"]
    steps = [s for s in rec["steps"] if t0 <= s["t0"] and s["t1"] <= t1]
    if not steps or not t["busy_s"]:
        return None
    least, bounds = least_seconds(rec, steps)
    # busy time of the whole traced window holds a little more than these
    # whole steps (the two cut at its edges), which can only lower the share
    from benchmarks.lib.common import note
    note("serve_step_roofline", steps=len(steps), least_s=least,
         busy_s=t["busy_s"], bound_by=bounds)
    return 100.0 * least / t["busy_s"]
