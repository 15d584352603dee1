"""Kernels (parallel/moe.py ``moe_serve``): device time of everything
the expert layer does around its projections (router product, softmax,
top-k, sort, gathers, activation, combine; trace group ``moe_route``)
over device-busy time, traced window."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["busy_s"]:
        return None
    if "moe_route" not in t["groups_s"]:
        return None
    return 100.0 * t["groups_s"]["moe_route"] / t["busy_s"]
