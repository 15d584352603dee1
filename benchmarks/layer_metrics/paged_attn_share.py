"""Kernels (ops/paged_attention.py): device time of the Pallas
paged-attention custom calls over device-busy time, traced window."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["busy_s"]:
        return None
    if "paged_attention" not in t["groups_s"]:
        return None
    return 100.0 * t["groups_s"]["paged_attention"] / t["busy_s"]
