"""Kernels (ops/grouped_matmul.py): device time of the grouped
matrix-multiplication kernel, the experts' three projections (trace
group ``moe_expert_gemm``), over device-busy time, traced window."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["busy_s"]:
        return None
    if "moe_expert_gemm" not in t["groups_s"]:
        return None
    return 100.0 * t["groups_s"]["moe_expert_gemm"] / t["busy_s"]
