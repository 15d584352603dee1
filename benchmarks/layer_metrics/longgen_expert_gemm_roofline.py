"""Kernels (ops/grouped_matmul.py): the least time the chip could take
for the routed experts' projections of the traced steps (k experts a
token in each EXPERT layer, the weights of every expert that took a row
read once, by the program's own count ``moe_experts_touched``, the rows
in and out; by benchmarks/lib/arith_hybrid.py, which leaves the leading
dense layers out) over the device time of the grouped
matrix-multiplication kernel in the traced window (trace group
``moe_expert_gemm``).  The kernel's share of its roofline."""

from benchmarks.lib import arith_hybrid as A
from benchmarks.lib.common import note


def read(rec):
    t = rec.get("trace")
    kernel_s = t and t["groups_s"].get("moe_expert_gemm")
    found = kernel_s and A.least_seconds(rec, lambda m, s: (
        A.expert_gemm_flops(m, s["n_tokens"]),
        A.expert_gemm_bytes(m, s["n_tokens"], s[A.TOUCHED])))
    if not found:
        return None
    steps, least, bounds = found
    note("longgen_expert_gemm_roofline", steps=steps, least_s=least,
         kernel_s=kernel_s, bound_by=bounds)
    return 100.0 * least / kernel_s
