"""Kernels (ops/grouped_matmul.py): the least time the chip could take
for the expert projections of the traced steps (k experts a token, every
touched expert's weights read once a layer, the rows in and out; by
benchmarks/lib/arith_moe.py) over the device time of the grouped
matrix-multiplication kernel in the traced window (the trace group
``moe_expert_gemm`` of the configuration's ``trace_groups``).  The
kernel's share of its roofline."""

from benchmarks.lib import arith_moe
from benchmarks.lib.common import note


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or "peaks" not in rec:
        return None
    kernel_s = t["groups_s"].get("moe_expert_gemm")
    found = kernel_s and arith_moe.traced_least_seconds(rec, lambda m, s: (
        arith_moe.expert_gemm_flops(m, s["n_tokens"]),
        arith_moe.expert_gemm_bytes(m, s["n_tokens"])))
    if not found:
        return None
    steps, least, bounds = found
    note("moe_expert_gemm_roofline", steps=steps, least_s=least,
         kernel_s=kernel_s, bound_by=bounds)
    return 100.0 * least / kernel_s
