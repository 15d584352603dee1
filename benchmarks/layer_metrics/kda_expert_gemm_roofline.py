"""Kernels (ops/grouped_matmul.py): the least time the chip could take
for the expert projections of the traced steps of a model that holds a
SHARE of each layer's experts (three products an assignment computed
here, a held expert's weights read once where it took a row, the rows in
and out; from the readback spans' ``moe_assignments`` and
``moe_experts_touched``, by benchmarks/lib/arith_kda.py) over the device
time of the grouped matrix-multiplication kernel in the traced window
(the trace group ``moe_expert_gemm``).  The kernel's share of its
roofline, where ``moe_expert_gemm_roofline`` would count every expert of
every layer."""

from benchmarks.lib import arith_kda as A
from benchmarks.lib.common import note


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t:
        return None
    kernel_s = t["groups_s"].get("moe_expert_gemm")
    found = kernel_s and A.least_seconds(rec, lambda m, s: (
        A.expert_gemm_flops(m, s), A.expert_gemm_bytes(m, s)))
    if not found:
        return None
    steps, least, bounds = found
    note("kda_expert_gemm_roofline", steps=steps, least_s=least,
         kernel_s=kernel_s, bound_by=bounds)
    return 100.0 * least / kernel_s
