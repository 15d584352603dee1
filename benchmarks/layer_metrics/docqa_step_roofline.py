"""Kernels: the least time the chip could take for the steps of the
traced window of a model with latent attention in both sublayers of
every shortcut-connected layer and a share of its experts (for each step
the larger of its required operations over the bf16 peak and its
required bytes over the HBM peak: the attentions', dense MLPs' and
routers' weights, the head, the latent rows read once a sequence and the
products over its query-row pairs, the held experts that took a row;
from shapes and the spans' counts, by benchmarks/lib/arith_mla.py) over
the device-busy time of that window.  The whole step's roofline share,
as ``kda_step_roofline`` is for a model with a recurrent state.

``BENCHMARK.json`` holds at most 128 per-layer metrics and held 127: the
step's parts that would be metrics of their own stand in this reader's
earlier line instead (``docqa_step_parts``: the latent attention's share
of ITS roofline, the expert products' of theirs, the share of the
routers' assignments that fell on experts which compute nothing and on
experts held here, the sequences evicted inside the window)."""

from benchmarks.lib import arith_kda, arith_mla as A
from benchmarks.lib.common import note


def part_roofline(rec, kernel_s, per_step):
    """A part's least time over its device time, in percent, or None."""
    found = kernel_s and A.least_seconds(rec, per_step)
    return found and 100.0 * found[1] / kernel_s


def parts(rec, t) -> dict:
    steps = A.traced_steps(rec)
    made = sum(s["moe_assignments_made"] for s in steps)
    evicted = [float(st["preemptions"]) for _, st in A.stage_spans(rec)[0]
               if "preemptions" in st]
    return {
        "latent_attn_roofline": part_roofline(
            rec, arith_kda.scope_seconds(rec).get("latent_attn"),
            lambda m, s: (A.latent_flops(m, s["latent_pairs"]),
                          A.latent_bytes(m, s["latent_tokens"], 0.0))),
        "expert_gemm_roofline": part_roofline(
            rec, t["groups_s"].get("moe_expert_gemm"),
            lambda m, s: (A.expert_gemm_flops(m, s),
                          A.expert_gemm_bytes(m, s))),
        "zero_assignment_share": made and 100.0 * sum(
            s["moe_zero_assignments"] for s in steps) / made,
        "held_assignment_share": made and 100.0 * sum(
            s["moe_assignments"] for s in steps) / made,
        "preemptions": sum(evicted) if evicted else None}


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["busy_s"]:
        return None
    found = A.least_seconds(rec, lambda m, s: (A.step_flops(m, s),
                                               A.step_bytes(m, s)))
    if not found:
        return None
    steps, least, bounds = found
    # the steps are those staged wholly inside the window, all but the
    # last: the window's busy time holds a little more than their work,
    # which can only lower the share
    note("docqa_step_roofline", steps=steps, least_s=least,
         busy_s=t["busy_s"], bound_by=bounds)
    note("docqa_step_parts", **parts(rec, t))
    return 100.0 * least / t["busy_s"]
