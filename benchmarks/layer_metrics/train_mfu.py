"""End-to-end utilisation, not a kernel's roofline: FLOPs the forward
and backward passes REQUIRE per token (benchmarks/lib/arith.py, no
recomputation) times tokens per second over chips times the published
bf16 peak.  Tokens per second here are tokens per step over the MEDIAN
step time (``train_step_p50_ms``): this reader runs in the traced run,
whose window holds the seconds the profiler takes to stop."""

from benchmarks.lib import arith
from benchmarks.lib.common import quantile


def read(rec):
    if rec["kind"] != "train" or "peaks" not in rec or not rec["step_gaps_s"]:
        return None
    tok_s = rec["tokens_per_step"] / quantile(rec["step_gaps_s"], 0.5)
    m = {**rec["config"], **rec["config"].get("arith", {})}
    flops = arith.train_flops_per_token(m, rec["seq_len"])
    return 100.0 * tok_s * flops / (rec["chips"] * rec["peaks"]["flops_bf16"])
