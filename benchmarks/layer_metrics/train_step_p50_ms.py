"""Median time per train step: gaps between consecutive steps'
completions (``block_until_ready``), steps pipelined one deep.  Host
clock."""

from benchmarks.lib.common import quantile


def read(rec):
    if rec["kind"] != "train" or not rec["step_gaps_s"]:
        return None
    return 1e3 * quantile(rec["step_gaps_s"], 0.5)
