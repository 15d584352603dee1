"""Serving step: sequences whose delta-rule state a step advances by one
token (``state_rows`` of the program's ``ds.serve.stage`` spans), mean
over the steps staged in the traced window."""

from benchmarks.lib import arith_kda as A


def read(rec):
    steps = A.traced_steps(rec)
    if not steps:
        return None
    return sum(s["state_rows"] for s in steps) / len(steps)
