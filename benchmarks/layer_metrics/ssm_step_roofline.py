"""Kernels: the least time the chip could take for the steps of the
traced window of a model with a Mamba-2 mixer beside its attention (for
each step the larger of its required operations over the bf16 peak and
its required bytes over the HBM peak: weights, head, cached keys and
values, and the recurrent states read and written; from shapes and the
stage span's counts, by benchmarks/lib/arith_ssm.py) over the
device-busy time of that window.  The whole step's roofline share, as
``serve_step_roofline`` is for a model without a mixer."""

from benchmarks.lib import arith_ssm as A
from benchmarks.lib.common import note


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
    note("ssm_step_roofline", steps=steps, least_s=least,
         busy_s=t["busy_s"], bound_by=bounds)
    return 100.0 * least / t["busy_s"]
