"""Kernels: the least time the chip could take for the steps of the
traced window of a model with delta-rule layers, a latent layer and a
share of its experts (for each step the larger of its required
operations over the bf16 peak and its required bytes over the HBM peak:
the mixers', dense MLPs', routers' and shared experts' weights, the head,
the states read and written, the latent rows read, the held experts that
took a row; from shapes and the spans' counts, by
benchmarks/lib/arith_kda.py) over the device-busy time of that window.
The whole step's roofline share, as ``serve_step_roofline`` is for a
model of one kind of layer that holds all its experts."""

from benchmarks.lib import arith_kda as A
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
    note("kda_step_roofline", steps=steps, least_s=least,
         busy_s=t["busy_s"], bound_by=bounds)
    return 100.0 * least / t["busy_s"]
