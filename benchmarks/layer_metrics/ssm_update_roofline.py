"""Kernels (ops/ssm.py ``state_update``): the least time the chip could
take for the one-token state updates of the traced steps (every advanced
sequence's state and convolution tail read and written in their stored
type, its x, B, C, dt, z in and y out, all layers; by
benchmarks/lib/arith_ssm.py, the larger of bytes over the HBM peak and
operations over the bf16 peak) over the device time of the operations
under the scope ``ssm_update`` in the traced window.  The update's share
of its byte floor."""

from benchmarks.lib import arith_ssm as A
from benchmarks.lib.common import note


def read(rec):
    kernel_s = A.scope_seconds(rec).get("ssm_update")
    found = kernel_s and A.least_seconds(rec, lambda m, s: (
        A.update_flops(m, s["state_rows"]),
        A.update_bytes(m, s["state_rows"])))
    if not found:
        return None
    steps, least, bounds = found
    note("ssm_update_roofline", steps=steps, least_s=least,
         kernel_s=kernel_s, bound_by=bounds)
    return 100.0 * least / kernel_s
