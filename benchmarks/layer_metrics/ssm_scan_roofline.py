"""Kernels (ops/ssm.py ``chunk_scan``): the least time the chip could
take for the chunked form over the longer runs of the traced steps (the
larger of its least products over the bf16 peak and its rows in and out
and its runs' first and last states over the HBM peak, all layers; by
benchmarks/lib/arith_ssm.py) over the device time of the operations
under the scope ``ssm_scan`` in the traced window."""

from benchmarks.lib import arith_ssm as A
from benchmarks.lib.common import note


def read(rec):
    kernel_s = A.scope_seconds(rec).get("ssm_scan")
    found = kernel_s and A.least_seconds(rec, lambda m, s: (
        A.scan_flops(m, s["scan_tokens"]),
        A.scan_bytes(m, s["scan_tokens"], A.scan_runs(s),
                     min(s["state_starts"], A.scan_runs(s)))))
    if not found:
        return None
    steps, least, bounds = found
    note("ssm_scan_roofline", steps=steps, least_s=least,
         kernel_s=kernel_s, bound_by=bounds)
    return 100.0 * least / kernel_s
