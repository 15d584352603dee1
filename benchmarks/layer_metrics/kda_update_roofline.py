"""Kernels (ops/kda.py ``state_update``): the least time the chip could
take for the one-token delta-rule updates of the traced steps (every
advanced sequence's state and convolution tail read and written in their
stored type, its q, k, v, g, beta in and o out, all KDA layers; by
benchmarks/lib/arith_kda.py, the larger of bytes over the HBM peak and
operations over the bf16 peak) over the device time of the operations
under the scope ``kda_update`` in the traced window.  The update's share
of its byte floor."""

from benchmarks.lib import arith_kda as A


def read(rec):
    return A.scope_roofline(rec, "kda_update", lambda m, s: (
        A.update_flops(m, s["state_rows"]),
        A.update_bytes(m, s["state_rows"])))
