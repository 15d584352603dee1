"""Kernels (ops/kda.py): device time of the delta-rule mixer's four
computations (the operations under the scopes ``kda_conv``,
``kda_update``, ``kda_chunk`` and ``kda_gate``; its projections are
matrix multiplications like the rest) over device-busy time, traced
window, device 0."""

from benchmarks.lib import arith_kda as A
from benchmarks.lib.common import note


def read(rec):
    sc = A.scope_seconds(rec)
    if not sc or not sc.get("busy_s"):
        return None
    note("kda_scopes", busy_s=sc["busy_s"],
         seconds={k: v for k, v in sorted(sc.items()) if k != "busy_s"})
    return 100.0 * sum(sc.get(k, 0.0) for k in A.KDA_SCOPES) / sc["busy_s"]
