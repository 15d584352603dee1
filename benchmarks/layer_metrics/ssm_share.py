"""Kernels (ops/ssm.py): device time of the mixer's three computations
(the operations under the scopes ``ssm_update``, ``ssm_scan`` and
``ssm_conv``; its two projections are matrix multiplications like the
rest) over device-busy time, traced window, device 0."""

from benchmarks.lib import arith_ssm as A
from benchmarks.lib.common import note


def read(rec):
    sc = A.scope_seconds(rec)
    if not sc or not sc.get("busy_s"):
        return None
    note("ssm_scopes", busy_s=sc["busy_s"],
         seconds={k: v for k, v in sorted(sc.items()) if k != "busy_s"})
    return 100.0 * sum(sc.get(k, 0.0) for k in
                       ("ssm_update", "ssm_scan", "ssm_conv")) / sc["busy_s"]
