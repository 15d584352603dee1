"""Serving step: of the (token, expert) assignments the routers made in
the traced steps (``moe_assignments_made`` of the program's
``ds.serve.readback`` spans: top-k a real row a layer), the share that
fell on experts held here and was computed (``moe_assignments``).  Near
the share of the experts held (a quarter) where the router is even."""

from benchmarks.lib import arith_kda as A


def read(rec):
    steps = A.traced_steps(rec)
    made = sum(s["moe_assignments_made"] for s in steps)
    if not made:
        return None
    return 100.0 * sum(s["moe_assignments"] for s in steps) / made
