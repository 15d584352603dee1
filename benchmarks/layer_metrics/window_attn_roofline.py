"""Kernels (ops/paged_attention.py, the window layers' calls): the least
time the chip could take for the window layers' attention of the traced
steps (the keys and values inside the queries' windows, from the
program's ``kv_tokens_window``, the queries in and the outputs out; by
benchmarks/lib/arith_hybrid.py) over the device time of the window
kernel in the traced window (trace group ``window_attention``: the
Pallas calls under the scope ``attn_window``).  The kernel's share of
its roofline."""

from benchmarks.lib import arith_hybrid as A
from benchmarks.lib.common import note

GROUP = "window_attention"


def read(rec):
    t = rec.get("trace")
    kernel_s = t and t["groups_s"].get(GROUP)
    found = kernel_s and A.least_seconds(rec, lambda m, s: (
        A.window_attn_flops(m, s["kv_tokens_window"]),
        A.window_attn_bytes(m, s["n_tokens"], s["kv_tokens_window"])))
    if not found:
        return None
    steps, least, bounds = found
    note("window_attn_roofline", steps=steps, least_s=least,
         kernel_s=kernel_s, bound_by=bounds)
    return 100.0 * least / kernel_s
