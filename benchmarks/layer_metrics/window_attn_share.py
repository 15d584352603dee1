"""Kernels (ops/paged_attention.py, the window layers' calls): device
time of the window kernel (trace group ``window_attention``) over
device-busy time, traced window.  Over the number of window layers it
stands beside ``paged_attn_share``, the full layers' calls."""

GROUP = "window_attention"


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["busy_s"] \
            or GROUP not in t["groups_s"]:
        return None
    return 100.0 * t["groups_s"][GROUP] / t["busy_s"]
