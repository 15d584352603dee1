"""Kernels (ops/mla.py): device time of the latent layer's cache write
and attention (the operations under the scopes ``latent_write`` and
``latent_attn``) over device-busy time, traced window, device 0."""

from benchmarks.lib import arith_kda as A


def read(rec):
    sc = A.scope_seconds(rec)
    if not sc or not sc.get("busy_s"):
        return None
    return 100.0 * (sc.get("latent_attn", 0.0)
                    + sc.get("latent_write", 0.0)) / sc["busy_s"]
