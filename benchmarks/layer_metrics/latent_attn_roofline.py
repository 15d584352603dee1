"""Kernels (ops/mla.py ``latent_attend``): the least time the chip could
take for the latent layer's attention of the traced steps (the cached
rows it reads, ``latent_tokens`` of 1,152 bytes, and the folded form's
products: every head's query over a row of 576 for the score and the
weighted rows of 512 for the value; by benchmarks/lib/arith_kda.py) over
the device time of the operations under the scope ``latent_attn`` in the
traced window.  The attention's share of its byte floor."""

from benchmarks.lib import arith_kda as A


def read(rec):
    return A.scope_roofline(rec, "latent_attn", lambda m, s: (
        A.latent_flops(m, s["latent_tokens"]),
        A.latent_bytes(m, s["latent_tokens"], 0.0)))
