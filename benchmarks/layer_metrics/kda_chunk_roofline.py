"""Kernels (ops/kda.py ``chunk_rule``): the least time the chip could
take for the chunked delta rule over the longer runs of the traced steps
(the chunked form's least products a token; every token's inputs and
output, a run's last state written and its first read unless it starts
at position 0; all KDA layers; by benchmarks/lib/arith_kda.py) over the
device time of the operations under the scope ``kda_chunk`` in the
traced window.  The chunked form's share of its roofline: low, because
the scope's reads and writes of state rows around the chunks stand in
every step and its products only in the steps that hold a prompt."""

from benchmarks.lib import arith_kda as A


def read(rec):
    return A.scope_roofline(rec, "kda_chunk", lambda m, s: (
        A.chunk_flops(m, s["scan_tokens"]),
        A.chunk_bytes(m, s["scan_tokens"], A.scan_runs(s),
                      min(s["state_starts"], A.scan_runs(s)))))
