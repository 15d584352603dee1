"""Kernels: device time of the operations whose JAX path holds the scope
``kv_write`` (the cache write of ``inference/model.py`` ``ragged_forward``
and whatever copy of the pool XLA makes for it) over device-busy time,
traced window, device 0."""

from benchmarks.lib import program_spans


def read(rec):
    split = program_spans.of(rec)
    return split and split.scope_share("kv_write")
