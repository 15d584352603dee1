"""Kernels: device time of the operations under the scopes ``unembed``
and ``sample`` (final norm, logits and the sampler of the jitted step)
over device-busy time, traced window, device 0."""

from benchmarks.lib import program_spans


def read(rec):
    split = program_spans.of(rec)
    return split and split.scope_share("unembed", "sample")
