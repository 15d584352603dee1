"""Wire: device-0 idle time per step under ``ds.gateway.route``, the
event loop's delivery of a step's tokens (and release of stalled
streams).  The program's spans in the device trace."""

from benchmarks.lib import program_spans


def read(rec):
    split = program_spans.of(rec)
    return split and split.idle_ms_per_step(program_spans.ROUTE)
