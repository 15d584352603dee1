"""Wire: device-0 idle time per step under ``ds.gateway.apply``, the
feeding of each stream's token back to the engine (one ``put`` each) on
the engine's thread.  The program's spans in the device trace."""

from benchmarks.lib import program_spans


def read(rec):
    split = program_spans.of(rec)
    return split and split.idle_ms_per_step(program_spans.APPLY)
