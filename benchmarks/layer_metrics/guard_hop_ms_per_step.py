"""Serving step: what the watchdog's two thread hops cost a step, the sum
of ``hop_us`` on its ``ds.serve.dispatch`` and ``ds.serve.wait`` spans
(``inference/failures.py`` ``Watchdog.run``), in milliseconds.  The
program's spans in the device trace."""

from benchmarks.lib import program_spans


def read(rec):
    split = program_spans.of(rec)
    if not split or not split.steps:
        return None
    return split.hop_us / 1e3 / split.steps
