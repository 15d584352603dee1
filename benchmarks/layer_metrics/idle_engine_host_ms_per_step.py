"""Serving step: device-0 idle time per step that lies under the engine's
own host phases (``ds.serve.schedule`` + ``stage`` + ``dispatch`` +
``readback``), booked by overlap.  The program's spans in the device
trace (benchmarks/lib/program_spans.py)."""

from benchmarks.lib import program_spans


def read(rec):
    split = program_spans.of(rec)
    return split and split.idle_ms_per_step(*program_spans.ENGINE_HOST)
