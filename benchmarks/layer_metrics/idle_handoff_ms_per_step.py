"""Wire: device-0 idle time per step between one ``ds.gateway.pump``'s
end and the next one's start that lies under no ``ds.*`` span: the
thread hops between the event loop and the engine's thread, and the
loop's other work (SSE writers, arrivals).  The program's spans in the
device trace."""

from benchmarks.lib import program_spans


def read(rec):
    split = program_spans.of(rec)
    return split and split.idle_ms_per_step("handoff")
