"""Serving step: rows of the fullest expert over the mean rows an expert,
worst layer of a step, mean over the steps read back in the traced
window.  The program's own number: ``moe_load`` on its
``ds.serve.readback`` spans (what the gauge
``serving_moe_expert_load_max_over_mean`` shows), from the host planes of
the device trace.  1 is perfectly even routing."""

from benchmarks.lib import trace

SPAN, STAT = "ds.serve.readback", "moe_load"


def read(rec):
    path = trace.find_xplane(rec["trace_dir"]) \
        if rec.get("kind") == "serve" and rec.get("trace_dir") else None
    if not path:
        return None
    from jax.profiler import ProfileData
    loads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SPAN:
                        load = dict(ev.stats).get(STAT)
                        if load is not None:
                            loads.append(float(load))
    return sum(loads) / len(loads) if loads else None
