"""Writes ``data/spans.xplane.pb``: a small XSpace in the profiler's own
format with what ``benchmarks/lib/program_spans.py`` reads: device 0's
``XLA Ops`` line, and a host plane with two thread lines carrying the
program's ``ds.*`` spans and their stats (the engine's thread, and the
event loop with its ``ds.gateway.route``).  Times are round microseconds
so the booking can be checked by hand (``tests/test_program_spans.py``
has the arithmetic).  Run once:  python benchmarks/tests/make_span_fixture.py
"""

import os

US = 1_000_000          # picoseconds in a microsecond

# device 0 is idle in [40,60] [100,110] [200,300] [400,450] [500,600]
# [700,900]: 480 us of [0,1000]
OPS = [(0, 40), (60, 40), (110, 90), (300, 100), (450, 50), (600, 100),
       (900, 100)]
FUSION = ("%fusion.{i} = bf16[512,4096]{{1,0}} fusion(%p0), kind=kLoop, "
          "calls=%fused_computation.{i}")

# (start_us, dur_us, name, {stat: value})
ENGINE = [
    (90, 230, "ds.gateway.pump", {"queued_us": 7.5, "n_out": 2}),
    (205, 20, "ds.serve.schedule", {"sid": 1}),
    (210, 5, "ds.serve.prefix_match", {"uid": 9}),
    (225, 20, "ds.serve.stage", {"sid": 1, "n_tokens": 5}),
    (245, 30, "ds.serve.dispatch", {"sid": 1, "hop_us": 20.0}),
    (275, 35, "ds.serve.wait", {"sid": 1, "hop_us": 10.0}),
    (310, 8, "ds.serve.readback", {"sid": 1}),
    (420, 20, "ds.gateway.apply", {"queued_us": 3.0, "n_put": 2,
                                   "n_flush": 0}),
    (520, 480, "ds.gateway.pump", {"queued_us": 5.0, "n_out": 2}),
    (520, 20, "ds.serve.schedule", {"sid": 2}),
    (540, 20, "ds.serve.stage", {"sid": 2, "n_tokens": 5}),
    (560, 30, "ds.serve.dispatch", {"sid": 2, "hop_us": 30.0}),
    (590, 390, "ds.serve.wait", {"sid": 2, "hop_us": 0.0}),
    (980, 10, "ds.serve.readback", {"sid": 2}),
    (0, 10, "not.ours", {}),
]
# the second route lies wholly under the engine thread's dispatch and
# wait: the engine's thread is asked first
LOOP = [
    (330, 80, "ds.gateway.route", {"wake_us": 10.0, "n_tokens": 2,
                                   "n_closed": 0}),
    (585, 10, "ds.gateway.route", {"wake_us": 0.0, "n_tokens": 0,
                                   "n_closed": 0}),
]


def line(lid, name, events, names, stats):
    rows = []
    for start, dur, nm, st in events:
        mid = names.setdefault(nm, len(names) + 1)
        body = ""
        for key, val in st.items():
            sid = stats.setdefault(key, len(stats) + 1)
            kind = "double_value" if isinstance(val, float) else "int64_value"
            body += f" stats {{ metadata_id: {sid} {kind}: {val} }}"
        rows.append(f"    events {{ metadata_id: {mid} offset_ps: {start * US} "
                    f"duration_ps: {dur * US}{body} }}")
    return (f'  lines {{ id: {lid} name: "{name}" timestamp_ns: 5000\n'
            + "\n".join(rows) + "\n  }\n")


def plane(pid, name, lines):
    names, stats = {}, {}
    body = "".join(line(i, ln, evs, names, stats)
                   for i, (ln, evs) in enumerate(lines, 1))
    for nm, mid in names.items():
        esc = nm.replace('"', '\\"')
        body += (f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                 f'name: "{esc}" }} }}\n')
    for key, sid in stats.items():
        body += (f'  stat_metadata {{ key: {sid} value {{ id: {sid} '
                 f'name: "{key}" }} }}\n')
    return f'planes {{ id: {pid} name: "{name}"\n{body}}}\n'


def main():
    from jax.profiler import ProfileData
    ops = [(s, d, FUSION.format(i=i), {}) for i, (s, d) in enumerate(OPS, 1)]
    text = (plane(1, "/device:TPU:0", [("XLA Ops", ops)])
            + plane(2, "/host:CPU", [("gateway-engine_0", ENGINE),
                                     ("gateway-loop", LOOP)]))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "spans.xplane.pb")
    with open(out, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main()
