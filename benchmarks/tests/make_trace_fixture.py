"""Writes ``data/small.xplane.pb``: a small XSpace in the profiler's own
format, with the planes, lines and names a TPU v5e trace has (device
planes ``/device:TPU:n`` with an ``XLA Ops`` line, operations named by
their HLO text, a ``tf_op`` stat with the JAX source path, host spans
from ``TraceAnnotation``), and round times so that the reduction can be
checked by hand.  Run once:  python benchmarks/tests/make_trace_fixture.py
"""

import os

US = 1_000_000          # picoseconds in a microsecond

OPS0 = [  # (start_us, dur_us, name, tf_op)
    (0, 100, '%fusion.1 = bf16[512,4096]{1,0:T(8,128)(2,1)} fusion(%p0, %p1), '
             'kind=kOutput, calls=%fused_computation.1', 'jit(pstep)/dot_general'),
    (100, 50, '%all-gather.1 = bf16[2048,8192]{1,0} all-gather(%p2), '
              'channel_id=1, replica_groups={{0,1,2,3}}', 'jit(train_step)/all_gather'),
    (140, 20, '%fusion.2 = f32[512,4096]{1,0} fusion(%p3), kind=kLoop, '
              'calls=%fused_computation.2', 'jit(pstep)/mul'),
    (200, 60, '%closed_call.3 = bf16[512,32,128]{2,1,0} custom-call(%q, %kv), '
              'custom_call_target="tpu_custom_call"',
     'jit(pstep)/paged_attention/pallas_call'),
    (300, 20, '%copy.4 = bf16[16,1536,64]{2,1,0} copy(%p4)', 'jit(pstep)/copy'),
]
OPS1 = [(0, 320, '%convolution.9 = bf16[8,8]{1,0} convolution(bf16[8,8] %a, bf16[8,8] %b)',
         'jit(pstep)/dot_general')]
HOST = [(150, 60, 'bench.engine.schedule'), (120, 200, 'bench.engine.step'),
        (0, 10, 'not.ours')]


def plane(pid, name, line_name, events, with_stats):
    meta, lines = [], []
    for i, ev in enumerate(events, 1):
        start, dur, nm = ev[:3]
        stat = (f' stats {{ metadata_id: 1 str_value: "{ev[3]}" }}'
                if with_stats else "")
        lines.append(f"    events {{ metadata_id: {i} offset_ps: {start * US} "
                     f"duration_ps: {dur * US}{stat} }}")
        esc = nm.replace('"', '\\"')
        meta.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{esc}" }} }}')
    stat_meta = ('  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n'
                 if with_stats else "")
    return (f'planes {{ id: {pid} name: "{name}"\n'
            f'  lines {{ id: 1 name: "{line_name}" timestamp_ns: 5000\n'
            + "\n".join(lines) + "\n  }\n" + "\n".join(meta) + "\n"
            + stat_meta + "}\n")


def main():
    from jax.profiler import ProfileData
    text = (plane(1, "/device:TPU:0", "XLA Ops", OPS0, True)
            + plane(2, "/device:TPU:1", "XLA Ops", OPS1, True)
            + plane(3, "/host:CPU", "python", HOST, False))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "small.xplane.pb")
    with open(out, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main()
