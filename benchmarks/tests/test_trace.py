"""The trace reduction on a small recorded XSpace, checked by hand
(``make_trace_fixture.py`` wrote it; times there are in microseconds)."""

import os

import pytest

from benchmarks.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6


@pytest.fixture(scope="module")
def red():
    return trace.reduce(trace.read(os.path.join(HERE, "data", "small.xplane.pb")))


def test_busy_union_and_idle_share(red):
    # device 0: [0,160] + [200,260] + [300,320] = 240; device 1: 320
    assert red["devices"] == 2 and red["op_events"] == 6
    assert red["busy_s"] == pytest.approx(280 * US)
    assert red["window_s"] == pytest.approx(320 * US)
    assert red["idle_share"] == pytest.approx(1 - 280 / 320)


def test_a_given_window_takes_the_place_of_first_to_last_op():
    """The window is two stamps on the trace's clock, never a length from
    another clock: what lies outside them counts nowhere."""
    tr = trace.read(os.path.join(HERE, "data", "small.xplane.pb"))
    # the file's times lie 5 us after the fixture's round ones: [5,325]
    r = trace.reduce(tr, window=(-35 * US, 365 * US))
    assert r["window_s"] == pytest.approx(400 * US)
    assert r["idle_share"] == pytest.approx(1 - 280 / 400)
    # [110,290]: device 0 keeps [110,165] + [205,265], device 1 all of it
    r = trace.reduce(tr, window=(110 * US, 290 * US))
    assert r["busy_s"] == pytest.approx((115 + 180) / 2 * US)
    assert r["window_s"] == pytest.approx(180 * US)
    assert r["op_events"] == 4
    # of the all-gather [105,155] 45 us are left, 10 of them under fusion.2
    assert r["collective_s"] == pytest.approx(45 * US)
    assert r["exposed_collective_s"] == pytest.approx(35 * US)
    g = r["groups_s"]
    assert g["matmul_fusions"] == pytest.approx(180 / 2 * US)
    assert g["other_fusions"] == pytest.approx(20 / 2 * US)
    assert "copy" not in g
    # idle 165..205 and 265..290: the second is cut at the window's end
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.engine.schedule"] == pytest.approx(40 * US)
    assert gaps["bench.engine.step"] == pytest.approx(25 * US)


FUSION = "%fusion.{} = f32[8]{{0}} fusion(%a), kind=kOutput, calls=%f"
COPY = "%copy.{} = f32[8]{{0}} copy(%a)"


def synthetic(ops, span=(10.0, 20.0), host=()):
    spans = [(span[0], span[1], trace.WINDOW_SPAN)] if span else []
    return {"devices": {0: ops}, "host": spans + list(host)}


def test_operations_outside_the_window_span_count_nowhere():
    r = trace.reduce(synthetic([(2.0, 9.0, FUSION.format(1)),
                                (12.0, 15.0, COPY.format(2)),
                                (20.0, 31.0, FUSION.format(3))]))
    assert r["cut_by"] == "span" and r["window"] == (10.0, 20.0)
    assert r["busy_s"] == 3.0 and r["window_s"] == 10.0
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["groups_s"] == {"copy": 3.0} and r["op_events"] == 1
    assert r["device_ops"] == [["copy", 3.0]]
    # the file's own extent is kept for the self-check's message only
    assert (r["first_op_s"], r["last_op_s"]) == (2.0, 31.0)
    # idle from the window's edges to the first and from the last operation
    assert dict(r["idle_gaps"]) == {"bench.unattributed": 7.0}


def test_an_operation_across_an_edge_is_cut_there():
    coll = "%all-gather.4 = f32[8]{0} all-gather(f32[2] %x)"
    r = trace.reduce(synthetic(
        [(8.0, 12.0, FUSION.format(1)), (14.0, 16.0, COPY.format(2)),
         (18.0, 25.0, coll), (19.0, 26.0, FUSION.format(3))],
        host=[(0.0, 13.5, "bench.engine.step"),
              (15.0, 30.0, "bench.gateway.between_steps")]))
    assert r["busy_s"] == 2.0 + 2.0 + 2.0 and r["window_s"] == 10.0
    assert r["groups_s"] == {"matmul_fusions": 2.0 + 1.0, "copy": 2.0,
                             "collectives": 2.0}
    assert r["collective_s"] == 2.0 and r["exposed_collective_s"] == 1.0
    gaps = dict(r["idle_gaps"])
    assert gaps == {"bench.engine.step": 2.0,
                    "bench.gateway.between_steps": 2.0}
    assert trace.WINDOW_SPAN not in gaps


@pytest.mark.parametrize("ops", [
    [(3.0, 27.0, FUSION.format(1))],
    # back to back, as a loop that launches one step ahead leaves them
    [(9.99, 12.5, FUSION.format(1)), (12.5, 17.0, COPY.format(2)),
     (17.0, 20.0001, FUSION.format(3))],
    [(0.0, 40.0, "%while.1 = (s32[]) while(%t), body=%b"),
     (1.0, 39.0, FUSION.format(1))],
])
def test_a_device_busy_past_both_edges_reads_the_window_exactly(ops):
    r = trace.reduce(synthetic(ops))
    assert r["busy_s"] == r["window_s"] == 10.0
    assert r["idle_share"] == 0.0
    assert r["idle_gaps"] == []


def test_a_trace_without_the_span_reads_first_operation_to_last():
    r = trace.reduce(synthetic([(2.0, 9.0, FUSION.format(1)),
                                (12.0, 15.0, COPY.format(2))], span=None))
    assert r["cut_by"] == "ops" and r["window"] == (2.0, 15.0)
    assert r["busy_s"] == 10.0 and r["window_s"] == 13.0
    assert trace.window_of({"host": [(0.0, 1.0, "bench.engine.step")]}) is None


def test_idle_share_is_not_clamped():
    """With every operation clipped it cannot be negative; a fault that
    made it so (here: busy counted on a window handed in too short for the
    operations, which clip() is bypassed to show) must be seen, not
    hidden at 0."""
    tr = synthetic([(10.0, 20.0, FUSION.format(1))])
    real_clip = trace.clip
    trace.clip = lambda ops, window: ops
    try:
        r = trace.reduce(tr, window=(12.0, 16.0))
    finally:
        trace.clip = real_clip
    assert r["idle_share"] == pytest.approx(1 - 10.0 / 4.0)


def test_every_plane_is_clipped_to_the_one_window():
    tr = synthetic([(5.0, 14.0, FUSION.format(1))])
    tr["devices"][1] = [(16.0, 50.0, FUSION.format(2))]
    tr["devices"][2] = [(0.0, 5.0, FUSION.format(3))]      # nothing inside
    r = trace.reduce(tr)
    assert r["devices"] == 3 and r["busy_s"] == pytest.approx((4 + 4 + 0) / 3)


def test_per_name_sums_are_averaged_over_the_chips(red):
    g = red["groups_s"]
    assert g["matmul_fusions"] == pytest.approx((100 + 320) / 2 * US)
    assert g["collectives"] == pytest.approx(50 / 2 * US)
    assert g["other_fusions"] == pytest.approx(20 / 2 * US)
    # the custom call is named closed_call; this file has no HLO metadata
    assert g["custom_call:closed_call"] == pytest.approx(60 / 2 * US)
    assert g["copy"] == pytest.approx(20 / 2 * US)
    assert red["device_ops"][0][0] == "matmul_fusions"


def test_exposed_collectives(red):
    # the all-gather runs 100..150; another op covers 140..150 of it
    assert red["collective_s"] == pytest.approx(50 * US)
    assert red["exposed_collective_s"] == pytest.approx(40 * US)


def test_idle_gaps_are_named_by_the_innermost_host_span(red):
    gaps = dict(red["idle_gaps"])
    # 160..200 lies under bench.engine.schedule (150..210), the shorter of
    # the two spans over it; 260..300 only under bench.engine.step
    assert gaps["bench.engine.schedule"] == pytest.approx(40 * US)
    assert gaps["bench.engine.step"] == pytest.approx(40 * US)
    assert "not.ours" not in gaps


def test_names_of_groups():
    g = trace.op_group
    assert g("%all-reduce-start.5 = f32[8]{0} all-reduce-start(f32[8] %x)") \
        == "collectives"
    assert g("%reduce-scatter.2 = f32[8]{0} reduce-scatter(f32[32] %x)") \
        == "collectives"
    assert g("%collective-permute-done.1 = f32[8]{0} collective-permute-done(%x)") \
        == "collectives"
    # an operand's name says nothing about the instruction
    assert g("%fusion.7 = f32[8]{0:T(8,128)(2,1)} fusion(f32[8] %all-gather.3, "
             "f32[8] %custom-call.5), kind=kLoop, calls=%fc.1") == "other_fusions"
    assert g("%fusion.7 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(%a, %b), "
             "kind=kOutput, calls=%fc.2") == "matmul_fusions"
    assert g("%bitcast_dynamic-update-slice_fusion.8 = bf16[8]{0} fusion(%a), "
             "kind=kOutput, calls=%fc.3") == "other_fusions"
    # with the HLO metadata, the root operation decides
    assert g("%fusion.7 = bf16[8]{0} fusion(%a), kind=kOutput, calls=%fc.2",
             {"fusion.7": ("jit(f)/mul",)}) == "other_fusions"
    assert g("%fusion.9 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fc.2",
             {"fusion.9": ("jit(f)/layer/dot_general",)}) == "matmul_fusions"
    call = ('%closed_call.3 = bf16[512,32,128]{2,1,0} custom-call(%q, %kv), '
            'custom_call_target="tpu_custom_call"')
    assert g(call) == "custom_call:closed_call"
    assert g(call, {"closed_call.3": ("jit(pstep)/paged_attention/pallas_call",)}) \
        == "paged_attention"
    assert g(call, {"closed_call.3": ("jit(f)/flash_attention/pallas_call",)}) \
        == "flash_attention"
    # a kernel whose path does not name it is named by the configuration,
    # by the file its innermost source frame lies in
    path = "jit(pstep)/while/body/closed_call/pallas_call"
    alias = {"pallas_call@deepspeed_tpu/ops/paged_attention.py": "paged_attention"}
    here = "@/some/checkout/deepspeed_tpu/ops/paged_attention.py"
    assert g(call, {"closed_call.3": (path,)}) == "custom_call:closed_call"
    assert g(call, {"closed_call.3": (path, here)}, alias) == "paged_attention"
    # a Pallas call made from another file, or with no source, is not
    assert g(call, {"closed_call.3": (path, "@/x/ops/fused_mlp.py")}, alias) \
        == "custom_call:closed_call"
    assert g(call, {"closed_call.3": (path,)}, alias) == "custom_call:closed_call"
    # nor is another operation of that file
    assert g('%custom-call.1 = s32[8]{0} custom-call(%a), custom_call_target="g"',
             {"custom-call.1": ("jit(pstep)/gather", here)}, alias) \
        == "custom_call:custom-call"
    assert g('%mixed_matmul_2d.1 = bf16[8]{0} custom-call(%a), '
             'custom_call_target="tpu_custom_call"') == "mixed_gemm"
    assert g("%dynamic-update-slice.3 = bf16[8]{0} dynamic-update-slice(%a, %b)") \
        == "dynamic-update-slice"
    assert g("%copy-done.6 = bf16[8]{0} copy-done(%copy-start.6)") == "copy"
    assert g("%while.13 = (s32[]{:T(128)}, bf16[8,8]{1,0}) while(%t), "
             "condition=%c, body=%b") == "container"
    assert g("fusion.12") == "other_fusions"


def test_containers_count_as_busy_and_in_no_group():
    tr = {"devices": {0: [(0.0, 10.0, "%while.1 = (s32[]) while(%t), body=%b"),
                          (1.0, 4.0, "%fusion.1 = f32[8]{0} fusion(%a), "
                                     "kind=kOutput, calls=%f"),
                          (12.0, 13.0, "%copy.2 = f32[8]{0} copy(%a)")]},
          "host": []}
    r = trace.reduce(tr)
    assert r["busy_s"] == 11.0 and r["window_s"] == 13.0
    assert r["groups_s"] == {"matmul_fusions": 3.0, "copy": 1.0}


def test_interval_arithmetic():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace._subtract([[0, 10]], [[2, 3], [5, 7]]) == 7
    assert trace._subtract([[0, 10]], []) == 10
    assert trace._subtract([[0, 4], [6, 8]], [[3, 7]]) == 4


def test_hlo_collectives_counts_sync_and_async_forms():
    text = ("%ag = all-gather(%x)\n %ar = f32[] all-reduce-start(%y)\n"
            " %d = all-reduce-done(%ar)\n %cp = collective-permute(%z)\n")
    c = trace.hlo_collectives(text)
    assert c["all-gather"] == 1 and c["all-reduce"] == 1
    assert c["collective-permute"] == 1 and c["total"] == 3


def test_no_device_plane_gives_nothing():
    assert trace.reduce({"devices": {}, "host": []}) is None
    assert trace.reduce_dir(None) is None


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n >> 7 else 0)])
        n >>= 7
        if not n:
            return out


def _msg(fno, body):
    return _varint(fno << 3 | 2) + _varint(len(body)) + body


def _int(fno, n):
    return _varint(fno << 3) + _varint(n)


def test_source_file_of_an_instruction_from_the_stack_frame_index(tmp_path):
    # an HloModuleProto by hand: computations (3) of instructions (2) with
    # name (1) and OpMetadata (7: op_name 2, stack_frame_id 15), and the
    # module's StackFrameIndex (17).  Frame 2 is the kernel's call site.
    index = (_msg(1, b"/co/engine.py") + _msg(1, b"/co/ops/paged_attention.py")
             + _msg(3, _int(1, 1) + _int(3, 10)) + _msg(3, _int(1, 2) + _int(3, 162))
             + _msg(4, _int(1, 1)) + _msg(4, _int(1, 2) + _int(2, 1)))

    def instr(name, op_name, frame):
        return _msg(2, _msg(1, name) + _msg(7, _msg(2, op_name) + _int(15, frame)))
    module = (_msg(1, b"jit_pstep")
              + _msg(3, _msg(1, b"body")
                     + instr(b"closed_call.3", b"jit(pstep)/while/body/closed_call/pallas_call", 2)
                     + instr(b"fusion.1", b"jit(pstep)/while/body/dot_general", 1))
              + _msg(17, index))
    # XSpace.planes (1) -> XPlane.event_metadata (4) -> value (2) -> stats
    # (5) -> bytes_value (6) = HloProto{hlo_module (1)}
    space = _msg(1, _msg(4, _msg(2, _msg(5, _msg(6, _msg(1, module))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    names = trace.hlo_op_names(str(path))
    assert names["closed_call.3"] == (
        "jit(pstep)/while/body/closed_call/pallas_call",
        "@/co/ops/paged_attention.py")
    assert names["fusion.1"] == ("jit(pstep)/while/body/dot_general",
                                 "@/co/engine.py")
    assert trace._stack_files(b"not a proto") == {}
