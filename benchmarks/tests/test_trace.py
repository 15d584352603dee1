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
    r = trace.reduce(trace.read(os.path.join(HERE, "data", "small.xplane.pb")),
                     window_s=400 * US)
    assert r["idle_share"] == pytest.approx(1 - 280 / 400)


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
