"""The reader of the program's own spans in a profiler trace
(``benchmarks/lib/program_spans.py``) against a synthetic XSpace written
by ``benchmarks/tests/make_span_fixture.py`` (round microseconds, so the
booking is checked by hand here), and BENCHMARK.json's new entries
against their readers.

The fixture: device 0 is idle in [40,60] [100,110] [200,300] [400,450]
[500,600] [700,900] of [0,1000] us.  The engine's thread holds two
``ds.gateway.pump`` spans ([90,320] and [520,1000]) with a step's phases
inside each and a ``ds.gateway.apply`` [420,440] between them; the event
loop holds ``ds.gateway.route`` [330,410] and a second one [585,595]
that lies wholly under the engine thread's dispatch and wait."""

import json
import os

import pytest

from benchmarks.lib import program_spans as ps
from benchmarks.lib.common import reader_path

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "benchmarks", "tests", "data", "spans.xplane.pb")
US = 1e-6
NEW = ("idle_engine_host_ms_per_step", "idle_route_ms_per_step",
       "idle_apply_ms_per_step", "idle_handoff_ms_per_step",
       "guard_hop_ms_per_step", "kv_write_share", "sampler_share")


@pytest.fixture(scope="module")
def parts():
    threads, ops, op_names = ps.read(FIXTURE)
    assert op_names == {}             # the fixture embeds no HLO proto
    return threads, ops


@pytest.fixture(scope="module")
def split(parts):
    return ps.Split(*parts, op_names={})


def test_reads_ds_spans_by_thread_with_their_stats(parts):
    threads, ops = parts
    assert len(ops) == 7
    assert sorted(len(v) for v in threads.values()) == [2, 14]
    engine = max(threads.values(), key=len)
    names = {nm for _, _, nm, _ in engine}
    assert "not.ours" not in names and "ds.gateway.pump" in names
    stats = {(nm, st.get("sid")): st for _, _, nm, st in engine}
    assert stats[("ds.serve.dispatch", 2)]["hop_us"] == 30.0
    assert stats[("ds.serve.stage", 1)]["n_tokens"] == 5


def test_idle_is_booked_by_overlap_not_by_midpoint(parts):
    """[200,300] spans five phases: each gets its own piece (the midpoint
    rule of ``trace.reduce`` would give all 100 us to the dispatch)."""
    by = ps.book_idle(*parts)["by_span"]
    assert by["ds.serve.schedule"] == pytest.approx((5 + 10 + 20) * US)
    assert by["ds.serve.prefix_match"] == pytest.approx(5 * US)   # innermost
    assert by["ds.serve.stage"] == pytest.approx((20 + 20) * US)
    assert by["ds.serve.dispatch"] == pytest.approx((30 + 30) * US)
    assert by["ds.serve.wait"] == pytest.approx((25 + 10 + 200) * US)
    # inside a pump, outside every phase: the pump itself
    assert by["ds.gateway.pump"] == pytest.approx((10 + 5) * US)
    assert "ds.serve.readback" not in by      # the device was busy there


def test_engine_thread_is_asked_before_the_event_loop(parts):
    """The route span at [585,595] overlaps idle time, but the engine's
    thread had dispatch and wait open there: only [400,410] of the first
    route is booked to the loop."""
    by = ps.book_idle(*parts)["by_span"]
    assert by["ds.gateway.route"] == pytest.approx(10 * US)
    assert by["ds.gateway.apply"] == pytest.approx(20 * US)


def test_no_span_is_handoff_between_pumps_else_unattributed(parts):
    by = ps.book_idle(*parts)["by_span"]
    # [410,420] + [440,450] + [500,520]: after pump 1, before pump 2
    assert by["handoff"] == pytest.approx(40 * US)
    # [40,60]: before the first pump
    assert by["unattributed"] == pytest.approx(20 * US)


def test_the_pieces_sum_to_the_idle_total(parts, split):
    booked = ps.book_idle(*parts)
    assert booked["idle_s"] == pytest.approx(480 * US)
    assert sum(booked["by_span"].values()) == pytest.approx(booked["idle_s"])
    assert booked["window"][1] - booked["window"][0] == pytest.approx(1e-3)
    # the four idle_* metrics, the unattributed and the spans no metric
    # reads (pump, wait) make up device-0 idle per step
    four = sum(split.idle_ms_per_step(*names) for names in (
        ps.ENGINE_HOST, (ps.ROUTE,), (ps.APPLY,), ("handoff",)))
    rest = split.idle_ms_per_step("unattributed", ps.PUMP, ps.WAIT)
    assert split.steps == 2
    assert four == pytest.approx((140 + 10 + 20 + 40) / 2 * 1e-3)
    assert four + rest == pytest.approx(480 / 2 * 1e-3)


@pytest.mark.parametrize("name,want", [
    ("idle_engine_host_ms_per_step", 0.070),
    ("idle_route_ms_per_step", 0.005),
    ("idle_apply_ms_per_step", 0.010),
    ("idle_handoff_ms_per_step", 0.020),
    ("guard_hop_ms_per_step", 0.030),
])
def test_span_readers_on_the_fixture(tmp_path, name, want, capsys):
    """Each reader through the path run.py takes: ``rec["trace_dir"]``."""
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(open(FIXTURE, "rb").read())
    rec = {"kind": "serve", "trace_dir": str(tmp_path)}
    from benchmarks.lib.common import load_module
    reader = load_module(reader_path("layer_metrics", "decode." + name),
                         "metric_" + name)
    assert reader.read(rec) == pytest.approx(want)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["note"] for ln in lines] == ["program_spans", "device_scopes"]
    assert lines[0]["spans"]["ds.serve.wait"]["idle_s"] \
        == pytest.approx(235 * US)
    assert lines[0]["other_spans_s"] == pytest.approx(250 * US)
    # computed once, printed once
    assert reader.read(rec) == pytest.approx(want)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("rec", [
    {"kind": "serve", "trace_dir": None},            # untraced run
    {"kind": "train", "trace_dir": "/nonexistent"},  # a train cell
    {"kind": "serve", "trace_dir": "/nonexistent"},  # no file
])
@pytest.mark.parametrize("name", NEW)
def test_readers_report_nothing_without_a_trace(rec, name):
    from benchmarks.lib.common import load_module
    reader = load_module(reader_path("layer_metrics", "prefill." + name),
                         "metric_" + name)
    assert reader.read(dict(rec)) is None


def test_readers_report_nothing_without_ds_events(tmp_path):
    """The parent's trace: ``bench.*`` spans, no ``ds.*`` one, no scope."""
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    old = os.path.join(ROOT, "benchmarks", "tests", "data", "small.xplane.pb")
    (d / "t.xplane.pb").write_bytes(open(old, "rb").read())
    assert ps.of({"kind": "serve", "trace_dir": str(tmp_path)}) is None


def at(a, b):
    """A window in the fixture's round microseconds; the file's times lie
    5 us after them."""
    return ((a + 5) * US, (b + 5) * US)


@pytest.mark.parametrize("window,ops,idle_us,by_us", [
    # no window: first operation to last
    (None, None, 480, {}),
    # [250,650]: of [200,300] only the dispatch's [250,275] and the wait's
    # [275,300] are left, of [500,600] all of it; [40,60] lies before it
    (at(250, 650), None, 200,
     {"ds.serve.dispatch": 55, "ds.serve.wait": 35, "ds.gateway.route": 10,
      "ds.gateway.apply": 20, "handoff": 40, "ds.serve.schedule": 20,
      "ds.serve.stage": 20, "unattributed": 0}),
    # a window that opens and closes while the device idles: the stretches
    # from its edges to the first and from the last operation count
    (at(420, 800), None, 230,
     {"ds.gateway.apply": 20, "ds.serve.wait": 110}),
    # a device busy past both edges has no idle to book
    (at(300, 400), [(0.0, 1.0, "%fusion.1 = f32[8] fusion(%a)")], 0, {}),
])
def test_idle_is_booked_inside_the_window_only(parts, window, ops, idle_us,
                                               by_us):
    """ISSUE 33's cases for ``book_idle``'s ``(lo, hi)`` (they stand in
    ``benchmarks/tests/test_trace_window.py`` too, which tier-1 does not
    run)."""
    threads, recorded = parts
    booked = ps.book_idle(threads, ops or recorded, window=window)
    assert booked["window"] == pytest.approx(window or at(0, 1000))
    assert booked["idle_s"] == pytest.approx(idle_us * US)
    assert sum(booked["by_span"].values()) == pytest.approx(booked["idle_s"])
    for name, us in by_us.items():
        assert booked["by_span"].get(name, 0.0) == pytest.approx(us * US)
    if not idle_us:
        assert booked["by_span"] == {}


def test_scopes_by_whole_path_component():
    assert ps.scope_of(("jit(pstep)/while/body/kv_write/dynamic_update_slice",
                        "@model.py")) == "kv_write"
    assert ps.scope_of(("jit(pstep)/while/body/attn_out/dot_general",)) \
        == "attn_out"
    assert ps.scope_of(("jit(pstep)/while/body/attn/closed_call",)) == "attn"
    assert ps.scope_of(("jit(pstep)/sample/argmax",)) == "sample"
    # a component that merely contains a scope's name is not that scope
    assert ps.scope_of(("jit(pstep)/kv_write_back/copy",)) is None
    assert ps.scope_of(()) is None


def test_scope_shares_leave_containers_out():
    fus = ("%fusion.{0} = bf16[8]{{0}} fusion(%p), kind=kLoop, "
           "calls=%fused_computation.{0}")
    ops = [(0.0, 1.0, "%while.1 = (s32[]) while(%t), body=%b"),   # container
           (0.0, 0.2, fus.format(1)), (0.2, 0.5, fus.format(2)),
           (0.5, 0.9, fus.format(3)), (0.9, 1.0, "%copy.4 = bf16[8] copy(%p)")]
    names = {"fusion.1": ("jit(pstep)/while/body/kv_write/scatter",),
             "fusion.2": ("jit(pstep)/while/body/ffn/mul",),
             "fusion.3": ("jit(pstep)/unembed/dot_general",),
             "copy.4": ("jit(pstep)/sample/argmax",)}
    got = ps.book_scopes(ops, names)
    assert got["busy_s"] == pytest.approx(1.0)
    assert got["by_scope"] == pytest.approx(
        {"kv_write": 0.2, "ffn": 0.3, "unembed": 0.4, "sample": 0.1})
    assert got["unscoped"] == {}
    # what no scope covers is still named: stem and tail of its path
    names["fusion.2"] = ("jit(pstep)/while/body/dynamic_update_slice",)
    del names["copy.4"]
    assert ps.book_scopes(ops, names)["unscoped"] == pytest.approx(
        {"fusion while/body/dynamic_update_slice": 0.3, "copy": 0.1})
    sp = ps.Split.__new__(ps.Split)
    sp.scopes = got
    assert sp.scope_share("kv_write") == pytest.approx(20.0)
    assert sp.scope_share("unembed", "sample") == pytest.approx(50.0)
    # the parent's programs carry no scope: nothing to report
    sp.scopes = ps.book_scopes(ops, {})
    assert sp.scope_share("kv_write") is None


def test_each_new_entry_has_a_reader_and_the_agreed_shape():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        for pre, moves, cell in (("decode", "out_tokens_per_s", "serve-decode"),
                                 ("prefill", "itl_p95_ms", "serve-prefill")):
            m = by_name[f"{pre}.{name}"]
            assert (m["moves"], m["workloads"], m["better"]) == \
                (moves, [cell], "lower")
            assert m["source"] == ("device_trace" if name.endswith("_share")
                                   else "program_span")
            path = reader_path("layer_metrics", m["name"])
            assert path.endswith(os.path.join("layer_metrics", name + ".py"))
            assert os.path.exists(path)
