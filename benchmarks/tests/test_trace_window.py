"""The traced window as the other users of a trace take it: the program's
spans (``program_spans.book_idle`` / ``book_scopes``) on the recorded
fixture ``spans.xplane.pb`` (device 0 idle in [40,60] [100,110] [200,300]
[400,450] [500,600] [700,900] of [0,1000] us; ``tests/test_program_spans.py``
has the spans), and run.py's check of its own last line."""

import os

import pytest

from benchmarks.lib import common, program_spans as ps, trace

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6


def at(a, b):
    """A window in the fixture's round microseconds; the file's times lie
    5 us after them."""
    return ((a + 5) * US, (b + 5) * US)


@pytest.fixture(scope="module")
def parts():
    threads, ops, _ = ps.read(os.path.join(HERE, "data", "spans.xplane.pb"))
    return threads, ops


def test_without_a_window_idle_is_booked_first_operation_to_last(parts):
    booked = ps.book_idle(*parts)
    assert booked["window"] == pytest.approx(at(0, 1000))
    assert booked["idle_s"] == pytest.approx(480 * US)


def test_idle_outside_the_window_is_booked_nowhere(parts):
    """[250,650]: of [200,300] only the dispatch's [250,275] and the wait's
    [275,300] are left, of [500,600] all of it, of [600,700] (busy) none."""
    booked = ps.book_idle(*parts, window=at(250, 650))
    assert booked["window"] == at(250, 650)
    by = booked["by_span"]
    assert by["ds.serve.dispatch"] == pytest.approx((25 + 30) * US)
    assert by["ds.serve.wait"] == pytest.approx((25 + 10) * US)
    assert by["ds.gateway.route"] == pytest.approx(10 * US)
    assert by["ds.gateway.apply"] == pytest.approx(20 * US)
    assert by["handoff"] == pytest.approx(40 * US)
    assert by["ds.serve.schedule"] == pytest.approx(20 * US)
    assert by["ds.serve.stage"] == pytest.approx(20 * US)
    assert "unattributed" not in by        # [40,60] lies before the window
    assert booked["idle_s"] == pytest.approx((50 + 50 + 100) * US)
    assert sum(by.values()) == pytest.approx(booked["idle_s"])


def test_idle_at_the_windows_edges_is_booked_too(parts):
    """A window that opens and closes while the device idles: [420,440] is
    the apply's, [700,800] the second step's wait; the stretches from the
    edges to the first and from the last operation count."""
    booked = ps.book_idle(*parts, window=at(420, 800))
    by = booked["by_span"]
    assert by["ds.gateway.apply"] == pytest.approx(20 * US)
    assert by["ds.serve.wait"] == pytest.approx((10 + 100) * US)
    assert booked["idle_s"] == pytest.approx((30 + 100 + 100) * US)
    assert sum(by.values()) == pytest.approx(booked["idle_s"])


def test_a_device_busy_past_both_edges_has_no_idle_to_book(parts):
    threads, _ = parts
    booked = ps.book_idle(threads, [(0.0, 1.0, "%fusion.1 = f32[8] fusion(%a)")],
                          window=at(300, 400))
    assert booked["idle_s"] == 0.0 and booked["by_span"] == {}


def test_scopes_and_steps_count_inside_the_window_only(parts):
    assert ps.book_scopes(trace.clip(parts[1], at(400, 1000)), {})["busy_s"] \
        == pytest.approx(250 * US)
    threads, ops = parts
    whole = ps.Split(threads, ops, {})
    cut = ps.Split(threads, ops, {}, window=at(400, 1000))
    assert whole.steps == 2 and cut.steps == 1
    assert whole.scopes["busy_s"] == pytest.approx(520 * US)
    # [450,500] + [600,700] + [900,1000]
    assert cut.scopes["busy_s"] == pytest.approx(250 * US)
    assert cut.scopes["by_scope"] == {"none": pytest.approx(250 * US)}
    assert cut.hop_us == 30.0


def test_the_split_takes_the_window_the_reduction_cut(tmp_path, capsys):
    """``of(rec)`` hands on the window that ``trace.reduce`` cut; without
    a reduction (the tier-1 tests' ``rec``) it is first operation to last."""
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    with open(os.path.join(HERE, "data", "spans.xplane.pb"), "rb") as f:
        (d / "t.xplane.pb").write_bytes(f.read())
    rec = {"kind": "serve", "trace_dir": str(tmp_path),
           "trace": {"window": at(400, 1000), "cut_by": "span"}}
    assert ps.of(rec).idle["window"] == at(400, 1000)
    rec = {"kind": "serve", "trace_dir": str(tmp_path)}
    assert ps.of(rec).idle["window"] == pytest.approx(at(0, 1000))
    capsys.readouterr()


LINE = {"correct": True, "attempted": 3, "failed": 0, "metrics": {},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 1}}
RED = {"cut_by": "span", "window": (1.0, 6.0), "first_op_s": 0.9,
       "last_op_s": 6.4, "op_events": 7}


def line(**device):
    return {**LINE, "device": {**LINE["device"], **device}}


def test_a_sound_traced_line_has_no_fault():
    for busy in (4.2, 5.0):           # a loop that never idles reads 5.0
        assert common.last_line_faults(line(busy_s=busy, window_s=5.0),
                                       traced=True, on_chip=True,
                                       reduced=RED) == []


@pytest.mark.parametrize("device,says", [
    ({"busy_s": 5.0004, "window_s": 5.0}, "busy_s 5.0004 is over"),
    ({"busy_s": 0.0, "window_s": 5.0}, "busy_s is 0.0"),
    ({"busy_s": float("nan"), "window_s": 5.0}, "busy_s is nan"),
    ({"busy_s": 1.0, "window_s": 0.0}, "window_s is 0.0"),
    ({"busy_s": 1.0, "window_s": None}, "window_s is None"),
    ({}, "window_s is None"),
])
def test_a_traced_line_the_driver_would_refuse_is_refused_here(device, says):
    faults = common.last_line_faults(line(**device), traced=True,
                                     on_chip=True, reduced=RED)
    assert len(faults) == 1 and says in faults[0]
    # which of the two, and the stamps it was cut from
    assert "first operation starts at 0.9" in faults[0]
    assert "(1.0, 6.0)" in faults[0] and "last one ends at 6.4" in faults[0]


def test_a_traced_run_whose_trace_gave_nothing_says_so():
    faults = common.last_line_faults(line(), traced=True, on_chip=True,
                                     reduced=None)
    assert faults == ["device.window_s is None, not a number above 0: "
                      "the trace gave nothing"]


def test_off_the_chip_or_untraced_the_line_carries_neither_number():
    for traced, on_chip in ((False, True), (True, False), (False, False)):
        assert common.last_line_faults(line(), traced, on_chip) == []
        faults = common.last_line_faults(line(busy_s=1.0, window_s=2.0),
                                         traced, on_chip)
        assert len(faults) == 2 and "busy_s" in faults[0]
    missing = {k: v for k, v in LINE.items() if k != "failed"}
    assert common.last_line_faults(missing, False, True) \
        == ["key 'failed' is missing"]


def test_reduce_and_the_self_check_agree_on_a_saturated_device():
    red = trace.reduce({"devices": {0: [(0.0, 9.0, "%fusion.1 = f32[8] "
                                         "fusion(%a), kind=kLoop")]},
                        "host": [(2.0, 7.0, trace.WINDOW_SPAN)]})
    assert common.last_line_faults(
        line(busy_s=red["busy_s"], window_s=red["window_s"]),
        traced=True, on_chip=True, reduced=red) == []
