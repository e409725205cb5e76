"""The trace reduction gives the known busy time, idle share, top
operations and named idle gaps."""
import glob
import json
import os

import pytest

from chipbench import traces

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _hand_trace():
    # window [0, 100] ns; ops overlap, and one runs past the window's end
    return traces.Trace(
        device={"/device:TPU:0": [("a", 10, 30), ("b", 20, 40), ("a", 50, 60),
                                  ("c", 90, 120), ("c", 150, 160)]},
        modules={},
        host=[("window", 0, 100), ("next_input", 0, 12), ("dispatch", 38, 52),
              ("block", 55, 95)],
    )


def test_hand_trace_busy_idle_ops_and_gaps():
    s = traces.summarize(_hand_trace())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(50e-9)  # [10,40] + [50,60] + [90,100]
    assert s.idle_share == pytest.approx(0.5)
    assert s.device_span_s == pytest.approx(90e-9)
    assert [n for n, _ in s.top_ops] == ["a", "b", "c"]
    assert [t for _, t in s.top_ops] == pytest.approx([30e-9, 20e-9, 10e-9])
    assert s.gaps[0] == ["block", pytest.approx(30e-9)]
    assert sorted(n for n, _ in s.gaps[1:]) == ["dispatch", "next_input"]
    assert s.gap_totals == pytest.approx({"block": 30e-9, "dispatch": 10e-9,
                                          "next_input": 10e-9})


def test_two_devices_are_averaged():
    t = _hand_trace()
    t.device["/device:TPU:1"] = [("a", 0, 100)]
    t.modules["/device:TPU:1"] = []
    s = traces.summarize(t)
    assert s.busy_s == pytest.approx(75e-9)
    assert s.idle_share == pytest.approx(0.25)


def test_trace_without_window_or_device_ops_is_refused():
    t = _hand_trace()
    with pytest.raises(ValueError):
        traces.summarize(traces.Trace(t.device, {}, [h for h in t.host if h[0] != "window"]))
    with pytest.raises(ValueError):
        traces.summarize(traces.Trace({}, {}, t.host))


def test_loop_ops_do_not_count_twice_among_top_ops():
    # a while op spans its body's ops on the same line
    t = traces.Trace({"/device:TPU:0": [("while.1", 0, 90), ("k", 5, 45), ("f", 50, 80)]},
                     {}, [("window", 0, 100)])
    s = traces.summarize(t)
    assert [n for n, _ in s.top_ops] == ["k", "f"]
    assert s.busy_s == pytest.approx(90e-9)


def test_device_clock_is_put_on_the_host_clock():
    # programs stamped 7 ns before their dispatch ends: shifted by +7
    t = traces.Trace(
        {"/device:TPU:0": [("k", 3, 33), ("k", 53, 83)]},
        {"/device:TPU:0": [("jit_call", 3, 33), ("jit_call", 53, 83)]},
        [("window", 0, 100), ("dispatch", 2, 10), ("block", 10, 45),
         ("dispatch", 50, 60), ("block", 60, 95)])
    assert traces.clock_offset(t.modules["/device:TPU:0"], t.host) == 7
    s = traces.summarize(t)
    assert s.offset_s == {"/device:TPU:0": pytest.approx(7e-9)}
    assert s.busy_s == pytest.approx(60e-9)
    # idle [0,10] in dispatch, [40,60] in block (5) and dispatch (10), [90,100]
    assert s.gaps[0] == ["dispatch", pytest.approx(20e-9)]


RECORDED = sorted(glob.glob(os.path.join(DATA, "trace_*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_chip_trace(path):
    """The milliseconds around one gap between two calls of a cell, as
    recorded on a TPU v5e and cut to kilobytes.  The expected numbers were
    worked out apart from ``summarize``, on a 1 ns timeline."""
    with open(path) as f:
        rec = json.load(f)
    s = traces.summarize(traces.Trace.from_json(rec["trace"]))
    want = rec["expected"]
    assert s.offset_s[next(iter(s.offset_s))] * 1e9 == pytest.approx(want["offset_ns"])
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-4)
    assert s.idle_share == pytest.approx(want["idle_share"], rel=1e-4)
    assert s.gaps[0][1] == pytest.approx(want["longest_gap_s"], rel=1e-3)
    assert [n for n, _ in s.top_ops[:3]] == want["top_ops"]
    assert s.gaps[0][0] == want["longest_gap"]
    assert os.path.getsize(path) < 64 * 1024
