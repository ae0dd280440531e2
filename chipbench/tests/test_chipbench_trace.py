"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e (`record_trace.py`, two 5-epoch §IV sessions)."""
import os

import pytest

import run
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "sec4_small.xplane.pb")


def test_merge_and_cover():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert merged == [(0, 3), (5, 9), (12, 13)]
    assert tr.covered(merged, 0, 20) == 3 + 4 + 1
    assert tr.covered(merged, 2, 6) == 1 + 1
    assert tr.covered(merged, 9, 12) == 0


def _summary():
    ops = [("_masked_kernel", 10.0, 20.0), ("fusion.1", 15.0, 30.0),
           ("_masked_kernel", 60.0, 70.0), ("copy", 95.0, 120.0),
           ("late", 110.0, 115.0)]
    dev = tr.Device(0, ops, tr.merge((s, e) for _, s, e in ops))
    spans = {"window": [(0.0, 100.0)], "plan": [(0.0, 45.0)],
             "run": [(45.0, 100.0)], "session": [(0.0, 100.0)]}
    return tr.TraceSummary(window=(0.0, 100.0), devices=[dev], spans=spans)


def test_summary_by_hand():
    s = _summary()
    d = s.devices[0]
    assert s.window_ns == 100.0
    assert s.busy_ns(d) == 20 + 10 + 5          # (10,30) (60,70) (95,100)
    assert s.busy_ns(d, 45.0, 100.0) == 10 + 5
    assert s.op_time("_masked_kernel") == [20.0]
    # an op counts whole where it starts inside the window, not at all
    # where it starts after it
    assert dict(s.top_ops()) == pytest.approx(
        {"copy": 25e-9, "_masked_kernel": 20e-9, "fusion.1": 15e-9})
    names, secs = zip(*s.idle_gaps())
    # (30, 60) has its middle in run, (70, 95) in run, (0, 10) in plan
    assert names == ("run", "run", "plan")
    assert secs == pytest.approx((30e-9, 25e-9, 10e-9))


def test_recorded_v5e_trace():
    s = tr.reduce_trace(DATA, run.SPANS)
    assert len(s.devices) >= 1
    dev = s.devices[0]
    assert dev.ops, "no device ops on the TPU plane"
    busy = s.busy_ns(dev)
    assert 0 < busy < s.window_ns
    # two sessions ran inside the window, each with a plan and a run
    assert len(s.spans["plan"]) == len(s.spans["run"]) == 2
    lo, hi = s.window
    assert all(lo <= a < b <= hi for a, b in s.spans["run"])
    # the round-gradient kernel ran in both sessions' 5 epochs, and the
    # reader of its roofline finds it by name
    kernels = run.find_reader("round_grad_roofline").KERNELS
    assert sum(s.op_time(kernels)) > 0
    assert all(name.count("/%") == 1 for name, _ in s.top_ops())
    assert all(name in s.spans or name == "none"
               for name, _ in s.idle_gaps())
