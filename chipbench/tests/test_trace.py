"""The trace reduction: interval arithmetic on made-up intervals, and the
recorded TPU trace ``chipbench/testdata/small.xplane.pb`` (five bursts of
matrix multiplications with a 30 ms sleep after each, recorded on a TPU
v5e by ``record_fixture.py``)."""

import os

import pytest

import tiny
from chipbench import trace as tr

FIXTURE = os.path.join(tiny.PKG, "testdata", "small.xplane.pb")


def test_union_gaps_and_clip_on_known_intervals():
    busy = tr.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6),
                     (5.0, 5.0)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.total(busy) == 3.0
    assert tr.gaps(busy, -1.0, 6.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 6.0)]
    assert tr.clip(busy, 1.5, 3.5) == [(1.5, 2.0), (3.0, 3.5)]
    assert tr.overlap((0.0, 2.0), (1.5, 9.0)) == 0.5


def test_busy_idle_and_attribution_on_a_made_up_trace():
    dev = tr.DeviceTrace("/device:TPU:0")
    dev.ops = [("%fusion.1 = f32[] fusion()", 1.0, 2.0),
               ("%fusion.2 = f32[] fusion()", 2.0, 2.5),
               ("%collective-permute-done.1 = f32[] x()", 4.0, 4.5),
               ("%copy.3 = f32[] copy()", 4.25, 5.0),
               # a scan's parent encloses its body's operations
               ("%while.1 = () while()", 4.0, 5.0)]
    dev.modules = [("jit_step(1)", 1.0, 2.5), ("jit_step(1)", 4.0, 5.0),
                   ("jit_other(2)", 0.0, 0.5)]
    spans = [("window", 0.0, 10.0), ("loadgen", 0.0, 10.0),
             ("drain", 2.4, 4.1)]
    red = tr.TraceReduction([dev], spans)
    assert red.window_s == 10.0 and red.busy_s == pytest.approx(2.5)
    assert red.idle_share == pytest.approx(0.75)
    assert red.module_runs(r"jit_step") == [1.5, 1.0]
    ops = dict(red.top_ops())
    assert ops["fusion"] == pytest.approx(1.5) and ops["copy"] == 0.75
    gaps = dict(red.idle_gaps())
    # the 1.5 s gap sits inside the shorter 'drain' span; the rest has
    # only 'loadgen' over it
    assert gaps["drain"] == pytest.approx(1.5)
    assert gaps["loadgen"] == pytest.approx(6.0)
    assert set(red.breakdown()) == {"device_ops", "idle_gaps"}


def test_op_names_are_cut_from_the_hlo_text():
    name = ("%dynamic-update-slice.6 = bf16[1,1600,50257]{2,1,0} "
            "dynamic-update-slice(bf16[1,1600,50257] %x, u32[] %y)")
    assert tr.op_name(name) == "dynamic-update-slice.6"
    assert tr.op_kind(name) == "dynamic-update-slice"
    assert tr.op_kind("fusion.123") == "fusion"


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_the_recorded_trace_gives_the_known_numbers():
    red = tr.load(FIXTURE)
    assert len(red.devices) == 1
    assert len(red.devices[0].modules) == 5
    # the device's events sit about 1 ms before the host spans that
    # launched them (the two clocks of this trace are that far apart), so
    # the first burst falls just outside the window span: four runs count
    runs = red.module_runs(r"jit_burst")
    assert len(runs) == 4
    assert all(r == pytest.approx(0.722e-3, rel=0.01) for r in runs)
    # the recipe: five 30 ms sleeps, each a long idle gap inside 'sleep'
    gaps = dict(red.idle_gaps())
    assert gaps["sleep"] == pytest.approx(5 * 0.030, rel=0.15)
    assert set(gaps) <= {"sleep", "work", "between_ops_under_20us"}
    # busy time is the union of the operations: the program runs' own
    # durations (another line of the trace) confirm it
    assert red.busy_s == pytest.approx(sum(runs), rel=0.02)
    assert red.busy_s == pytest.approx(EXPECT["busy_s"], rel=1e-6)
    assert red.window_s == pytest.approx(EXPECT["window_s"], rel=1e-6)
    assert red.idle_share == pytest.approx(
        1 - EXPECT["busy_s"] / EXPECT["window_s"], rel=1e-6)
    assert red.top_ops()[0][0] == EXPECT["top_op"]


#: read once from the recorded file (2026-09-27) and checked by hand
#: against the recipe above
EXPECT = {"busy_s": 0.002886769, "window_s": 0.162458677, "top_op": "fusion"}
