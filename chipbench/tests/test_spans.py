"""The program's own spans in a reduced trace (``chipbench/spans.py``) and
the five readers that read them: made-up intervals, a program without
spans (the parent), and the tiny traced rehearsals of both cells."""

import time
import types

import pytest

import tiny
from chipbench import spans as sp
from chipbench import trace as tr
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

READERS = ["decode_prefill_ms", "decode_host_serial_ms",
           "engine_serial_host_ms", "serve_host_bound_idle_share",
           "door_admit_ms"]


def _made_up_serving_trace():
    """One chip, 100 ms: two 38 ms steps with a 4 ms gap between them,
    then 20 ms with nothing to do.  The engine's spans tile the gap."""
    dev = tr.DeviceTrace("/device:TPU:0")
    dev.ops = [("%fusion.1 = f32[] fusion()", 0.000, 0.038),
               ("%fusion.1 = f32[] fusion()", 0.042, 0.080)]
    spans = [("window", 0.0, 0.100), ("loadgen", 0.0, 0.100),
             ("engine.step", 0.0000, 0.0391),
             ("engine.dispatch", 0.0000, 0.0004),
             ("engine.device", 0.0004, 0.0380),
             ("engine.sync", 0.0380, 0.0381),
             ("engine.delivery", 0.0381, 0.0391),     # 1 ms of the gap
             ("engine.join", 0.0391, 0.0401),         # 1 ms
             ("engine.step", 0.0401, 0.0811),
             ("engine.gather", 0.0401, 0.0411),       # 1 ms
             ("engine.dispatch", 0.0411, 0.0415),     # 0.4 ms
             ("engine.device", 0.0415, 0.0800),       # 0.5 ms of the gap
             ("engine.sync", 0.0800, 0.0801),
             ("engine.delivery", 0.0801, 0.0811),
             ("engine.join", 0.0811, 0.0812),
             ("engine.park", 0.0812, 0.1312),         # past the window
             ("door.admit", 0.0500, 0.0502), ("door.admit", 0.0600, 0.0606)]
    return tr.TraceReduction([dev], spans)


def test_durations_and_the_split_of_a_gap_among_the_spans_over_it():
    red = _made_up_serving_trace()
    assert sp.durations(red, "engine.join") == pytest.approx([0.001, 1e-4])
    assert sp.durations(red, "engine.step") == pytest.approx(
        [0.0391, 0.0410])
    # the park span ends outside the window: not an occurrence inside it
    assert sp.durations(red, "engine.park") == []
    assert sp.durations(red, "no.such.span") == []
    gaps = dict(tr.idle_split(red))
    assert sum(gaps.values()) == pytest.approx(0.024)
    # the 4 ms gap, each instant to the innermost span over it; the
    # 0.1 ms before each 'delivery' lies under 'sync'
    assert gaps["engine.sync"] == pytest.approx(2e-4)
    assert gaps["engine.gather"] == pytest.approx(0.001)
    assert gaps["engine.dispatch"] == pytest.approx(4e-4)
    assert gaps["engine.device"] == pytest.approx(5e-4)
    # the 20 ms tail: delivery 1 ms, join 0.1 ms, the rest parked
    assert gaps["engine.delivery"] == pytest.approx(0.002)
    assert gaps["engine.join"] == pytest.approx(0.0011)
    assert gaps["engine.park"] == pytest.approx(0.0188)
    assert "loadgen" not in gaps and "unattributed" not in gaps
    # the breakdown's split is this one (until PR 52 it handed every gap
    # to the span around the whole call: {"loadgen": 0.024})
    assert dict(red.idle_gaps(n=99)) == gaps
    assert [k for k, _v in red.breakdown()["idle_gaps"]][:2] == [
        "engine.park", "engine.delivery"]


def test_a_gaps_uncovered_remainder_goes_to_its_largest_sharer():
    dev = tr.DeviceTrace("/device:TPU:0")
    dev.ops = [("%copy.1 = f32[] copy()", 0.0, 1.0),
               ("%copy.2 = f32[] copy()", 5.0, 6.0),
               ("%copy.3 = f32[] copy()", 8.0, 9.0)]
    spans = [("window", 0.0, 9.0), ("a", 1.5, 2.5), ("b", 3.0, 4.5)]
    gaps = dict(tr.idle_split(tr.TraceReduction([dev], spans)))
    # gap 1-5: a 1.0, b 1.5, bare 1.5 -> b; gap 6-8: no span touches it
    assert gaps == {"a": pytest.approx(1.0), "b": pytest.approx(3.0),
                    "unattributed": pytest.approx(2.0)}


def test_with_one_span_over_a_gap_the_split_is_the_old_rule_s():
    """The made-up trace of ``test_trace.py``: where one span lies over a
    gap, the split reads what most-overlap read until PR 52."""
    dev = tr.DeviceTrace("/device:TPU:0")
    dev.ops = [("%fusion.1 = f32[] fusion()", 1.0, 2.5),
               ("%copy.3 = f32[] copy()", 4.0, 5.0)]
    spans = [("window", 0.0, 10.0), ("loadgen", 0.0, 10.0),
             ("drain", 2.4, 4.1)]
    red = tr.TraceReduction([dev], spans)
    assert dict(tr.idle_split(red)) == dict(red.idle_gaps()) \
        == {"drain": pytest.approx(1.5), "loadgen": pytest.approx(6.0)}


def _read(name, red):
    run = types.SimpleNamespace(trace=red, counters={})
    return Manifest().reader(name).read(run)


def test_the_serving_span_readers_on_made_up_spans():
    red = _made_up_serving_trace()
    # delivery 2 x 1.0, join 1.0 + 0.1, gather 1.0, dispatch 2 x 0.4 ms
    # over two steps
    assert _read("engine_serial_host_ms", red) == pytest.approx(
        (2.0 + 1.1 + 1.0 + 0.8) / 2)
    assert _read("door_admit_ms", red) == pytest.approx(0.4)
    # 24 ms idle in the 100 ms window, 18.8 of them parked
    assert _read("serve_host_bound_idle_share", red) == pytest.approx(5.2)
    assert 100 * red.idle_share == pytest.approx(24.0)


def test_the_decode_span_readers_on_made_up_spans():
    dev = tr.DeviceTrace("/device:TPU:0")
    dev.ops = [("%fusion.1 = f32[] fusion()", 0.0, 3.0)]
    spans = [("window", 0.0, 3.0), ("decode.prefill", 0.0, 0.120),
             ("decode.dispatch", 0.120, 0.1215), ("decode.sync", 0.1215, 1.5),
             ("decode.scatter", 1.5, 1.5005), ("decode.emit", 1.5005, 1.501),
             ("decode.dispatch", 1.501, 1.5025), ("decode.sync", 1.5025, 2.9),
             ("decode.scatter", 2.9, 2.9005), ("decode.emit", 2.9005, 2.901),
             ("decode.prefill", 2.95, 3.08)]          # cut by the window
    red = tr.TraceReduction([dev], spans)
    assert _read("decode_prefill_ms", red) == pytest.approx(120.0)
    assert _read("decode_host_serial_ms", red) == pytest.approx(2.5)


@pytest.mark.parametrize("name", READERS)
def test_a_span_reader_gives_nothing_for_a_program_without_spans(name):
    dev = tr.DeviceTrace("/device:TPU:0")
    dev.ops = [("%fusion.1 = f32[] fusion()", 0.0, 1.0)]
    parent = tr.TraceReduction([dev], [("window", 0.0, 2.0),
                                       ("loadgen", 0.0, 2.0)])
    assert _read(name, parent) is None
    assert _read(name, None) is None


def test_the_recorded_trace_holds_no_program_span():
    red = tr.load(tiny.PKG + "/testdata/small.xplane.pb")
    assert sp.durations(red, "sleep") == pytest.approx(5 * [0.0308],
                                                       rel=0.03)
    assert not [n for n, _s, _e in red.spans if "." in n]
    # two spans touch each gap there: the sleep, and the work beside it
    split = dict(tr.idle_split(red))
    assert split["sleep"] == pytest.approx(0.1511, rel=0.01)
    assert split["work"] == pytest.approx(0.0084, rel=0.05)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny_root")))


@pytest.mark.parametrize("cell, metrics", [
    ("batch_tiny", {"decode_prefill_ms", "decode_host_serial_ms"}),
    ("chat_tiny", {"engine_serial_host_ms", "serve_host_bound_idle_share",
                   "door_admit_ms"})])
def test_a_traced_run_reads_the_programs_own_spans(root, cell, metrics):
    doc = run_cell(workload=cell, seed=2 ** 31 + 7, seconds=1.0, trace=True,
                   t_start=time.perf_counter(), root=root, require_tpu=False)
    assert doc["correct"] is True
    assert metrics <= set(doc["metrics"])
    assert all(doc["metrics"][m]["value"] > 0 for m in metrics)
    if cell == "chat_tiny":
        m = doc["metrics"]
        assert m["serve_host_bound_idle_share"]["value"] \
            <= m["serve_device_idle_share"]["value"] + 1e-9
