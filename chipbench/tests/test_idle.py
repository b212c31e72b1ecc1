"""Idle time by what the host's decode loop was doing
(``chipbench/idle.py``) and the eight readers over it: made-up intervals,
a window with one pause marker, the planes' causality bracket with a
known skew, a program without the launch spans (the parent), and the
tiny traced rehearsals of both kinds of cell."""

import math
import time
import types

import pytest

import tiny
from chipbench import idle
from chipbench import trace as tr
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

DECODE_READERS = ["decode_idle_wake_ms", "decode_idle_launch_ms",
                  "decode_upload_ms", "decode_pause_share"]
ENGINE_READERS = ["engine_idle_wake_ms", "engine_idle_launch_ms",
                  "engine_upload_ms", "serve_pause_share"]


def _read(name, red):
    run = types.SimpleNamespace(trace=red, counters={})
    return Manifest().reader(name).read(run)


def _decode_trace(skew=0.0, chips=1):
    """100 ms, two chunks of 20 ms.  Before the first program the host
    uploads 0.5 ms and launches 1.5; between the two the chip idles 4 ms
    under sync 1.0, scatter 1.0, emit 0.5, upload 0.5, launch 1.0; behind
    the second under sync 1.0, scatter 1.0, emit 0.2 and then only the
    span around the whole generation.  The device plane's clock reads
    ``skew`` over the host plane's; a second chip ends each program
    0.5 ms later."""
    devs = []
    for c in range(chips):
        dev = tr.DeviceTrace(f"/device:TPU:{c}")
        runs = [(0.010, 0.030 + 0.0005 * c), (0.034, 0.054 + 0.0005 * c)]
        dev.ops = [("%fusion.1 = f32[] fusion()", s + skew, e + skew)
                   for s, e in runs]
        dev.modules = [("jit_device_decode(123)", s + skew, e + skew)
                       for s, e in runs]
        devs.append(dev)
    spans = [("window", 0.0, 0.100), ("generate", 0.0, 0.100),
             ("decode.generate", 0.0001, 0.0999),
             ("decode.dispatch", 0.0080, 0.0100),
             ("decode.upload", 0.0080, 0.0085),
             ("decode.launch", 0.0085, 0.0100),
             ("decode.sync", 0.0100, 0.0310),
             ("decode.scatter", 0.0310, 0.0320),
             ("decode.emit", 0.0320, 0.0325),
             ("decode.dispatch", 0.0325, 0.0340),
             ("decode.upload", 0.0325, 0.0330),
             ("decode.launch", 0.0330, 0.0340),
             ("decode.sync", 0.0340, 0.0550),
             ("decode.scatter", 0.0550, 0.0560),
             ("decode.emit", 0.0560, 0.0562)]
    return tr.TraceReduction(devs, spans)


def test_a_gap_is_split_between_sync_scatter_upload_and_launch():
    red = _decode_trace()
    by = idle.split(red, idle.DECODE)
    assert sum(by.values()) == pytest.approx(0.060)
    assert by["decode.sync"] == pytest.approx(0.0020)
    assert by["decode.scatter"] == pytest.approx(0.0020)
    assert by["decode.emit"] == pytest.approx(0.0007)
    assert by["decode.upload"] == pytest.approx(0.0010)
    assert by["decode.launch"] == pytest.approx(0.0025)
    assert "decode.dispatch" not in by       # its children tile it
    # what no phase covers stays with the span around the generation
    assert by["decode.generate"] == pytest.approx(0.0516)
    assert by["generate"] == pytest.approx(0.0002)
    assert _read("decode_idle_wake_ms", red) == pytest.approx(1.0)
    assert _read("decode_idle_launch_ms", red) == pytest.approx(1.75)
    assert _read("decode_upload_ms", red) == pytest.approx(0.5)
    assert _read("decode_pause_share", red) == 0.0


def test_idle_time_a_round_is_the_mean_over_the_cells_chips():
    one, two = _decode_trace(), _decode_trace(chips=2)
    # the second chip ends each program 0.5 ms later: it idles half as
    # long under the sync, and the mean has half of that
    assert idle.per_round_ms(two, idle.DECODE, idle.DECODE.launch) \
        == pytest.approx(1.75)
    assert idle.per_round_ms(two, idle.DECODE, idle.DECODE.wake) \
        == pytest.approx(0.75)
    assert sum(idle.split(two, idle.DECODE).values()) == pytest.approx(
        0.5 * (0.060 + 0.059))
    assert idle.per_round_ms(one, idle.DECODE, ("decode.scatter",)) \
        == pytest.approx(1.0)


def test_the_causality_bracket_holds_zero_on_planes_that_read_one_clock():
    red = _decode_trace()
    lo, hi, rounds = idle.causality_bracket(red, idle.DECODE)
    # the host was back 1.0 ms after a program's end at the least, and a
    # program began 1.0 ms after its launch began at the least
    assert (lo, hi, rounds) == (pytest.approx(-0.0010),
                                pytest.approx(0.0010), 2)
    assert idle.skew_shift(red, idle.DECODE) == pytest.approx(0.0)
    # on two chips: the earliest end and the earliest start bound it
    lo, hi, _n = idle.causality_bracket(_decode_trace(chips=2), idle.DECODE)
    assert (lo, hi) == (pytest.approx(-0.0010), pytest.approx(0.0010))


def test_a_host_that_woke_late_is_not_held_against_the_next_program():
    """The paused sync ended 60 ms after its program and 1.5 ms before the
    next one began: its round says 'at least 60 ms', not 'skew'."""
    red = _paused_trace()
    for dev in red.devices:
        dev.modules = [("jit_device_decode(1)", s, e)
                       for _n, s, e in dev.ops]
        dev.modules.append(("jit_device_decode(1)", 0.1265, 0.1445))
    lo, hi, rounds = idle.causality_bracket(red, idle.DECODE)
    assert (lo, hi, rounds) == (pytest.approx(-0.0005),
                                pytest.approx(0.0015), 3)
    # a bracket that contradicts itself moves nothing: the first program
    # begins before its launch did and ends after its wait did
    for dev in red.devices:
        dev.modules[0] = ("jit_device_decode(1)", 0.0, 0.0215)
    lo, hi, _n = idle.causality_bracket(red, idle.DECODE)
    assert (lo, hi) == (pytest.approx(0.0010), pytest.approx(-0.0005))
    assert idle.skew_shift(red, idle.DECODE) == 0.0


def _queued_trace(skew=0.0):
    """The ring since PR 37, one chip, 140 ms: a chunk is launched before
    the one ahead of it is read.  Generation A launches L0 onto an idle
    chip (its program begins 1.5 ms later) and L1 behind it, then reads
    and launches in turn; L2 and L3 find the chip 1.5 ms into the
    program before theirs.  A is stopped behind its third read: P3 is
    never read.  Generation B waits for P3, launches L4 onto an idle
    chip (1.0 ms) and L5, and its first wait ends 0.05 ms behind P4: the
    tightest of the four, and the fourth wait of the window."""
    dev = tr.DeviceTrace("/device:TPU:0")
    runs = [(0.0035, 0.0235), (0.02351, 0.0435), (0.04351, 0.0635),
            (0.06351, 0.0835), (0.0910, 0.1110), (0.11101, 0.1310)]
    dev.ops = [("%fusion.1 = f32[] fusion()", s + skew, e + skew)
               for s, e in runs]
    dev.modules = [("jit_device_decode(9)", s + skew, e + skew)
                   for s, e in runs]
    spans = [("window", 0.0, 0.140), ("generate", 0.0005, 0.0650),
             ("decode.generate", 0.0010, 0.0650),
             ("generate", 0.0655, 0.1400),
             ("decode.generate", 0.0660, 0.1399)]
    for t in (0.0020, 0.0030, 0.0250, 0.0450, 0.0900, 0.0910):
        spans += [("decode.dispatch", t, t + 0.0010),
                  ("decode.upload", t, t + 0.0003),
                  ("decode.launch", t + 0.0003, t + 0.0010)]
    spans += [("decode.sync", 0.0040, 0.0237), ("decode.sync", 0.0260, 0.0436),
              ("decode.sync", 0.0460, 0.0638),
              ("decode.init", 0.0661, 0.0836),      # waits for P3
              ("decode.sync", 0.0920, 0.11105)]
    return tr.TraceReduction([dev], spans)


@pytest.mark.parametrize("skew", [0.0, 0.0012, -0.0016])
def test_with_a_chunk_queued_ahead_the_kth_wait_reads_the_kth_program(skew):
    red = _queued_trace(skew)
    lo, hi, rounds = idle.causality_bracket(red, idle.DECODE)
    # hi from L4 alone of the launches that found the chip idle (L0:
    # 1.2 ms from the launch's own start); lo from B's first wait, held
    # against B's first program, not A's unread one
    assert (lo, hi, rounds) == (pytest.approx(skew - 0.00005),
                                pytest.approx(skew + 0.0007), 4)
    assert idle.skew_shift(red, idle.DECODE) == pytest.approx(
        skew + 0.000325)
    # until PR 52 L2 was held against P1, running since 1.5 ms: hi < lo
    # and "contradicts itself, nothing moved" in every ring cell
    wake = _read("decode_idle_wake_ms", red)
    assert wake == pytest.approx(_read("decode_idle_wake_ms",
                                       _queued_trace()), abs=1e-6)


@pytest.mark.parametrize("skew", [0.003, -0.002])
def test_a_known_skew_is_bracketed_and_taken_out_before_the_split(skew):
    red = _decode_trace(skew=skew)
    lo, hi, _n = idle.causality_bracket(red, idle.DECODE)
    assert lo == pytest.approx(skew - 0.0010)
    assert hi == pytest.approx(skew + 0.0010)
    assert idle.skew_shift(red, idle.DECODE) == pytest.approx(skew)
    # left as it is, the split would hand the launch's idle time to the
    # sync (or the other way round); moved to the bracket's middle it is
    # what the planes on one clock read
    assert _read("decode_idle_wake_ms", red) == pytest.approx(1.0, abs=0.01)
    assert _read("decode_idle_launch_ms", red) == pytest.approx(
        1.75, abs=0.01)
    raw = dict(idle.idle_split(red, n=99))
    assert abs(1e3 * raw.get("decode.sync", 0.0) / 2 - 1.0) > 0.5


def test_a_bracket_that_holds_zero_off_its_middle_is_centred_all_the_same():
    """Two sessions of one program whose planes' offsets differ by less
    than the bracket's width read the same split."""
    a, b = _decode_trace(), _decode_trace(skew=0.0006)
    lo, hi, _n = idle.causality_bracket(b, idle.DECODE)
    assert lo < 0 < hi
    for name in ("decode_idle_wake_ms", "decode_idle_launch_ms"):
        assert _read(name, b) == pytest.approx(_read(name, a), abs=0.01)


def _paused_trace():
    """One chip, 200 ms: three chunks; the third's sync held a pause."""
    dev = tr.DeviceTrace("/device:TPU:0")
    dev.ops = [("%fusion.1 = f32[] fusion()", 0.002, 0.020),
               ("%fusion.1 = f32[] fusion()", 0.024, 0.042),
               ("%fusion.1 = f32[] fusion()", 0.046, 0.064)]
    spans = [("window", 0.0, 0.200), ("decode.generate", 0.0, 0.200)]
    for t in (0.0, 0.022, 0.044):
        spans += [("decode.dispatch", t, t + 0.002),
                  ("decode.upload", t, t + 0.0005),
                  ("decode.launch", t + 0.0005, t + 0.002)]
    spans += [("decode.sync", 0.002, 0.0205), ("decode.sync", 0.024, 0.0425),
              ("decode.sync", 0.046, 0.1245),        # 60 ms over
              ("decode.pause", 0.124502, 0.124503),
              ("decode.scatter", 0.1246, 0.1250)]
    return tr.TraceReduction([dev], spans)


def test_a_window_with_one_marker_reads_its_phases_excess():
    red = _paused_trace()
    # the chip ran 18 ms of the 78.5: idle under most of the pause
    assert idle.pauses(red, idle.DECODE) == [
        ("decode.sync", pytest.approx(0.0785), pytest.approx(0.060),
         pytest.approx(0.018 / 0.0785))]
    assert _read("decode_pause_share", red) == pytest.approx(30.0)
    # the engine's reader looks for its own marker and phases
    assert idle.pauses(red, idle.ENGINE) == []


def _engine_trace():
    """One chip, 100 ms: two 38 ms steps with a 4 ms gap between them,
    then 20 ms parked (``test_spans.py``'s, with the launch in two)."""
    dev = tr.DeviceTrace("/device:TPU:0")
    dev.ops = [("%fusion.1 = f32[] fusion()", 0.000, 0.038),
               ("%fusion.1 = f32[] fusion()", 0.042, 0.080)]
    dev.modules = [("jit_step(7)", 0.000, 0.038),
                   ("jit_step(7)", 0.042, 0.080)]
    spans = [("window", 0.0, 0.100), ("loadgen", 0.0, 0.100),
             ("engine.step", 0.0375, 0.0811),
             ("engine.gather", 0.0401, 0.0411),
             ("engine.dispatch", 0.0411, 0.0421),
             ("engine.upload", 0.0411, 0.0414),       # 0.3 ms
             ("engine.launch", 0.0414, 0.0421),       # 0.6 of 0.7 idle
             ("engine.device", 0.0421, 0.0806),       # 0.6 ms behind it
             ("engine.sync", 0.0806, 0.0808),         # 0.2 ms
             ("engine.delivery", 0.0808, 0.0811),
             ("engine.join", 0.0811, 0.0812),
             ("engine.park", 0.0812, 0.1312)]
    return tr.TraceReduction([dev], spans)


def test_the_engines_readers_on_made_up_spans():
    red = _engine_trace()
    assert _read("engine_idle_launch_ms", red) == pytest.approx(0.9)
    assert _read("engine_idle_wake_ms", red) == pytest.approx(0.8)
    assert _read("engine_upload_ms", red) == pytest.approx(0.3)
    assert _read("serve_pause_share", red) == 0.0
    lo, hi, rounds = idle.causality_bracket(red, idle.ENGINE)
    assert (lo, hi, rounds) == (pytest.approx(-0.0006),
                                pytest.approx(0.0006), 1)


@pytest.mark.parametrize("name", DECODE_READERS + ENGINE_READERS)
def test_a_reader_gives_nothing_for_a_program_without_the_launch_spans(
        name, capsys):
    """The parent names ``decode.dispatch`` and ``engine.dispatch`` but
    not their children: every new reader is silent there."""
    dev = tr.DeviceTrace("/device:TPU:0")
    dev.ops = [("%fusion.1 = f32[] fusion()", 0.0, 1.0)]
    parent = tr.TraceReduction([dev], [
        ("window", 0.0, 2.0), ("loadgen", 0.0, 2.0),
        ("decode.generate", 0.0, 2.0), ("decode.dispatch", 1.0, 1.002),
        ("decode.sync", 1.002, 1.5), ("engine.step", 1.0, 1.5),
        ("engine.dispatch", 1.0, 1.002), ("engine.device", 1.002, 1.5)])
    assert _read(name, parent) is None
    assert _read(name, None) is None
    assert capsys.readouterr().out == ""


def test_the_note_prints_the_bracket_the_phases_and_the_pauses(capsys):
    idle.note(_decode_trace(), idle.DECODE)
    idle.note(_paused_trace(), idle.DECODE)
    out = capsys.readouterr().out
    assert "planes decode: skew in [-1.0000, 1.0000] ms over 2 rounds" in out
    assert "holds 0, the distances may be latencies; host spans moved " \
        "to its middle, 0.0000 ms" in out
    assert "decode.launch 1.2500" in out and "decode.sync 1.0000" in out
    # what a next span would name: the tail behind the last emit, and
    # the head before the first dispatch
    assert "longest stretches under no phase: 43.800 ms between " \
        "decode.emit and -, 8.000 ms between - and decode.dispatch" in out
    assert "pause decode.sync 78.500 ms, 60.000 over the window's " \
        "median, the chips busy 22.9% of it" in out
    idle.note(_decode_trace(skew=0.003), idle.DECODE)
    out = capsys.readouterr().out
    assert "does not hold 0, skew for certain; host spans moved to its " \
        "middle, 3.0000 ms" in out
    assert "40.800 ms between decode.emit and -, 11.000 ms between - and " \
        "decode.dispatch" in out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny_root")))


@pytest.mark.parametrize("cell, metrics", [
    ("batch_tiny", DECODE_READERS), ("chat_tiny", ENGINE_READERS)])
def test_a_traced_run_reads_idle_time_by_phase(root, cell, metrics, capfd):
    doc = run_cell(workload=cell, seed=2 ** 31 + 11, seconds=1.0,
                   trace=True, t_start=time.perf_counter(), root=root,
                   require_tpu=False)
    assert doc["correct"] is True
    assert set(metrics) <= set(doc["metrics"])
    values = {m: doc["metrics"][m]["value"] for m in metrics}
    assert all(math.isfinite(v) and v >= 0 for v in values.values())
    upload, share = values[metrics[2]], values[metrics[3]]
    assert upload > 0 and share < 100
    layer = "decode" if cell == "batch_tiny" else "engine"
    assert f"chipbench: idle {layer}:" in capfd.readouterr().out
