"""Program spans (``defer_tpu.obs.span``): one pair of clock reads feeds
the phase's histogram, the process tracer and a profiler annotation,
and the decode ring, the decode engine and the front door name their
phases with it, from ``obs/profile.py``'s tables and nowhere else.

The last tests pin the three program names that the benchmark's trace
readers search for (``chipbench/metrics/*``): a rename fails here and
not in a metric.
"""

import glob
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from defer_tpu.models.gpt import gpt_tiny
from defer_tpu.obs import (DECODE_DISPATCH_PHASES, DECODE_PHASES,
                           DECODE_STATS_PHASES, DOOR_PHASES,
                           ENGINE_DISPATCH_PHASES, ENGINE_LOOP_PHASES,
                           ENGINE_PHASES, REGISTRY, SETUP_PHASES,
                           SPAN_LAYERS, pause_watcher, recorder, span,
                           tracer)
from defer_tpu.obs.events import validate_event
from defer_tpu.obs.profile import (PAUSE_MARKER, PAUSE_OVER_S,
                                   PAUSE_UNJUDGED, PAUSE_UNJUDGED_FIRST)
from defer_tpu.obs.trace import ANNOTATION_PREFIX as PREFIX
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve import ContinuousBatchEngine, ServeClient
from defer_tpu.serve.client import fetch_stats
from defer_tpu.serve.frontdoor import ServeFrontDoor

PLEN, NEW, CHUNK = 4, 9, 2


@pytest.fixture(scope="module")
def decoder():
    g = gpt_tiny(seq_len=32)
    dec = PipelinedDecoder(g, g.init(jax.random.key(0)), num_stages=2,
                           microbatch=1, max_len=32)
    prompts = np.random.default_rng(3).integers(
        0, 97, (2, PLEN)).astype(np.int32)
    # compile both programs once, outside every test's spans
    dec.generate(prompts, NEW, prefill=True, token_chunk=CHUNK,
                 on_tokens=lambda *a, **k: None)
    return dec, prompts


@pytest.fixture(scope="module")
def door():
    g = gpt_tiny(seq_len=48)
    engine = ContinuousBatchEngine(g, g.init(jax.random.key(0)),
                                   num_stages=2, width=3)
    d = ServeFrontDoor(engine=engine,
                       decode_defaults={"max_new_tokens": 4}).start()
    host, port = d.address
    # compile the step program once, outside every test's spans
    ServeClient(host, port, "warm", max_new_tokens=2).stream(
        [np.arange(3, dtype=np.int32)])
    yield d
    d.stop()


def _ends(s):
    return s["ts_us"] + s["dur_us"]


def _in_order(spans, slack_us=2):
    """Sorted by start, no span starts before the one before it ended."""
    spans = sorted(spans, key=lambda s: s["ts_us"])
    return all(b["ts_us"] >= _ends(a) - slack_us
               for a, b in zip(spans, spans[1:]))


# -- the primitive ------------------------------------------------------------

def test_a_span_with_everything_off_feeds_only_its_histogram():
    tr = tracer()
    assert not tr.enabled
    before, hist = len(tr.spans), REGISTRY.histogram("decode.scatter_s")
    n0 = hist.count
    with span("decode", "scatter") as sp:
        time.sleep(0.002)
    assert hist.count == n0 + 1 and len(tr.spans) == before
    assert sp.t1 - sp.t0 >= 0.002 and hist.max >= 0.002
    with pytest.raises(ZeroDivisionError):
        with span("decode", "scatter"):
            1 / 0
    assert hist.count == n0 + 2 and len(tr.spans) == before


def test_a_span_with_the_tracer_on_links_its_parent_and_keeps_args(traced):
    with pytest.raises(KeyError):
        with span("engine", "step", {"step": 7, "rows": 2}) as root:
            with span("engine", "gather"):
                pass
            with span("engine", "dispatch"):
                raise KeyError("boom")
    by = {s["name"]: s for s in traced.spans}
    assert set(by) == {"engine.step", "engine.gather", "engine.dispatch"}
    assert by["engine.step"]["args"] == {"step": 7, "rows": 2,
                                         "error": "KeyError"}
    assert by["engine.dispatch"]["args"] == {"error": "KeyError"}
    assert by["engine.gather"]["parent"] == root.span_id \
        == by["engine.dispatch"]["parent"]
    assert by["engine.step"]["parent"] is None
    # one pair of clock reads: the tracer's record is the pair the
    # caller can read back
    assert by["engine.step"]["dur_us"] == max(
        int((root.t1 - root.t0) * 1e6), 1)


@pytest.mark.parametrize("layer", sorted(SPAN_LAYERS))
def test_names_come_from_the_phase_tables_and_nowhere_else(layer):
    prefix, root, phases = SPAN_LAYERS[layer]
    assert phases == {
        "decode": DECODE_PHASES + DECODE_DISPATCH_PHASES
        + DECODE_STATS_PHASES,
        "engine": ENGINE_PHASES + ENGINE_DISPATCH_PHASES
        + ENGINE_LOOP_PHASES,
        "door": DOOR_PHASES, "setup": SETUP_PHASES}[layer]
    for phase in phases:
        hist = REGISTRY.histogram(f"{prefix}.{phase}_s")
        n0 = hist.count
        with span(layer, phase) as sp:
            pass
        assert sp.name == f"{layer}.{phase}" and hist.count == n0 + 1
    if root is not None:
        with span(layer, root) as sp:     # a root feeds no histogram
            pass
        assert sp.name == f"{layer}.{root}" and sp._hist is None
    with pytest.raises(KeyError):
        span(layer, "no_such_phase")
    with pytest.raises(KeyError):
        span("no_such_layer", "dispatch")


# -- the CPU clocks and the pause watch ----------------------------------------------

@pytest.mark.parametrize("busy", [False, True])
def test_a_span_knows_whether_its_thread_worked_or_waited(busy):
    with span("decode", "emit") as sp:
        t_end = time.perf_counter() + 0.05
        if busy:
            while time.perf_counter() < t_end:
                pass
        else:
            time.sleep(0.05)
    wall = sp.t1 - sp.t0
    assert wall >= 0.05
    if busy:    # worked: the thread's CPU clock ran with the wall clock
        assert sp.cpu_s >= 0.6 * wall and sp.proc_cpu_s >= sp.cpu_s - 1e-3
    else:       # waited
        assert sp.cpu_s <= 0.2 * wall
    # a reading up to 5 ms old serves as the span's first
    assert sp.cpu_s <= wall + 6e-3
    # a short phase reads no CPU clock at all: both ends share a reading
    with span("decode", "emit") as a:
        pass
    with span("decode", "emit") as b:
        pass
    assert a.cpu_s == b.cpu_s == 0.0


def _fresh_watch(layer, phase):
    """The phase's watch as a new process has it, and an unlimited
    stderr line."""
    pw = pause_watcher()
    w = pw.phase(layer, phase)
    if w is not None:
        w.typ, w._first, w._streak = None, [], 0
    pw._last_line = float("-inf")
    return pw, w


def _pauses(prefix):
    return (REGISTRY.counter(f"{prefix}.pauses").value,
            REGISTRY.histogram(f"{prefix}.pause_s").count,
            REGISTRY.histogram(f"{prefix}.pause_s").sum,
            len([e for e in recorder().snapshot()
                 if e["kind"] == "host_pause"]))


def _occur(layer, phase, seconds, args=None):
    with span(layer, phase, args) as sp:
        time.sleep(seconds)
    return sp


def test_no_occurrence_is_judged_before_the_phases_eighth(capfd):
    _pw, w = _fresh_watch("decode", "scatter")
    before = _pauses("decode")
    for i in range(PAUSE_UNJUDGED_FIRST):
        # the second is as long as a pause: still finding what is typical
        _occur("decode", "scatter", 0.03 if i == 1 else 0.001)
        assert (w.typ is None) == (i < PAUSE_UNJUDGED_FIRST - 1)
    assert _pauses("decode") == before
    assert 0.001 <= w.typ < 0.003      # the median left the long one out
    assert "host_pause" not in capfd.readouterr().err


def test_a_pause_fires_once_with_every_field_the_platform_has(capfd):
    pw, w = _fresh_watch("decode", "sync")
    with span("decode", "generate", {"rows": 1}):      # the baseline
        # what is typical, and the two occurrences that are no pause,
        # are TOLD to the watch: slept, a loaded machine makes 9 ms into
        # 13 and a pause of them.  Only the pause itself is slept
        for i in range(PAUSE_UNJUDGED_FIRST + 2):
            with span("decode", "dispatch", {"steps_run": 4 * i}):
                pass
            w.feed(None, 0.002)
        typ, before = w.typ, _pauses("decode")
        assert typ == pytest.approx(0.002)
        # over 10 ms but under 3x, and 3x but under 10 ms over: no pause
        w.typ = 0.02
        w.feed(None, 0.035)
        w.typ = typ
        w.feed(None, 0.009)
        assert _pauses("decode") == before
        typ = w.typ
        with span("decode", "dispatch", {"steps_run": 444}):
            pass
        # the thread's last CPU reading is over 5 ms old by then, so the
        # span takes its own and reads none of the work above
        time.sleep(0.006)
        sp = _occur("decode", "sync", 0.04)
    n, hn, hsum, ne = _pauses("decode")
    assert (n, hn, ne) == (before[0] + 1, before[1] + 1, before[3] + 1)
    wall = sp.t1 - sp.t0
    assert hsum - before[2] == pytest.approx(wall - typ, abs=1e-6)
    ev = validate_event([e for e in recorder().snapshot()
                         if e["kind"] == "host_pause"][-1])
    d = ev["data"]
    assert (d["layer"], d["phase"], d["round"]) == ("decode", "sync", 444)
    assert d["wall_ms"] == pytest.approx(1e3 * wall, abs=1e-3)
    assert d["typical_ms"] == pytest.approx(1e3 * typ, abs=1e-3)
    assert d["cpu_ms"] <= 0.2 * d["wall_ms"] and d["proc_cpu_ms"] >= 0
    assert d["since_ms"] >= d["wall_ms"]
    # the thread slept: it gave the core up of its own accord, at least
    # once since the generation began
    assert d["vol_switches"] >= 1
    assert d["invol_switches"] >= 0 and d["major_faults"] >= 0
    assert d["gc_collections"] >= 0
    for key, path in (("runq_wait_ms", "/proc/thread-self/schedstat"),
                      ("steal_ms", "/proc/stat")):
        if key in d:        # a field the platform lacks is absent
            assert d[key] >= 0 and os.path.exists(path)
    line = [ln for ln in capfd.readouterr().err.splitlines()
            if "host_pause" in ln]
    assert len(line) == 1
    assert "phase=sync" in line[0] and "round=444" in line[0] \
        and f"wall_ms={d['wall_ms']}" in line[0]
    # the layer's totals ride the line: a thinned log still adds up
    assert f"pauses={n} pause_s_sum={hsum:.6f}" in line[0]
    # the estimate took the pause for 3x typical at most
    assert w.typ == pytest.approx(1.25 * typ)


@pytest.mark.parametrize("layer, phase", [
    ("engine", "park"), ("engine", "dispatch"), ("decode", "dispatch"),
    ("decode", "generate"), ("engine", "step")])
def test_park_the_tiled_parents_and_the_roots_are_never_judged(layer, phase):
    pw, w = _fresh_watch(layer, phase)
    assert w is None
    assert phase == SPAN_LAYERS[layer][1] or phase in PAUSE_UNJUDGED[layer]
    before = _pauses(SPAN_LAYERS[layer][0])
    for i in range(PAUSE_UNJUDGED_FIRST + 2):
        _occur(layer, phase, 0.0005)
    _occur(layer, phase, 0.03)
    assert _pauses(SPAN_LAYERS[layer][0]) == before


def test_the_warm_ups_compiling_first_dispatch_is_no_pause():
    _fresh_watch("decode", "launch")
    _fresh_watch("decode", "prefill")
    seen = recorder().cursor()
    g = gpt_tiny(seq_len=32)
    dec = PipelinedDecoder(g, g.init(jax.random.key(1)), num_stages=1,
                           microbatch=2, max_len=32)
    prompts = np.zeros((2, PLEN), np.int32)
    # both programs compile inside their phases' first occurrences
    dec.generate(prompts, 2 * CHUNK + 1, prefill=True, token_chunk=CHUNK,
                 on_tokens=lambda *a, **k: None)
    assert REGISTRY.histogram("decode.launch_s").max > 10 * PAUSE_OVER_S
    assert not [e for e in recorder().events_since(seen)[1]
                if e["kind"] == "host_pause"
                and e["data"]["phase"] in ("launch", "prefill")]
    w = pause_watcher().phase("decode", "launch")
    for _ in range(3):
        dec.generate(prompts, 2 * CHUNK + 1, prefill=True,
                     token_chunk=CHUNK, on_tokens=lambda *a, **k: None)
    # what is typical is a dispatch, not the compile
    assert w.typ is not None and w.typ < PAUSE_OVER_S


def test_the_stderr_line_is_rate_limited_and_the_event_is_not(capfd):
    pw, w = _fresh_watch("engine", "delivery")
    for _ in range(PAUSE_UNJUDGED_FIRST):
        _occur("engine", "delivery", 0.001)
    before = _pauses("serve.decode")
    for _ in range(3):
        _occur("engine", "delivery", 0.03)
        w.typ, w._streak = 0.001, 0
    assert _pauses("serve.decode")[0] == before[0] + 3
    assert _pauses("serve.decode")[3] == before[3] + 3
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if "host_pause" in ln]
    assert len(lines) == 1 and "layer=engine" in lines[0]
    pw._last_line -= 1.0               # a second later the next is said
    w._streak = 0
    _occur("engine", "delivery", 0.03)
    assert len([ln for ln in capfd.readouterr().err.splitlines()
                if "host_pause" in ln]) == 1


def test_a_phase_that_became_longer_is_typical_after_a_few_occurrences():
    _pw, w = _fresh_watch("decode", "scatter")
    for _ in range(PAUSE_UNJUDGED_FIRST):
        _occur("decode", "scatter", 0.001)
    before = _pauses("decode")[0]
    for _ in range(3 + PAUSE_UNJUDGED_FIRST):  # say, a longer token_chunk
        _occur("decode", "scatter", 0.02)
    # two were taken for pauses; the third in a row began the estimate anew
    assert _pauses("decode")[0] == before + 2
    assert 0.02 <= w.typ < 0.03
    _occur("decode", "scatter", 0.02)
    assert _pauses("decode")[0] == before + 2


def test_the_baseline_is_the_generations_begin_or_the_last_park():
    pw = pause_watcher()
    bases = pw._bases()
    bases.clear()
    with span("decode", "generate"):
        t_gen = bases["decode"][0]             # a generation begins
        assert "engine" not in bases
    assert bases["decode"][0] == t_gen
    with span("engine", "park"):
        assert "engine" not in bases
    t_left = bases["engine"][0]                # the engine left park
    with span("engine", "park") as sp:
        pass
    assert bases["engine"][0] >= sp.t1 > t_left
    with span("engine", "join"):
        pass
    with span("engine", "step", {"step": 3, "rows": 1}):
        pass
    left = bases["engine"][0]
    _fresh_watch("engine", "join")
    for _ in range(PAUSE_UNJUDGED_FIRST):
        _occur("engine", "join", 0.001)
    assert bases["engine"][0] == left          # nothing is read per round
    _occur("engine", "join", 0.03)
    ev = [e for e in recorder().snapshot() if e["kind"] == "host_pause"][-1]
    assert ev["data"]["round"] == 3 and ev["data"]["phase"] == "join"
    assert bases["engine"][0] > left           # taken anew after a pause
    # a thread without a baseline says what the span alone knows
    bases.clear()
    _occur("engine", "join", 0.03)
    ev = [e for e in recorder().snapshot() if e["kind"] == "host_pause"][-1]
    assert "since_ms" not in ev["data"] and "vol_switches" not in ev["data"]
    assert ev["data"]["wall_ms"] >= 30


def test_a_pause_leaves_a_marker_behind_its_phase_in_a_profiler_trace(
        tmp_path):
    from chipbench import trace as cb_trace
    _pw, w = _fresh_watch("decode", "emit")
    for _ in range(PAUSE_UNJUDGED_FIRST):
        _occur("decode", "emit", 0.001)
    _occur("decode", "emit", 0.03)     # no session: the marker is inert
    w.typ = 0.001
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _occur("decode", "emit", 0.001)
        # what is typical is TOLD to the watch: on a loaded machine the
        # 1 ms sleep above takes over 3, the third long one in a row,
        # and the next would be a phase grown longer, not a pause
        w.typ, w._streak = 0.001, 0
        sp = _occur("decode", "emit", 0.03)
    finally:
        jax.profiler.stop_trace()
    assert PREFIX + "decode." + PAUSE_MARKER in _host_event_names(
        str(tmp_path))
    # as the benchmark's reduction reads it: one marker, right behind
    # the end of the occurrence that was the pause
    red = cb_trace.load(cb_trace.find_xplane(str(tmp_path)),
                        host_ops_as_device=True)
    marks = [(s, e) for n, s, e in red.spans if n == "decode.pause"]
    emits = [(s, e) for n, s, e in red.spans if n == "decode.emit"]
    assert len(marks) == 1 and len(emits) == 2
    long = max(emits, key=lambda se: se[1] - se[0])
    assert long[1] - long[0] == pytest.approx(sp.t1 - sp.t0, abs=2e-4)
    assert 0 <= marks[0][0] - long[1] < 2e-3


# -- the decode ring ------------------------------------------------------------

def _decode_spans(tr):
    spans = [s for s in tr.spans if s["name"].startswith("decode.")]
    roots = [s for s in spans if s["name"] == "decode.generate"]
    assert len(roots) == 1
    inner = {f"decode.{p}" for p in DECODE_DISPATCH_PHASES}
    kids = [s for s in spans if s is not roots[0]
            and s["name"] not in inner]
    assert all(s["parent"] == roots[0]["span"] for s in kids)
    assert {s["name"].split(".", 1)[1] for s in kids} <= set(DECODE_PHASES)
    assert _in_order(kids)
    # the launch's two phases lie inside their dispatch, in order, once
    for d in (s for s in kids if s["name"] == "decode.dispatch"):
        two = sorted((s for s in spans if s["parent"] == d["span"]),
                     key=lambda s: s["ts_us"])
        assert [s["name"] for s in two] \
            == [f"decode.{p}" for p in DECODE_DISPATCH_PHASES]
        assert _in_order(two) and d["ts_us"] <= two[0]["ts_us"]
        assert _ends(d) >= _ends(two[-1]) - 2
    assert len([s for s in spans if s["name"] in inner]) \
        == 2 * len([s for s in kids if s["name"] == "decode.dispatch"])
    assert roots[0]["ts_us"] <= min(s["ts_us"] for s in kids)
    assert _ends(roots[0]) >= max(_ends(s) for s in kids) - 2
    return roots[0], sorted(kids, key=lambda s: s["ts_us"])


def test_generate_names_its_phases_once_a_chunk_in_order(decoder, traced):
    dec, prompts = decoder
    calls = []
    out = dec.generate(prompts, NEW, prefill=True, token_chunk=CHUNK,
                       on_tokens=lambda lo, hi, t, rows: calls.append(
                           (lo, hi)))
    assert out.shape == (2, PLEN + NEW)
    root, kids = _decode_spans(traced)
    names = [s["name"].split(".", 1)[1] for s in kids]
    num_steps, chunk_steps = dec._schedule(PLEN + NEW, PLEN, CHUNK)
    chunks = -(-num_steps // chunk_steps)
    assert root["args"] == {"rows": 2, "prompt_len": PLEN,
                            "new_tokens": NEW, "chunk_steps": chunk_steps}
    # what a generation sets up, in two parts around the one prefill
    assert names.count("prefill") == 1 and names.count("init") == 2
    assert names[:3] == ["init", "prefill", "init"]
    assert [names.count(p) for p in ("dispatch", "sync", "scatter")] \
        == [chunks] * 3
    assert names.count("emit") == len(calls) >= chunks
    assert names[3] == "emit"            # the prefill's own first token
    body = names[4:]
    # a round: the next chunk's dispatch, then the sync and the scatter
    # of the one before it, then emit where tokens came.  The same
    # counts as ever: the first round only launches, the last only reads
    assert [p for p in body if p != "emit"] == ["dispatch"] \
        + ["dispatch", "sync", "scatter"] * (chunks - 1) \
        + ["sync", "scatter"]
    assert all(body[i - 1] == "scatter"
               for i, p in enumerate(body) if p == "emit")
    assert [s["args"]["steps_run"] for s in kids
            if s["name"] == "decode.dispatch"] \
        == [i * chunk_steps for i in range(chunks)]
    # every wait but the last had a later chunk launched behind it
    assert [s["args"] for s in kids if s["name"] == "decode.sync"] \
        == [{"ahead": 1}] * (chunks - 1) + [{"ahead": 0}]


def test_an_exception_in_on_tokens_passes_through_emit(decoder, traced):
    dec, prompts = decoder

    class Stop(Exception):
        pass

    def cb(lo, hi, toks, rows):
        if hi > PLEN + 3:
            raise Stop

    with pytest.raises(Stop):
        dec.generate(prompts, NEW, prefill=True, token_chunk=CHUNK,
                     on_tokens=cb)
    root, kids = _decode_spans(traced)
    assert root["args"]["error"] == "Stop"
    assert kids[-1]["name"] == "decode.emit"
    assert kids[-1]["args"] == {"error": "Stop"}
    assert all("error" not in s["args"] for s in kids[:-1])
    # the chunk launched ahead of the stop was never waited for
    names = [s["name"] for s in kids]
    assert names.count("decode.dispatch") == names.count("decode.sync") + 1
    # the thread's span stack is clean again: the next span is a root
    with span("decode", "scatter") as sp:
        pass
    assert [s for s in traced.spans if s["span"] == sp.span_id][0][
        "parent"] is None


def test_generate_without_a_callback_syncs_after_the_last_dispatch(
        decoder, traced):
    dec, prompts = decoder
    n0 = REGISTRY.histogram("decode.dispatch_s").count
    u0 = [REGISTRY.histogram(f"decode.{p}_s").count
          for p in ("launch", "upload")]
    dec.generate(prompts, NEW, token_chunk=CHUNK)
    _root, kids = _decode_spans(traced)
    names = [s["name"].split(".", 1)[1] for s in kids]
    num_steps, chunk_steps = dec._schedule(PLEN + NEW, 0, CHUNK)
    chunks = -(-num_steps // chunk_steps)
    assert names == ["init"] * 2 + ["dispatch"] * chunks \
        + ["sync", "scatter"] * chunks
    # the histogram's count is the dispatch count (the old counter): the
    # children feed their own and leave the parent's as it was
    assert REGISTRY.histogram("decode.dispatch_s").count == n0 + chunks
    assert REGISTRY.histogram("decode.launch_s").count == u0[0] + chunks
    assert REGISTRY.histogram("decode.upload_s").count == u0[1] + chunks


def test_many_rounds_open_a_generate_span_each(decoder, traced):
    dec, prompts = decoder
    both = np.concatenate([prompts, prompts])
    dec.generate(both, NEW, prefill=True, token_chunk=CHUNK,
                 on_tokens=lambda *a, **k: None)
    roots = [s for s in traced.spans if s["name"] == "decode.generate"]
    assert len(roots) == 2 and _in_order(roots)
    assert all(s["parent"] is None and s["args"]["rows"] == 2
               for s in roots)


# -- the decode engine and the front door -----------------------------------------

def test_the_engine_loop_names_steps_joins_and_parks(door, traced):
    host, port = door.address
    time.sleep(0.12)                     # idle: the loop parks
    prompts = [np.arange(2, 6, dtype=np.int32),
               np.arange(5, 8, dtype=np.int32)]
    steps0 = door.engine.steps
    out = ServeClient(host, port, "t", max_new_tokens=3).stream(prompts)
    assert [o[0] for o in out] == ["ok", "ok"]
    deadline = time.monotonic() + 10
    while door.engine.active():          # the loop ends its last step
        assert time.monotonic() < deadline
        time.sleep(0.01)
    time.sleep(0.12)
    steps = door.engine.steps - steps0
    spans = [s for s in traced.spans if s["name"].startswith("engine.")]
    roots = sorted((s for s in spans if s["name"] == "engine.step"),
                   key=lambda s: s["ts_us"])
    assert len(roots) == steps > 0
    assert [s["args"]["step"] for s in roots] \
        == list(range(steps0, steps0 + steps))
    assert all(1 <= s["args"]["rows"] <= 3 for s in roots)
    # one root a step, around the call that launches it.  That call goes
    # on to read the step before — launch(n+1), then device(n), sync(n),
    # delivery(n): all five phases under one root, in the table's order
    # — unless nothing was in flight (a busy period's first launch)
    launch_only = 0
    for root in roots:
        kids = sorted((s for s in spans if s["parent"] == root["span"]),
                      key=lambda s: s["ts_us"])
        names = [s["name"] for s in kids]
        assert names in ([f"engine.{p}" for p in ENGINE_PHASES],
                         [f"engine.{p}" for p in ENGINE_PHASES[:2]])
        launch_only += len(names) == 2
        assert _in_order(kids)
        assert root["ts_us"] <= kids[0]["ts_us"]
        assert _ends(root) >= _ends(kids[-1]) - 2
        if len(names) == 5:     # the step launched first runs meanwhile
            assert kids[3]["args"] == {"ahead": 1}
        disp = kids[ENGINE_PHASES.index("dispatch")]
        two = sorted((s for s in spans if s["parent"] == disp["span"]),
                     key=lambda s: s["ts_us"])
        assert [s["name"] for s in two] \
            == [f"engine.{p}" for p in ENGINE_DISPATCH_PHASES]
        assert _in_order(two) and disp["ts_us"] <= two[0]["ts_us"]
        assert _ends(disp) >= _ends(two[-1]) - 2
    loop = sorted((s for s in spans if s["parent"] is None),
                  key=lambda s: s["ts_us"])
    assert _in_order(loop)
    # a busy period's last call launches nothing and has no root: its
    # three phases read the last step, with nothing running behind it
    tail = ["engine.device", "engine.sync", "engine.delivery"]
    assert {s["name"] for s in loop} \
        == {"engine.step", "engine.join", "engine.park", "engine.prefill",
            *tail}
    names = [s["name"] for s in loop]
    drains = [i for i, nm in enumerate(names) if nm == tail[0]]
    assert len(drains) == launch_only > 0
    assert all(names[i:i + 3] == tail
               and loop[i + 1]["args"] == {"ahead": 0} for i in drains)
    # every phase once a step, wherever its call put it
    for phase in ENGINE_PHASES:
        assert len([s for s in spans
                    if s["name"] == f"engine.{phase}"]) == steps
    # a join sweep before every call, a joined slot's prefill between
    # them: a sibling of both; parks only with nothing active
    between = [nm for nm in names if nm != "engine.prefill"]
    assert all(between[i - 1] == "engine.join"
               for i, nm in enumerate(between)
               if nm in ("engine.step", tail[0]))
    assert all(names[i - 1] in ("engine.join", "engine.prefill")
               and "engine.step" in names[i:]
               for i, nm in enumerate(names) if nm == "engine.prefill")
    # one call a request, its prompt but for the last token
    fills = [s for s in loop if s["name"] == "engine.prefill"]
    assert sorted(s["args"]["positions"] for s in fills) == [2, 3]
    assert names[-1] in ("engine.park", "engine.join")
    parks = [s for s in loop if s["name"] == "engine.park"]
    assert any(s["dur_us"] >= 40_000 for s in parks)    # a whole timeout
    # the phase histograms are what the spans fed
    for phase in ENGINE_PHASES + ENGINE_DISPATCH_PHASES \
            + ENGINE_LOOP_PHASES:
        assert REGISTRY.histogram(f"serve.decode.{phase}_s").count \
            >= (len(fills) if phase == "prefill" else steps)


def test_the_door_admits_each_request_under_a_span_with_its_rid(
        door, traced):
    host, port = door.address
    n0 = REGISTRY.histogram("serve.door.admit_s").count
    prompts = [np.arange(1, 4, dtype=np.int32) + i for i in range(3)]
    out = ServeClient(host, port, "acme", max_new_tokens=2).stream(prompts)
    assert [o[0] for o in out] == ["ok"] * 3
    admits = [s for s in traced.spans if s["name"] == "door.admit"]
    assert len(admits) == 3
    assert all(s["args"]["tenant"] == "acme" and s["parent"] is None
               for s in admits)
    rids = [s["args"]["rid"] for s in admits]
    assert len(set(rids)) == 3 and rids == sorted(rids)
    assert REGISTRY.histogram("serve.door.admit_s").count == n0 + 3
    stats = fetch_stats(host, port)["decode"]
    for key in ("step_s", "join_s", "park_s", "prefill_s"):
        assert stats[key]["count"] > 0


# -- the profiler's clock -----------------------------------------------------------

def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1
    names = set()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names |= {ev.name for ev in line.events}
    return names


def test_a_profiler_session_records_the_spans_as_host_events(
        decoder, door, tmp_path):
    dec, prompts = decoder
    host, port = door.address
    assert not tracer().enabled          # the session alone turns it on
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    # before the session: the same code paths leave nothing behind
    with span("decode", "scatter"):
        pass
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        dec.generate(prompts, NEW, prefill=True, token_chunk=CHUNK,
                     on_tokens=lambda *a, **k: None)
        ServeClient(host, port, "p", max_new_tokens=2).stream(
            [np.arange(3, dtype=np.int32)])
        time.sleep(0.06)
    finally:
        jax.profiler.stop_trace()
    with span("engine", "join"):         # after it: nothing either
        pass
    named = {n for n in _host_event_names(str(tmp_path))
             if n.startswith(PREFIX)}
    want = {f"{PREFIX}decode.{p}" for p in
            DECODE_PHASES + DECODE_DISPATCH_PHASES + ("generate",)} \
        | {f"{PREFIX}engine.{p}" for p in
           ENGINE_PHASES + ENGINE_DISPATCH_PHASES + ENGINE_LOOP_PHASES
           + ("step",)} \
        | {PREFIX + "door.admit"} \
        | {PREFIX + "setup.state"}      # a generation's zero state
    # a host that stalled meanwhile may have left a pause's marker too
    assert named - {f"{PREFIX}{layer}.{PAUSE_MARKER}"
                    for layer in SPAN_LAYERS} == want
    # every name in the trace is spelled in the tables
    table = {f"{PREFIX}{layer}.{p}"
             for layer, (_pre, root, phases) in SPAN_LAYERS.items()
             for p in phases + (PAUSE_MARKER,) + ((root,) if root else ())}
    assert named <= table
    # the namespace is the one the benchmark's reduction collects
    from chipbench.trace import SPAN_PREFIX
    assert PREFIX == SPAN_PREFIX


def test_spans_outside_a_session_leave_no_events(tmp_path):
    """A session during which no decode phase ran holds no
    ``chipbench:decode.`` event: only spans that ran inside it are written
    (the module's door may be parking in the background meanwhile)."""
    with span("decode", "emit"):
        pass
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("marker"):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    with span("decode", "emit"):
        pass
    names = _host_event_names(str(tmp_path))
    assert "marker" in names
    assert not {n for n in names if n.startswith(PREFIX + "decode.")}


# -- the names the benchmark's readers search for -----------------------------------

def _module_name(lowered) -> str:
    text = lowered.as_text()
    return text[text.index("module @") + len("module @"):].split()[0]


@pytest.mark.parametrize("program, want", [
    ("decode", "jit_device_decode"), ("prefill", "jit_device_prefill")])
def test_the_decode_ring_programs_keep_their_names(decoder, program, want):
    dec, prompts = decoder
    n, mb = dec.num_stages, dec.microbatch
    prompt = jnp.asarray(prompts.reshape(n, mb, PLEN))
    a, caches = dec._init_state()
    seed, temp = jnp.uint32(0), jnp.float32(0.0)
    if program == "prefill":
        lowered = dec._build_prefill_fn(PLEN, False, None).lower(
            dec._w, prompt, seed, temp, caches)
    else:
        num_steps, chunk_steps = dec._schedule(PLEN + NEW, PLEN, CHUNK)
        lowered = dec._get_decode_fn(chunk_steps, False, None).lower(
            dec._w, prompt, jnp.int32(PLEN), jnp.int32(0),
            jnp.int32(num_steps), seed, temp, jnp.zeros((n, mb), jnp.int32),
            jnp.int32(PLEN), jnp.int32(PLEN), a, caches)
    assert _module_name(lowered) == want


def test_the_engine_step_program_keeps_its_name(door):
    eng = door.engine
    lowered = eng._step_fn(False).lower(
        eng.params, eng._caches, eng._prev_ids, *eng._blank_rows())
    assert _module_name(lowered) == "jit_step"
