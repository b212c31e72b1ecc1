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
from defer_tpu.obs import (DECODE_PHASES, DECODE_STATS_PHASES, DOOR_PHASES,
                           ENGINE_LOOP_PHASES, ENGINE_PHASES, REGISTRY, SPAN_LAYERS, span,
                           tracer)
from defer_tpu.obs.trace import ANNOTATION_PREFIX as PREFIX
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve import ContinuousBatchEngine, ServeClient
from defer_tpu.serve.client import fetch_stats
from defer_tpu.serve.frontdoor import ServeFrontDoor

PLEN, NEW, CHUNK = 4, 9, 2


@pytest.fixture
def traced():
    """The process tracer, on and empty for one test."""
    tr = tracer()
    was = tr.enabled
    tr.clear()
    tr.enabled = True
    yield tr
    tr.enabled = was
    tr.clear()


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
    assert phases == {"decode": DECODE_PHASES + DECODE_STATS_PHASES,
                      "engine": ENGINE_PHASES + ENGINE_LOOP_PHASES,
                      "door": DOOR_PHASES}[layer]
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


# -- the decode ring ------------------------------------------------------------

def _decode_spans(tr):
    spans = [s for s in tr.spans if s["name"].startswith("decode.")]
    roots = [s for s in spans if s["name"] == "decode.generate"]
    assert len(roots) == 1
    kids = [s for s in spans if s is not roots[0]]
    assert all(s["parent"] == roots[0]["span"] for s in kids)
    assert {s["name"].split(".", 1)[1] for s in kids} <= set(DECODE_PHASES)
    assert _in_order(kids)
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
    assert names.count("prefill") == 1 and names[0] == "prefill"
    assert [names.count(p) for p in ("dispatch", "sync", "scatter")] \
        == [chunks] * 3
    assert names.count("emit") == len(calls) >= chunks
    assert names[1] == "emit"            # the prefill's own first token
    body = names[2:]
    # each chunk: dispatch, sync, scatter, then emit where tokens came
    assert [p for p in body if p != "emit"] \
        == ["dispatch", "sync", "scatter"] * chunks
    assert all(body[i - 1] == "scatter"
               for i, p in enumerate(body) if p == "emit")
    assert [s["args"]["steps_run"] for s in kids
            if s["name"] == "decode.dispatch"] \
        == [i * chunk_steps for i in range(chunks)]


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
    # the thread's span stack is clean again: the next span is a root
    with span("decode", "scatter") as sp:
        pass
    assert [s for s in traced.spans if s["span"] == sp.span_id][0][
        "parent"] is None


def test_generate_without_a_callback_syncs_after_the_last_dispatch(
        decoder, traced):
    dec, prompts = decoder
    n0 = REGISTRY.histogram("decode.dispatch_s").count
    dec.generate(prompts, NEW, token_chunk=CHUNK)
    _root, kids = _decode_spans(traced)
    names = [s["name"].split(".", 1)[1] for s in kids]
    num_steps, chunk_steps = dec._schedule(PLEN + NEW, 0, CHUNK)
    chunks = -(-num_steps // chunk_steps)
    assert names == ["dispatch"] * chunks + ["sync", "scatter"] * chunks
    # the histogram's count is the dispatch count (the old counter)
    assert REGISTRY.histogram("decode.dispatch_s").count == n0 + chunks


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
    for root in roots:
        kids = sorted((s for s in spans if s["parent"] == root["span"]),
                      key=lambda s: s["ts_us"])
        assert [s["name"] for s in kids] \
            == [f"engine.{p}" for p in ENGINE_PHASES]
        assert _in_order(kids)
        assert root["ts_us"] <= kids[0]["ts_us"]
        assert _ends(root) >= _ends(kids[-1]) - 2
    loop = sorted((s for s in spans if s["parent"] is None),
                  key=lambda s: s["ts_us"])
    assert _in_order(loop)
    assert {s["name"] for s in loop} \
        == {"engine.step", "engine.join", "engine.park"}
    names = [s["name"] for s in loop]
    # a join sweep before every step; parks only with nothing active
    assert all(names[i - 1] == "engine.join"
               for i, nm in enumerate(names) if nm == "engine.step")
    assert names[-1] in ("engine.park", "engine.join")
    parks = [s for s in loop if s["name"] == "engine.park"]
    assert any(s["dur_us"] >= 40_000 for s in parks)    # a whole timeout
    # the phase histograms are what the spans fed
    for phase in ENGINE_PHASES + ENGINE_LOOP_PHASES:
        assert REGISTRY.histogram(
            f"serve.decode.{phase}_s").count >= steps


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
    for key in ("step_s", "join_s", "park_s"):
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
    want = {f"{PREFIX}decode.{p}" for p in DECODE_PHASES + ("generate",)} \
        | {f"{PREFIX}engine.{p}" for p in
           ENGINE_PHASES + ENGINE_LOOP_PHASES + ("step",)} \
        | {PREFIX + "door.admit"}
    assert named == want
    # every name in the trace is spelled in the tables
    table = {f"{PREFIX}{layer}.{p}"
             for layer, (_pre, root, phases) in SPAN_LAYERS.items()
             for p in phases + ((root,) if root else ())}
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
    w = eng.width
    lowered = eng._step_fn(False).lower(
        eng.params, eng._caches, jnp.zeros(w, jnp.int32),
        jnp.zeros(w, jnp.int32), jnp.zeros(w, jnp.uint32),
        jnp.zeros(w, jnp.float32))
    assert _module_name(lowered) == "jit_step"
