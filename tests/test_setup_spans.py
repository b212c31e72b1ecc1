"""Set-up by span (``obs/profile.py``): the ``setup`` layer of
``defer_tpu.obs.span``, the compile listener's kinds of time and table
of programs, and ``setup_breakdown``, whose parts sum to the elapsed
time and which closes when the first generation (the engine's first
busy period) is over.
"""

import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from defer_tpu.models.gpt import gpt_tiny
from defer_tpu.obs import (REGISTRY, SETUP_PHASES, SPAN_LAYERS,
                           enable_tracing, pause_watcher, recompile_watcher,
                           recorder, setup_breakdown, setup_log, span,
                           tracer)
from defer_tpu.obs.profile import (JAX_KINDS, PROGRAM_TABLE_SIZE,
                                   SETUP_KINDS, RecompileWatcher,
                                   _costliest_rows)
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine, DecodeRequest

PLEN, NEW, CHUNK = 4, 7, 2
PARTS = [kind + "_s" for kind in SETUP_KINDS] + ["unnamed_s"]


@pytest.fixture
def fresh():
    """An open, empty set-up log and the tracer on, as a process has
    them when it starts (with ``enable_tracing()``)."""
    setup_log().clear()
    tracer().clear()
    was = tracer().enabled
    enable_tracing()
    yield
    tracer().enabled = was
    tracer().clear()
    setup_log().clear()


@pytest.fixture(scope="module")
def model():
    g = gpt_tiny(seq_len=32)
    return g, g.init(jax.random.key(0))


def _prompts(rows=2):
    return np.random.default_rng(3).integers(
        0, 97, (rows, PLEN)).astype(np.int32)


def _decoder(model, **kw):
    g, params = model
    return PipelinedDecoder(g, params, num_stages=2, microbatch=1,
                            max_len=32, **kw)


def _setup_spans(phase=None):
    return [s for s in tracer().spans if s["name"].startswith("setup.")
            and phase in (None, s["name"].split(".")[1])]


def _events(kind):
    return [e for e in recorder().snapshot() if e["kind"] == kind]


def _sum_parts(doc):
    return sum(doc[key] for key in PARTS)


# -- the spans ----------------------------------------------------------------

def test_the_setup_layer_is_spelled_in_the_tables_and_nowhere_else():
    assert SPAN_LAYERS["setup"] == ("setup", None, SETUP_PHASES)
    assert SETUP_PHASES == ("import", "place", "relay", "state",
                            "first_call")
    with pytest.raises(KeyError, match="setup.compile"):
        span("setup", "compile")


def test_importing_the_package_left_an_import_span():
    h = REGISTRY.histogram("setup.import_s")
    assert h.count >= 1 and 0 < h.sum < 600


def test_a_build_and_one_generation_leave_their_setup_spans(fresh, model):
    dec = _decoder(model)
    dec.generate(_prompts(), NEW, prefill=True, token_chunk=CHUNK)
    places = _setup_spans("place")
    assert len(places) == 1
    leaves = jax.tree.leaves(dec._w)
    assert places[0]["args"] == {
        "leaves": len(leaves), "bytes": sum(a.nbytes for a in leaves)}
    assert len(_setup_spans("state")) == 1
    # a first call a compiled program, by the program's name
    assert sorted(s["args"]["program"] for s in _setup_spans("first_call")) \
        == ["device_decode", "device_prefill"]
    # as many re-laid leaves as the gauge says: none on the CPU
    relaid = REGISTRY.gauge("decode.weights.relaid_leaves").value
    assert len(_setup_spans("relay")) == relaid == 0


def test_a_relaid_leaf_is_a_relay_span_inside_place(fresh, model,
                                                    monkeypatch):
    """A format the test names — every matrix with its last two
    dimensions exchanged — makes the ring re-lay those leaves."""
    row_major = PipelinedDecoder._leaf_format

    def exchanged(self, ndim):
        fmt = row_major(self, ndim)
        if ndim != 3:
            return fmt
        return Format(Layout((0, 2, 1)), fmt.sharding)

    monkeypatch.setattr(PipelinedDecoder, "_leaf_format", exchanged)
    dec = _decoder(model)
    relaid = REGISTRY.gauge("decode.weights.relaid_leaves").value
    relays = _setup_spans("relay")
    assert len(relays) == relaid == sum(
        a.ndim == 3 for a in jax.tree.leaves(dec._w)) > 0
    place, = _setup_spans("place")
    assert {s["parent"] for s in relays} == {place["span"]}
    # the re-laying program's compile is compile, and not also relay
    doc = setup_breakdown()
    assert doc["compile_s"] > 0 and doc["relay_s"] > 0
    assert REGISTRY.histogram("setup.relay_s").sum \
        > doc["relay_s"]            # the histogram stays inclusive


def test_a_second_generation_adds_no_first_call(fresh, model):
    dec = _decoder(model)
    dec.generate(_prompts(), NEW, prefill=True, token_chunk=CHUNK)
    first = len(_setup_spans("first_call"))
    table = recompile_watcher().programs()
    dec.generate(_prompts(), NEW, prefill=True, token_chunk=CHUNK)
    assert len(_setup_spans("first_call")) == first == 2
    assert recompile_watcher().programs() == table
    assert len(_setup_spans("state")) == 2      # once a generation


def test_the_engines_build_leaves_place_state_and_its_programs(fresh, model):
    g, params = model
    # (a listener of the test's own: the process's table names 64 programs)
    names = RecompileWatcher().install()
    eng = ContinuousBatchEngine(g, params, num_stages=1, width=3)
    place, = _setup_spans("place")
    assert place["args"]["leaves"] == len(jax.tree.leaves(eng.params))
    assert len(_setup_spans("state")) == 1
    assert not _setup_spans("first_call")       # nothing is called yet
    eng.run_all([DecodeRequest(np.arange(1, 5), 3, request_id=i)
                 for i in range(2)])
    assert sorted(s["args"]["program"] for s in _setup_spans("first_call")) \
        == ["engine_prefill", "engine_prefill_embed", "step"]
    # the prefill's programs lie there themselves from then on
    assert list(eng._prefill_calls) == list(eng._prefill_fns)
    for program in ("step", "engine_prefill", "engine_prefill_embed"):
        assert names.programs()[program]["count"] >= 1


def test_the_setup_layer_has_no_pause_watch(fresh):
    for phase in SETUP_PHASES:
        assert pause_watcher().phase("setup", phase) is None
    n0 = len(_events("host_pause"))
    for _ in range(12):
        with span("setup", "place"):
            pass
    with span("setup", "place"):        # 100x the phase's typical time
        time.sleep(0.03)
    assert len(_events("host_pause")) == n0


# -- the listener -------------------------------------------------------------

def test_the_table_names_the_rings_programs_with_each_kind(fresh, model):
    own = RecompileWatcher().install()  # the process's table names 64
    dec = _decoder(model)
    dec.generate(_prompts(), NEW, prefill=True, token_chunk=CHUNK)
    rows = own.programs()
    for program in ("device_decode", "device_prefill"):
        row = rows[program]
        assert set(row) == {k + "_s" for k in JAX_KINDS} | {"count"}
        assert row["trace_s"] > 0 and row["lower_s"] > 0
        assert row["compile_s"] + row["cache_load_s"] > 0
        assert row["count"] >= 1
    # set-up's close froze the process's table, costliest first
    frozen = setup_log().programs
    assert {"device_decode", "device_prefill"} <= set(frozen)
    assert list(frozen) == list(_costliest_rows(frozen))


def test_a_load_from_the_persistent_cache_is_no_compile(tmp_path):
    """The backend event wraps ``compile_or_get_cached``: a hit in the
    persistent cache fires it too, with a retrieval inside it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a listener of the test's own: the process's table names 64 programs
    w = RecompileWatcher().install()
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = [getattr(jax.config, key) for key in keys]
    for key, value in zip(keys, (str(tmp_path), 0.0, 0)):
        jax.config.update(key, value)
    cc.reset_cache()
    try:
        def setup_cache_probe(x):
            return jnp.tanh(x) * 3 + 1

        loads = REGISTRY.histogram("jax.cache_load_s").count
        compiles = REGISTRY.histogram("jax.compile_s").count
        jax.jit(setup_cache_probe)(jnp.ones(5)).block_until_ready()
        row = w.programs()["setup_cache_probe"]
        assert row["compile_s"] > 0 and row["cache_load_s"] == 0
        assert row["count"] == 1
        jax.clear_caches()      # the in-memory cache; the directory stays
        jax.jit(setup_cache_probe)(jnp.ones(5)).block_until_ready()
        again = w.programs()["setup_cache_probe"]
        assert again["cache_load_s"] > 0 and again["count"] == 2
        assert again["compile_s"] == row["compile_s"]
        # jax.compile_s keeps every backend event, loads included
        assert REGISTRY.histogram("jax.cache_load_s").count >= loads + 1
        assert REGISTRY.histogram("jax.compile_s").count >= compiles + 2
    finally:
        for key, value in zip(keys, was):
            jax.config.update(key, value)
        cc.reset_cache()


def test_a_jit_inside_a_jit_adds_its_trace_time_once():
    """An inner ``jit``'s trace event fires inside the outer's time: the
    outer's row takes its own time less the inner's."""
    w = RecompileWatcher()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    before = REGISTRY.histogram("jax.trace_s").sum
    durs = {}

    def traced(name, inner=()):     # as jax fires them: a scalar where
        w.on_start(trace, time.time(), fun_name=name)   # it begins, the
        t0 = time.perf_counter()                        # time at its end
        time.sleep(0.002)
        for nm in inner:
            traced(nm)
            time.sleep(0.001)
        durs[name] = time.perf_counter() - t0
        w.on_duration(trace, durs[name], fun_name=name)

    traced("outer", inner=("inner_a", "inner_b"))
    traced("later")
    rows = w.programs()
    for name in ("inner_a", "inner_b", "later"):
        assert rows[name]["trace_s"] == pytest.approx(durs[name])
    assert rows["outer"]["trace_s"] == pytest.approx(
        durs["outer"] - durs["inner_a"] - durs["inner_b"])
    # however many lie inside one: a block's trace holds hundreds
    w.on_start(trace, 0.0, fun_name="wide")
    for _ in range(1000):
        w.on_start(trace, 0.0, fun_name="leaf")
        w.on_duration(trace, 1e-3, fun_name="leaf")
    w.on_duration(trace, 1.25, fun_name="wide")
    rows = w.programs()
    assert rows["leaf"]["trace_s"] == pytest.approx(1.0)
    assert rows["wide"]["trace_s"] == pytest.approx(0.25)
    total = durs["outer"] + durs["later"] + 1.25
    assert sum(r["trace_s"] for r in rows.values()) == pytest.approx(total)
    assert REGISTRY.histogram("jax.trace_s").sum - before \
        == pytest.approx(total)
    # and of a real pair: the outer's time holds the inner's, once (a
    # listener of the test's own: the process's table names 64 programs)
    p = RecompileWatcher().install()

    @jax.jit
    def setup_inner_probe(x):
        return jnp.sin(x) + 1

    def setup_outer_probe(x):
        return setup_inner_probe(x) * 2

    t0 = time.perf_counter()
    jax.jit(setup_outer_probe).trace(jnp.ones(7))
    wall = time.perf_counter() - t0
    rows = p.programs()
    assert rows["setup_inner_probe"]["trace_s"] > 0
    assert rows["setup_inner_probe"]["trace_s"] \
        + rows["setup_outer_probe"]["trace_s"] <= wall


def test_the_table_is_bounded_and_sums_the_rest_under_other():
    w = RecompileWatcher()
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    n = 3 * PROGRAM_TABLE_SIZE
    for i in range(n):
        w.on_duration(lower, 1e-9 * (i + 1), fun_name=f"jit(program_{i})")
    rows = w.programs()
    assert len(rows) == PROGRAM_TABLE_SIZE + 1
    assert f"program_{n - 1}" in rows and "program_0" not in rows
    assert sum(r["lower_s"] for r in rows.values()) \
        == pytest.approx(1e-9 * n * (n + 1) / 2)
    assert len(w._programs) <= 2 * PROGRAM_TABLE_SIZE + 1
    assert _costliest_rows(rows) == rows


# -- the breakdown ------------------------------------------------------------

T = 7            # two made-up thread ids
U = 9


@pytest.mark.parametrize("intervals, end, want", [
    # nested: a relay's compile is compile, and not also relay and place
    ([("compile", T, 2.0, 3.0), ("relay", T, 1.5, 3.5),
      ("place", T, 1.0, 5.0)], None,
     {"elapsed_s": 4.0, "compile_s": 1.0, "relay_s": 1.0, "place_s": 2.0}),
    # abutting: nothing is lost or counted twice at the seam
    ([("import", T, 0.0, 1.25), ("place", T, 1.25, 2.0),
      ("state", T, 2.0, 2.5)], 4.0,
     {"elapsed_s": 4.0, "import_s": 1.25, "place_s": 0.75, "state_s": 0.5,
      "unnamed_s": 1.5}),
    # two threads: an instant both cover is counted once
    ([("trace", U, 2.0, 4.0), ("first_call", U, 1.0, 6.0),
      ("place", T, 3.0, 8.0)], None,
     {"elapsed_s": 7.0, "trace_s": 2.0, "first_call_s": 3.0,
      "place_s": 2.0}),
    # an event that straddles its span's end
    ([("state", T, 0.0, 10.0), ("lower", T, 8.0, 12.0)], None,
     {"elapsed_s": 12.0, "state_s": 10.0, "lower_s": 2.0}),
    # an inner jit's trace inside an outer's is counted once; a gap
    ([("trace", T, 1.0, 2.0), ("trace", T, 0.5, 3.0),
      ("warm_run", T, 5.0, 6.0)], None,
     {"elapsed_s": 5.5, "trace_s": 2.5, "warm_run_s": 1.0,
      "unnamed_s": 2.0}),
], ids=["nested", "abutting", "two-threads", "straddling", "inner-trace"])
def test_the_breakdowns_parts_sum_to_the_elapsed_time(intervals, end, want):
    doc = setup_breakdown(intervals, end)
    assert list(doc) == ["elapsed_s"] + PARTS
    for key in doc:
        assert doc[key] == pytest.approx(want.get(key, 0.0), abs=1e-9), key
    assert _sum_parts(doc) == pytest.approx(doc["elapsed_s"], abs=1e-6)
    # whatever order the list is in
    assert setup_breakdown(intervals[::-1], end) == pytest.approx(doc)


def test_an_empty_list_has_no_breakdown(fresh):
    assert setup_breakdown() is None and setup_breakdown([]) is None


def test_a_real_builds_parts_sum_and_lie_within_the_stopwatch(fresh, model):
    t0 = time.perf_counter()
    dec = _decoder(model)
    dec.generate(_prompts(), NEW, prefill=True, token_chunk=CHUNK)
    wall = time.perf_counter() - t0
    doc = setup_breakdown()
    assert _sum_parts(doc) == pytest.approx(doc["elapsed_s"], abs=1e-6)
    assert all(doc[key] >= -1e-9 for key in doc)
    assert 0 < doc["elapsed_s"] <= wall
    for key in ("place_s", "state_s", "trace_s", "lower_s", "first_call_s",
                "warm_run_s"):
        assert doc[key] > 0, key
    assert doc["compile_s"] + doc["cache_load_s"] > 0
    assert doc["import_s"] == 0     # the fixture's log began after it


def test_the_list_closes_at_the_end_of_the_first_generation(fresh, model,
                                                            capfd):
    dec = _decoder(model)
    done0 = len(_events("setup_done"))
    assert setup_log().done is None
    dec.generate(_prompts(), NEW, prefill=True, token_chunk=CHUNK)
    frozen = setup_breakdown()
    assert setup_log().done == frozen
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("defer_tpu: setup ")]
    assert len(lines) == 1 and len(_events("setup_done")) == done0 + 1
    # the line parses, and its parts sum to its elapsed_s
    said = dict(re.findall(r"(\w+)=([\d.eE+-]+)(?= |$)", lines[0]))
    assert list(said) == ["elapsed_s"] + PARTS
    assert sum(float(said[key]) for key in PARTS) \
        == pytest.approx(float(said["elapsed_s"]), abs=2e-5)
    for key in said:
        assert float(said[key]) == pytest.approx(frozen[key], abs=1e-6)
    costliest = re.search(r" costliest=(\S+)$", lines[0]).group(1)
    assert [part.split(":")[0] for part in costliest.split(",")] \
        == list(setup_log().programs)[:3]
    event = _events("setup_done")[-1]["data"]
    assert event["costliest"] == costliest and event["threads"] == 1
    assert {key: event[key] for key in frozen} == frozen
    # a generation of another token_chunk compiles: the table grows,
    # the breakdown stays as it was, and nothing more is said
    table = recompile_watcher().programs()
    dec.generate(_prompts(), NEW, prefill=True, token_chunk=CHUNK + 1)
    assert len(dec._decode_fns) == 2
    assert recompile_watcher().programs()["device_decode"]["count"] \
        == table["device_decode"]["count"] + 1
    assert setup_breakdown() == frozen
    assert len(_events("setup_done")) == done0 + 1
    assert "defer_tpu: setup " not in capfd.readouterr().err
    # a later placement feeds its histogram, and the frozen list nothing
    places = REGISTRY.histogram("setup.place_s").count
    dec.reweight(model[1])
    assert REGISTRY.histogram("setup.place_s").count == places + 1
    assert setup_breakdown() == frozen


def test_the_list_closes_at_the_engines_first_park_after_a_step(fresh,
                                                                model):
    g, params = model
    done0 = len(_events("setup_done"))
    ran = {}

    def loop():     # as serve/engine.py::EngineLoop.run: park, join, step
        eng = ContinuousBatchEngine(g, params, num_stages=1, width=2)
        with span("engine", "park"):
            pass
        ran["parked_idle"] = setup_log().done
        eng.run_all([DecodeRequest(np.arange(1, 5), 3, request_id=0)])
        ran["stepped"] = setup_log().done
        with span("engine", "park"):
            pass

    t = threading.Thread(target=loop)
    t.start()
    t.join()
    # an idle engine's parks close nothing, nor does a step: the park
    # after it does
    assert ran == {"parked_idle": None, "stepped": None}
    doc = setup_log().done
    assert doc is not None and len(_events("setup_done")) == done0 + 1
    assert _sum_parts(doc) == pytest.approx(doc["elapsed_s"], abs=1e-6)
    # the busy period began where the first park ended
    assert doc["warm_run_s"] > 0 and doc["first_call_s"] > 0
