"""A served request's life, stamped where it happens: the engine's
waypoints (``serve/engine.py::Waypoints``), the flight that knows what
rides in front of it, the door's decode buckets and its ``decode_done``
event, and the pause watch's typical time a count of passes.
"""

import time
import types

import numpy as np
import pytest

import jax

from defer_tpu.models.gpt import gpt_tiny
from defer_tpu.obs import REGISTRY, pause_watcher, recorder, span
from defer_tpu.obs.attrib import DECODE_BUCKETS, DOOR_BUCKETS
from defer_tpu.obs.events import validate_event
from defer_tpu.obs.profile import PAUSE_BEHIND_MOST, PAUSE_UNJUDGED_FIRST
from defer_tpu.serve import ContinuousBatchEngine, DecodeRequest, ServeClient
from defer_tpu.serve import engine as engine_mod
from defer_tpu.serve.client import fetch_stats
from defer_tpu.serve.frontdoor import ServeFrontDoor


@pytest.fixture(scope="module")
def gpt_setup():
    g = gpt_tiny(seq_len=48)
    return g, g.init(jax.random.key(0))


def _request(rid, plen, new, **kw):
    rng = np.random.default_rng(rid)
    return DecodeRequest(rng.integers(0, 97, (plen,)).astype(np.int32), new,
                         request_id=rid, **kw)


def _one_join_a_call(eng, queue):
    if queue and eng.free_slots():
        eng.join(queue.pop(0))


def _count(name):
    return REGISTRY.counter(f"serve.decode.{name}").value


# -- the engine alone: ``run_all`` fills the record without a door ---------------------

#: (prompt, answer) lengths: a one-token prompt has no pass, a one-token
#: answer no gap
SHAPES = [(4, 6), (1, 3), (7, 1), (5, 9), (1, 1), (2, 2)]


@pytest.fixture(scope="module")
def staggered(gpt_setup):
    """Six requests through a three-slot engine, one join a call: every
    one joins while others decode."""
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=3)
    reqs = [_request(i, p, n) for i, (p, n) in enumerate(SHAPES)]
    t0 = time.perf_counter()
    out = eng.run_all(list(reqs), joiner=_one_join_a_call)
    return eng, reqs, out, t0, time.perf_counter()


@pytest.mark.parametrize("rid", range(len(SHAPES)))
def test_run_all_leaves_every_request_its_stamps_in_order(staggered, rid):
    _eng, reqs, out, t0, t1 = staggered
    req, way = reqs[rid], reqs[rid].waypoints
    assert out[rid].size == req.prompt.size + req.max_new_tokens
    assert t0 <= way.prefill_at <= way.first_at <= way.last_at <= t1
    # a one-token answer's first id is its last
    assert (way.first_at == way.last_at) == (req.max_new_tokens == 1)


@pytest.mark.parametrize("rid", range(len(SHAPES)))
def test_rounds_are_the_answers_length_between_first_and_last_step(
        staggered, rid):
    eng, reqs, _out, _t0, _t1 = staggered
    req, way = reqs[rid], reqs[rid].waypoints
    assert way.rounds == req.max_new_tokens
    # a live slot is in every step from its first id to its last
    assert way.last_step - way.first_step == way.rounds - 1
    assert 0 <= way.first_step <= way.last_step < eng.steps
    assert way.forced_steps == 0
    # the gaps are the rounds behind the first id
    assert 0 <= way.pass_rounds <= way.rounds - 1
    assert (way.worst_gap > 0) == (way.rounds > 1)
    if way.rounds > 1:
        assert way.worst_gap <= way.last_at - way.first_at
        assert way.worst_gap >= (way.last_at - way.first_at) \
            / (way.rounds - 1) - 1e-9


def test_a_join_beside_a_decoding_slot_is_a_pass_round_of_the_older_one(
        gpt_setup):
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=3)
    older, newer = _request(0, 4, 8), _request(1, 5, 3)
    passes, rounds = _count("passes"), \
        REGISTRY.histogram("serve.decode.pass_round_s").count
    eng.join(older)
    eng.step()                  # its pass, its first step
    assert eng._flight.passes == 1 and _count("passes") == passes + 1
    eng.step()
    eng.step()
    assert eng._flight.passes == 0
    eng.join(newer)
    eng.step()                  # the newcomer's pass in front of this launch
    assert eng._flight.passes == 1 and _count("passes") == passes + 2
    held = eng._flight
    eng.step()                  # reads it: one round of the older's gaps
    assert eng._flight is not held and eng._flight.passes == 0
    eng.run_all([])
    assert older.waypoints.pass_rounds == 1
    # the newcomer's own pass rode in front of its first id: no gap
    assert newer.waypoints.pass_rounds == 0
    # both flights that held a pass were rounds of ``pass_round_s``
    assert REGISTRY.histogram("serve.decode.pass_round_s").count \
        == rounds + 2
    assert older.waypoints.worst_gap >= newer.waypoints.worst_gap > 0


def test_a_one_token_prompt_has_no_pass_and_is_stamped_at_its_launch(
        gpt_setup):
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    req = _request(0, 1, 3)
    passes = _count("passes")
    launches = REGISTRY.histogram("serve.decode.launch_s").count
    eng.join(req)
    t0 = time.perf_counter()
    eng.step()
    t1 = time.perf_counter()
    assert eng._flight.passes == 0 and _count("passes") == passes
    assert REGISTRY.histogram("serve.decode.launch_s").count == launches + 1
    eng.run_all([])
    way = req.waypoints
    assert t0 <= way.prefill_at <= t1 <= way.first_at
    assert (way.rounds, way.first_step, way.last_step) == (3, 0, 2)


@pytest.mark.parametrize("plen, forced", [(6, 3), (3, 0), (2, 0)])
def test_a_prompts_tail_fed_by_steps_is_counted_in_front_of_the_first_id(
        gpt_setup, monkeypatch, plen, forced):
    monkeypatch.setattr(engine_mod, "PREFILL_POSITIONS", 2)
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    req = _request(0, plen, 4)
    eng.run_all([req])
    way = req.waypoints
    assert way.forced_steps == forced == max(0, plen - 1 - 2)
    assert way.first_step == forced and way.rounds == 4
    assert way.prefill_at <= way.first_at <= way.last_at


def test_a_cancelled_request_gets_no_record(gpt_setup):
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    seen = []
    gone = _request(0, 4, 20, on_done=seen.append)
    stays = _request(1, 3, 5)
    eng.join(gone)
    eng.join(stays)
    eng.step()
    eng.step()
    assert eng.cancel(gone)
    eng.run_all([])
    assert seen == [None] and gone.waypoints is None
    assert stays.waypoints.rounds == 5


def test_the_record_is_on_the_request_before_on_done_fires(gpt_setup):
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    seen = []
    req = _request(0, 4, 3)
    req.on_done = lambda ids: seen.append((req.waypoints.rounds, ids.size))
    eng.run_all([req])
    assert seen == [(3, 7)]


# -- the door: buckets that tile the timeline, one event a finished request ----------

@pytest.fixture(scope="module")
def door(gpt_setup):
    g, params = gpt_setup
    engine = ContinuousBatchEngine(g, params, num_stages=2, width=3)
    door = ServeFrontDoor(engine=engine,
                          decode_defaults={"max_new_tokens": 4}).start()
    yield door
    door.stop()


def _done_events(tenant):
    return [validate_event(e)["data"] for e in recorder().snapshot()
            if e["kind"] == "decode_done" and e["data"]["tenant"] == tenant]


@pytest.fixture(scope="module")
def served(door):
    """Tenant ``tiled``: four requests over one connection, so that they
    decode side by side; then the stats reply."""
    host, port = door.address
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
               for n in (4, 1, 6, 3)]
    out = ServeClient(host, port, "tiled", max_new_tokens=5).stream(prompts)
    assert [o[0] for o in out] == ["ok"] * 4
    return prompts, fetch_stats(host, port)


def test_a_decode_tenants_block_holds_the_decode_buckets_and_no_other(served):
    _prompts, stats = served
    block = stats["attribution"]["tiled"]
    assert tuple(block) == DECODE_BUCKETS + ("e2e",)
    assert not {"gather", "chain"} & set(block)
    assert {b["count"] for b in block.values()} == {4}
    assert block["first_token"]["min"] > 0 and block["tokens"]["min"] > 0
    assert block["result_edge"]["min"] > 0
    # the tensor path's names are what they were
    assert DOOR_BUCKETS == ("admission", "gather", "chain", "result_edge")


def test_the_decode_buckets_sum_to_e2e(served, door):
    _prompts, stats = served
    block = stats["attribution"]["tiled"]
    # to the summary's rounding (1 ns a field)
    assert sum(block[k]["sum"] for k in DECODE_BUCKETS) \
        == pytest.approx(block["e2e"]["sum"], abs=1e-5)
    # and to the clock's in the histograms themselves
    hists = door.attrib._tenants["tiled"]
    assert sum(hists[k].sum for k in DECODE_BUCKETS) \
        == pytest.approx(hists["e2e"].sum, abs=1e-9)


@pytest.mark.parametrize("k", range(4))
def test_a_finished_request_leaves_one_event_with_its_stamps_in_order(
        served, k):
    prompts, _stats = served
    events = _done_events("tiled")
    assert len(events) == 4
    ev = sorted(events, key=lambda d: d["rid"])[k]
    assert set(ev) == {
        "rid", "tenant", "prompt", "new_tokens", "popped_ms", "prefill_ms",
        "first_ms", "last_ms", "delivered_ms", "forced_steps",
        "pass_rounds",
        "worst_gap_ms", "first_step", "last_step"}
    assert (ev["prompt"], ev["new_tokens"]) == (prompts[k].size, 5)
    assert 0 <= ev["popped_ms"] <= ev["prefill_ms"] < ev["first_ms"] \
        < ev["last_ms"] < ev["delivered_ms"]
    # the fixture's prompts (1-6 tokens) lie inside one pass
    assert ev["forced_steps"] == 0
    assert ev["last_step"] - ev["first_step"] == 4
    assert 0 <= ev["pass_rounds"] <= 4
    assert 0 < ev["worst_gap_ms"] <= ev["last_ms"] - ev["first_ms"]


def test_the_events_sum_to_the_buckets(served, door):
    events = _done_events("tiled")
    hists = door.attrib._tenants["tiled"]
    for bucket, lo, hi in (("admission", None, "popped_ms"),
                           ("join", "popped_ms", "prefill_ms"),
                           ("first_token", "prefill_ms", "first_ms"),
                           ("tokens", "first_ms", "last_ms"),
                           ("result_edge", "last_ms", "delivered_ms"),
                           ("e2e", None, "delivered_ms")):
        total = sum(ev[hi] - (ev[lo] if lo else 0.0) for ev in events)
        # an event's stamps are rounded to 0.1 us each
        assert total == pytest.approx(1e3 * hists[bucket].sum, abs=2e-3)


def test_a_long_prompts_forced_steps_ride_its_event(door):
    # what explains a long ``first_token``: the prompt's tail fed by steps
    req = _request(77, 9, 2)
    req.queued_pc, req.popped_at = 10.0, 10.001
    way = req.waypoints = engine_mod.Waypoints()
    way.prefill_at, way.first_at, way.last_at = 10.002, 10.050, 10.060
    way.forced_steps, way.rounds = 3, 2
    way.first_step, way.last_step = 3, 4
    door._record_decode("long", req, 10.0605)
    (ev,) = _done_events("long")
    assert (ev["forced_steps"], ev["first_step"], ev["prompt"]) == (3, 3, 9)
    assert ev["first_ms"] == pytest.approx(50.0)


def test_the_stats_reply_and_the_registry_carry_the_decode_histograms(
        served):
    _prompts, stats = served
    dec = stats["decode"]
    assert dec["first_token_s"]["count"] >= 4
    # the result edge has one source, the door's bucket
    assert "result_edge_s" not in dec
    assert dec["passes"] >= 3           # one of the four prompts has none
    assert dec["pass_round_s"]["count"] >= 1
    assert dec["pass_round_s"]["count"] <= dec["step_s"]["count"]
    # admitted -> first id holds the whole of admission, join, first_token
    block = stats["attribution"]["tiled"]
    assert dec["first_token_s"]["max"] * 1e3 >= block["first_token"]["min"]
    text = REGISTRY.exposition()           # the Prometheus text
    for name in ("first_token_s", "pass_round_s", "passes"):
        assert f"serve_decode_{name}" in text


def test_a_request_that_left_mid_decode_leaves_no_event_and_no_bucket(
        door, monkeypatch):
    host, port = door.address
    step = door.engine.step

    def slow_step():            # 40 tokens outlast the abort below
        time.sleep(0.02)
        return step()

    monkeypatch.setattr(door.engine, "step", slow_step)
    victim = ServeClient(host, port, "victim", max_new_tokens=40)
    victim.submit(np.arange(4, dtype=np.int32))
    deadline = time.monotonic() + 30
    while door.engine.active() == 0:
        assert time.monotonic() < deadline, "victim never joined"
        time.sleep(0.01)
    victim.abort()
    deadline = time.monotonic() + 30
    while door.engine.free_slots() != door.engine.width:
        assert time.monotonic() < deadline, "the slot was never reclaimed"
        time.sleep(0.02)
    assert _done_events("victim") == []
    assert "victim" not in fetch_stats(host, port)["attribution"]
    assert [e for e in recorder().snapshot() if e["kind"] == "decode_cancel"]


def test_monitor_serve_prints_a_decode_tenants_own_buckets(served, capsys):
    from defer_tpu.cli import _render_serve_stats
    _prompts, stats = served
    _render_serve_stats(stats)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if "p50ms:" in ln]
    assert len(line) == 1
    names = [part.split("=")[0] for part in line[0].split("p50ms:")[1].split()]
    assert names == list(DECODE_BUCKETS) + ["e2e"]
    # a tensor tenant's line is the four it always was
    four = {k: {"count": 1, "p50": 1.0} for k in DOOR_BUCKETS + ("e2e",)}
    _render_serve_stats({"tenants": {"t": {}}, "attribution": {"t": four}})
    assert ("p50ms: admission=1.00 gather=1.00 chain=1.00 result_edge=1.00 "
            "e2e=1.00") in capsys.readouterr().out


# -- the pause watch keeps a phase's typical time a count of passes ------------------

def _span_like():
    return types.SimpleNamespace(cpu_s=0.0, proc_cpu_s=0.0, args=None,
                                 t1=time.perf_counter())


def _pauses():
    return REGISTRY.counter("serve.decode.pauses").value


@pytest.fixture
def device_watches():
    """``engine.device``'s watches, as a new process has them."""
    pw = pause_watcher()
    plain = pw.phase("engine", "device")
    watches = [plain] + [plain.behind(k)
                         for k in range(1, PAUSE_BEHIND_MOST + 1)]
    for w in watches:
        w.typ, w._first, w._streak = None, [], 0
    pw._last_line = float("-inf")
    return watches


def test_a_span_with_a_count_feeds_the_counts_own_watch(device_watches):
    plain, one, two = device_watches
    assert len({id(plain), id(one), id(two)}) == 3
    assert plain.behind(0) is plain and plain.behind(1) is one
    assert plain.behind(7) is two                   # clipped
    pw = pause_watcher()
    assert pw._watches["engine", "device"] is plain
    assert pw._watches["engine", "device", 1] is one    # the one table
    assert span("engine", "device")._watch is plain
    for k, w in ((0, plain), (1, one), (2, two), (3, two)):
        assert span("engine", "device",
                    {"passes": k, "step": 5})._watch is w
    # args without a count, and an unjudged phase with one, take the
    # path they took
    assert span("engine", "sync", {"ahead": 1})._watch \
        is pw.phase("engine", "sync")
    assert span("engine", "park", {"passes": 1})._watch is None


def test_waits_behind_a_pass_are_no_pauses_beside_plain_waits(
        device_watches):
    plain, one, _two = device_watches
    before = _pauses()
    # a run's rounds: 5 ms waits, and every tenth behind a join's pass
    for i in range(20 * PAUSE_UNJUDGED_FIRST):
        if i % 10 == 9:
            one.feed(_span_like(), 0.015)
        else:
            plain.feed(_span_like(), 0.005)
    assert _pauses() == before
    assert plain.typ == pytest.approx(0.005)
    assert one.typ == pytest.approx(0.015)


@pytest.mark.parametrize("passes", [0, 1])
def test_a_late_wake_up_is_a_pause_at_either_count(device_watches, passes,
                                                   capfd):
    plain, one, _two = device_watches
    for _ in range(PAUSE_UNJUDGED_FIRST + 2):
        plain.feed(_span_like(), 0.005)
        one.feed(_span_like(), 0.015)
    before, seen = _pauses(), recorder().cursor()
    # through the span itself: only the pause is slept
    with span("engine", "device", {"passes": passes, "step": 77}):
        time.sleep(0.1)
    assert _pauses() == before + 1
    ev = [e for e in recorder().events_since(seen)[1]
          if e["kind"] == "host_pause"]
    assert len(ev) == 1
    d = validate_event(ev[0])["data"]
    assert (d["layer"], d["phase"], d["round"], d["passes"]) \
        == ("engine", "device", 77, passes)
    assert d["typical_ms"] == pytest.approx(15.0 if passes else 5.0)
    assert d["wall_ms"] >= 100
    line = [ln for ln in capfd.readouterr().err.splitlines()
            if "host_pause" in ln]
    assert len(line) == 1 and f"passes={passes}" in line[0]
    # the other count's typical time did not move
    assert (plain if passes else one).typ \
        == pytest.approx(0.005 if passes else 0.015)


def test_a_wait_behind_two_passes_is_judged_from_its_first(device_watches,
                                                           capfd):
    """Rounds behind two passes are too few a run to pass the unjudged
    first seven: the count's watch starts at one pass more than the
    waits behind one."""
    plain, one, two = device_watches
    pw = pause_watcher()
    for _ in range(PAUSE_UNJUDGED_FIRST + 2):
        plain.feed(_span_like(), 0.005)
        one.feed(_span_like(), 0.015)
    del pw._watches["engine", "device", 2]      # as before its first round
    two = plain.behind(2)
    assert two.typ == pytest.approx(0.025) and plain.behind(5) is two
    before = _pauses()
    two.feed(_span_like(), 0.026)               # two passes: no pause
    assert _pauses() == before
    with span("engine", "device", {"passes": 2, "step": 78}):
        time.sleep(0.1)                         # a late wake-up inside one
    assert _pauses() == before + 1
    assert "passes=2" in capfd.readouterr().err
    # before the waits behind one have a typical time there is nothing
    # to start from: the first seven go unjudged, as any phase's
    del pw._watches["engine", "device", 2]
    one.typ = None
    assert plain.behind(2).typ is None


def test_without_the_count_a_join_would_have_been_a_pause(device_watches):
    """What the count repairs: the same waits through one watch."""
    plain, _one, _two = device_watches
    before = _pauses()
    for i in range(3 * PAUSE_UNJUDGED_FIRST):
        plain.feed(_span_like(), 0.005)
    plain.feed(_span_like(), 0.0155)    # 3x and 10 ms over: flagged
    assert _pauses() == before + 1


def test_the_engines_device_wait_carries_its_flights_count(gpt_setup,
                                                           traced):
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    eng.join(_request(0, 4, 3))
    eng.run_all([])
    waits = [s["args"] for s in traced.spans if s["name"] == "engine.device"]
    # the first step rode behind the prompt's pass, the others behind none
    assert waits == [{"passes": 1, "step": 0}, {"passes": 0, "step": 1},
                     {"passes": 0, "step": 2}]
    launched = [s["args"]["step"] for s in traced.spans
                if s["name"] == "engine.step"]
    # the step a wait names is the one its launch's root span named
    assert launched == [0, 1, 2][:len(launched)] or launched == [1, 2]
