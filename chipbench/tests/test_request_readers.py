"""The four readers over the window's ``decode_done`` events
(``chipbench/request_events.py``): each on a list of events made by hand
— another tenant's mixed in, a ring that dropped, an empty window, a
tree without the event — and the manifest's entries, found by name."""

import pytest

from chipbench import request_events
from chipbench.manifest import Manifest
from chipbench.readings import quantile

ENGINE = "decode engine (serve/engine.py)"
DOOR = "front door (serve/frontdoor.py, serve/admission.py)"
#: name -> (unit, layer, the end-to-end metric it moves)
READERS = {
    "engine_first_token_ms": ("ms", ENGINE, "answer_ms_per_token_p90"),
    "engine_token_gap_ms": ("ms", ENGINE, "answer_ms_per_token_p50"),
    "engine_worst_gap_ms": ("ms", ENGINE, "answer_ms_per_token_p90"),
    "door_result_edge_ms": ("ms", DOOR, "answer_ms_per_token_p50"),
}
CELL = "gpt2xl_chat_serve"


def _event(i, *, tenant="bench", new=None, first=None, gap=10.0,
           worst=None, edge=0.25):
    """Request ``i``: ``new`` tokens, the first ``first`` ms after it was
    admitted, one every ``gap`` ms from then on, the answer written
    ``edge`` ms behind the last."""
    new = 2 + i if new is None else new
    first = 20.0 + i if first is None else first
    last = first + gap * (new - 1)
    return {"rid": i, "tenant": tenant, "prompt": 8 + i, "new_tokens": new,
            "popped_ms": 1.0, "prefill_ms": 2.0, "first_ms": first,
            "last_ms": last, "delivered_ms": last + edge, "forced_steps": 0,
            "pass_rounds": i % 3,
            "worst_gap_ms": (gap + i if worst is None else worst)
            if new > 1 else 0.0,
            "first_step": 10 * i, "last_step": 10 * i + new - 1}


@pytest.fixture
def ring():
    """The process's recorder, empty for one test; ``fill`` emits the
    given events' data as ``decode_done``."""
    from defer_tpu.obs.events import recorder
    rec = recorder()
    rec.clear()

    def fill(events):
        for data in events:
            rec.emit("decode_done", **data)
        return events

    yield fill
    rec.clear()


@pytest.fixture
def window(ring):
    """Twenty requests of the window's tenant, the warm-up's and the
    check's mixed in, and a shed beside them."""
    from defer_tpu.obs.events import recorder
    mine = [_event(i) for i in range(20)]
    ring([_event(100, tenant="warm", first=900.0)])
    for ev in mine:
        ring([ev])
        if ev["rid"] % 4 == 0:
            ring([_event(200 + ev["rid"], tenant="check", first=500.0,
                         gap=99.0, edge=40.0)])
            recorder().emit("shed", tenant="bench", reason="made up")
    return mine


def _read(name):
    return Manifest().reader(name).read(None)   # no reader asks the run


def test_the_window_is_the_benchmarks_tenant_alone(window):
    from defer_tpu.obs.events import recorder
    assert request_events.finished() == window
    others = [e["data"]["tenant"] for e in recorder().snapshot()
              if e["kind"] == "decode_done"
              and e["data"]["tenant"] != request_events.WINDOW_TENANT]
    assert sorted(set(others)) == ["check", "warm"] and len(others) == 6


def test_first_token_is_the_median_first_ms(window):
    assert _read("engine_first_token_ms") == pytest.approx(
        quantile([20.0 + i for i in range(20)], 0.5)) == pytest.approx(29.5)


def test_token_gap_is_the_median_gap_a_request(window, ring):
    assert _read("engine_token_gap_ms") == pytest.approx(10.0)
    # a request of twice the gap moves the median by its one vote, a
    # one-token answer by none
    ring([_event(30, gap=20.0), _event(31, gap=20.0), _event(32, new=1)])
    gaps = [10.0] * 20 + [20.0] * 2
    assert _read("engine_token_gap_ms") == pytest.approx(quantile(gaps, 0.5))
    ring([_event(40 + i, gap=20.0) for i in range(30)])
    assert _read("engine_token_gap_ms") == pytest.approx(20.0)


def test_worst_gap_is_the_p90_of_the_requests_longest_rounds(window, ring):
    want = quantile([10.0 + i for i in range(20)], 0.9)
    assert _read("engine_worst_gap_ms") == pytest.approx(want)
    ring([_event(32, new=1)])               # no gap: no vote
    assert _read("engine_worst_gap_ms") == pytest.approx(want)
    ring([_event(33, worst=100.0), _event(34, worst=100.0),
          _event(35, worst=100.0)])
    assert _read("engine_worst_gap_ms") == pytest.approx(quantile(
        [10.0 + i for i in range(20)] + [100.0] * 3, 0.9))


def test_result_edge_is_the_mean_from_last_id_to_written(window, ring):
    assert _read("door_result_edge_ms") == pytest.approx(0.25)
    ring([_event(30, edge=2.35)])
    assert _read("door_result_edge_ms") == pytest.approx(
        (20 * 0.25 + 2.35) / 21)


def test_one_request_is_a_window(ring):
    ring([_event(0)])
    assert _read("engine_first_token_ms") == pytest.approx(20.0)
    ring([_event(1, new=1)])
    assert _read("engine_token_gap_ms") == pytest.approx(10.0)


@pytest.mark.parametrize("name", list(READERS))
def test_a_reader_says_nothing_over_an_empty_window(name, ring):
    assert _read(name) is None
    ring([_event(0, tenant="warm"), _event(1, tenant="check")])
    assert _read(name) is None


@pytest.mark.parametrize("name", list(READERS))
def test_a_reader_says_nothing_over_a_ring_that_dropped(name, window):
    from defer_tpu.obs.events import recorder
    assert _read(name) is not None
    recorder().dropped = 1              # the sample may be a cut one
    assert _read(name) is None


@pytest.mark.parametrize("name", list(READERS))
def test_a_reader_says_nothing_in_a_tree_without_the_event(
        name, window, monkeypatch):
    from defer_tpu.obs import events
    kinds = dict(events.EVENT_KINDS)
    del kinds["decode_done"]
    monkeypatch.setattr(events, "EVENT_KINDS", kinds)
    assert _read(name) is None
    # nor in one without the recorder
    monkeypatch.delattr(events, "recorder")
    assert _read(name) is None


@pytest.mark.parametrize("name", list(READERS))
def test_the_manifest_gives_the_serving_cell_the_reader_by_name(name):
    m = Manifest()
    unit, layer, moves = READERS[name]
    assert m.metric(name) == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_counter", "layer": layer, "moves": moves,
        "workloads": [CELL]}
    reader = m.reader(name)
    assert (reader.LAYER, reader.SOURCE, reader.MOVES) \
        == (layer, "program_counter", moves)
    assert moves in m.cell(CELL).end_to_end
    for cell in m.workload_names():
        assert (name in m.cell(cell).per_layer) == (cell == CELL)
    # the layer is one the manifest already named for that cell
    assert layer in {e["layer"] for e in m.doc["per_layer"]
                     if e["name"] not in READERS
                     and CELL in e.get("workloads", ())}
