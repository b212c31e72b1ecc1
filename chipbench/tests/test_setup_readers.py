"""The ten ``setup_*_s`` readers: each gives its kind of the program's
``setup_breakdown()``, and nothing from a program that has no such
function (the parent of the PR that added it) or no interval."""

import pytest

from chipbench.manifest import Manifest

KINDS = {"import": "program_span", "place": "program_span",
         "relay": "program_span", "state": "program_span",
         "trace": "program_counter", "lower": "program_counter",
         "compile": "program_counter", "cache_load": "program_counter",
         "warm_run": "program_span", "unnamed": "program_span"}
LAYER = ("set-up (defer_tpu/__init__.py, runtime/decode.py, "
         "serve/engine.py, obs/profile.py)")


@pytest.fixture
def log():
    from defer_tpu.obs.profile import setup_log
    setup_log().clear()
    yield setup_log()
    setup_log().clear()


def test_the_manifest_gives_every_cell_the_ten_readers():
    m = Manifest()
    last = m.doc["per_layer"][-len(KINDS):]
    assert [e["name"] for e in last] == [f"setup_{k}_s" for k in KINDS]
    for entry in last:
        assert entry == {
            "name": entry["name"], "unit": "s", "better": "lower",
            "source": KINDS[entry["name"][len("setup_"):-len("_s")]],
            "layer": LAYER, "moves": "setup_s"}
        reader = m.reader(entry["name"])
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            LAYER, entry["source"], "setup_s")
    for cell in m.workload_names():
        assert {e["name"] for e in last} <= set(m.cell(cell).per_layer)


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_reader_gives_its_kinds_exclusive_seconds(kind, log, monkeypatch):
    from defer_tpu.obs import profile
    read = Manifest().reader(f"setup_{kind}_s").read
    assert read(None) is None                   # an empty list
    # one interval a kind, each inside the one before it and 1 s
    # shorter at both ends; then 3 s that nothing covers
    named = [k for k in KINDS if k != "unnamed"]
    for i, k in reversed(list(enumerate(named))):
        log.add(k, 100.0 + i, 200.0 - i)
    log.add("first_call", 203.0, 204.0)
    want = {k: 2.0 for k in named} | {named[-1]: 100.0 - 2 * (len(named) - 1),
                                      "unnamed": 3.0}
    assert read(None) == pytest.approx(want[kind])
    assert read(None) == profile.setup_breakdown()[kind + "_s"]
    # a tree from before the breakdown: nothing, and no error
    monkeypatch.delattr(profile, "setup_breakdown")
    assert read(None) is None
