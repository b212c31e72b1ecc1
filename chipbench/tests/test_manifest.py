"""The manifest loader: the contract's limits, and adding by adding files."""

import copy
import json
import os

import pytest

import tiny
from chipbench.manifest import Manifest, ManifestError


def _doc():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _root(tmp_path, doc):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(tmp_path)


def test_the_committed_manifest_loads_and_names_real_files():
    m = Manifest()
    # PR 23's two cells lead the list; later PRs append theirs
    assert m.workload_names()[:2] == ["gpt2xl_batch_decode",
                                      "gpt2xl_chat_serve"]
    for name in m.workload_names():
        cell = m.cell(name)
        assert hasattr(m.driver(cell), "measure")
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        for metric in cell.per_layer:
            reader, entry = m.reader(metric), m.metric(metric)
            # each reader names its layer, source and the one metric it moves
            assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
                entry["layer"], entry["source"], entry["moves"])
            assert entry["moves"] in cell.end_to_end
    four = [w for w in m.doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m.doc["workloads"]) // 4)


@pytest.mark.parametrize("edit, what", [
    (lambda d: d["workloads"][0].update(name="has space"), "not a name"),
    (lambda d: d["workloads"][0].update(name="x" * 65), "not a name"),
    (lambda d: d["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda d: d["end_to_end"][0].update(unit="µs"), "unit"),
    (lambda d: d["end_to_end"][0].update(better="faster"), "better"),
    (lambda d: d["end_to_end"][0].update(source="program_counter"), "source"),
    (lambda d: d["per_layer"][0].update(why="because"), "unknown keys"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "end-to-end"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
    (lambda d: d["workloads"][0].update(why="y" * 201), "why"),
    (lambda d: d.update(run_seconds=52), "run_seconds"),
    (lambda d: d.update(extra=1), "keys"),
    (lambda d: d["configs"][0].update(file="defer_tpu/x.json"), "outside"),
    (lambda d: d["end_to_end"].pop(), "setup_s"),
])
def test_a_bad_manifest_is_refused(tmp_path, edit, what):
    doc = _doc()
    edit(doc)
    with pytest.raises(ManifestError, match=what):
        Manifest(_root(tmp_path, doc))


def test_a_cell_and_a_metric_are_added_by_adding_files(tmp_path):
    """A later PR adds a cell (config + traffic + one entry) and a
    per-layer metric (reader + one entry) without editing a file."""
    root = tiny.make_root(str(tmp_path))
    before = {}
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            p = os.path.join(dirpath, fn)
            if fn != "BENCHMARK.json":
                before[p] = open(p, "rb").read()
    bench = os.path.join(root, "chipbench")
    cfg = copy.deepcopy(tiny.CONFIGS["gpt-tiny"])
    cfg["model_args"]["num_layers"] = 3
    cfg["reference"]["args"]["n_layer"] = 3
    with open(os.path.join(bench, "configs", "gpt-tiny-3l.json"), "w") as f:
        json.dump(cfg, f)
    traffic = dict(tiny.TRAFFIC["batch_tiny"], batch=4)
    with open(os.path.join(bench, "traffic", "batch4_tiny.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "metrics", "decode_readings.py"), "w") as f:
        f.write('LAYER = "decode ring (runtime/decode.py)"\n'
                'SOURCE = "host_clock"\nMOVES = "tokens_per_s"\n\n\n'
                'def read(run):\n    return float(len(run.readings))\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "gpt-tiny-3l", "source": "a test",
                           "file": "chipbench/configs/gpt-tiny-3l.json",
                           "reduced": [], "why": "added by a test"})
    doc["workloads"].append({"name": "batch4_tiny_3l",
                             "config": "gpt-tiny-3l",
                             "traffic": "batch4_tiny", "chips": 1,
                             "why": "added by a test"})
    for m in doc["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("batch4_tiny_3l")
    doc["per_layer"].append({
        "name": "decode_readings", "unit": "readings", "better": "higher",
        "source": "host_clock", "layer": "decode ring (runtime/decode.py)",
        "moves": "tokens_per_s", "workloads": ["batch4_tiny_3l"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    from chipbench.harness import run_cell
    import time
    out = run_cell(workload="batch4_tiny_3l", seed=5, seconds=0.5,
                   trace=False, t_start=time.perf_counter(), root=root,
                   require_tpu=False)
    assert out["correct"] and out["metrics"]["tokens_per_s"]["value"] > 0
    cell = Manifest(root).cell("batch4_tiny_3l")
    assert cell.per_layer == ("decode_readings",)
    for p, data in before.items():   # nothing that was there was edited
        assert open(p, "rb").read() == data
