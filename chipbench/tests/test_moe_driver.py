"""The routed-expert cell's driver, readers and counts at a tiny preset
on the CPU, through the harness; and ``roofline_moe`` against counts
made by hand."""

import json
import os
import time

import pytest

import tiny
from chipbench import roofline_moe
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

OLMOE_ARGS = {"num_layers": 2, "hidden": 64, "heads": 4, "seq_len": 64,
              "vocab": 211, "num_experts": 8, "experts_per_tok": 2,
              "expert_hidden": 32, "rope_theta": 10000.0, "rms_eps": 1e-05}
CONFIG = {"model_args": OLMOE_ARGS,
          "reference": {"module": "chipbench.reference.olmoe",
                        "args": {"n_layer": 2, "n_head": 4, "top_k": 2,
                                 "eps": 1e-05, "theta": 10000.0}}}
TRAFFIC = {"driver": "batch_decode_moe", "batch": 4, "prompt_len": 8,
           "new_tokens": 16, "token_chunk": 2, "max_len": 32,
           "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "trace_seconds": 0.5}
CELL = "moe_tiny"
SHARED = ("tokens_per_s", "decode_step_ms", "decode_chunk_ms",
          "decoder_launch_ms", "decode_device_idle_share",
          "decode_prefill_ms", "decode_host_serial_ms")
NEW = ("moe_decode_step_roofline", "moe_prefill_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_moe_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "olmoe-tiny", CONFIG),
                            ("traffic", "batch_moe_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "olmoe-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/olmoe-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "olmoe-tiny", "traffic": "batch_moe_tiny",
        "chips": 1, "why": "tiny preset for the CPU tests"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in SHARED + NEW:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_real_manifest_gives_the_cell_both_new_metrics():
    cell = Manifest().cell("olmoe_batch_decode")
    assert set(NEW) <= set(cell.per_layer)
    assert "decode_step_roofline" not in cell.per_layer   # GPT-2's count
    assert cell.traffic["driver"] == "batch_decode_moe"
    assert cell.config["num_hidden_layers"] == 8
    assert cell.config["published"]["num_hidden_layers"] == 16
    width_keys = {k: v for k, v in cell.config["published"].items()
                  if k != "num_hidden_layers"}
    assert all(cell.config[k] == v for k, v in width_keys.items())


def test_every_cell_of_the_real_manifest_names_real_files():
    """``test_manifest.py`` pins the list of cells to PR 23's two names, and
    a PR that adds cells may not edit it (PERF.md section 7): the rest of
    its check, for every cell there is now."""
    m = Manifest()
    assert m.workload_names()[:2] == ["gpt2xl_batch_decode",
                                      "gpt2xl_chat_serve"]
    assert {"olmoe_batch_decode", "gpt2xl_pipe4_decode"} \
        <= set(m.workload_names())
    for name in m.workload_names():
        cell = m.cell(name)
        assert hasattr(m.driver(cell), "measure")
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        for metric in cell.per_layer:
            reader, entry = m.reader(metric), m.metric(metric)
            assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
                entry["layer"], entry["source"], entry["moves"])
            assert entry["moves"] in cell.end_to_end
    four = [w for w in m.doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m.doc["workloads"]) // 4)


def test_an_untraced_run_prints_the_contracts_keys(root):
    doc = run_cell(workload=CELL, seed=2 ** 31 + 4321, seconds=1.0,
                   trace=False, t_start=time.perf_counter(), root=root,
                   require_tpu=False)
    json.dumps(doc)
    assert doc["correct"] is True and doc["failed"] == 0
    assert set(doc["metrics"]) == {"tokens_per_s", "setup_s"}
    assert doc["metrics"]["tokens_per_s"]["value"] > 0


def test_a_traced_run_reports_both_new_metrics(root, monkeypatch):
    """Off the chip the harness has no peak table's row and the trace
    no program runs (``XLA Modules`` is a TPU plane's line), so the
    readers would return nothing: give the run the v5e's peaks and
    stand-in program times, and see both shares come out of the traced
    run's own counters, above 0."""
    import chipbench.harness as harness
    import chipbench.trace as trace

    monkeypatch.setattr(trace.TraceReduction, "module_runs",
                        lambda self, pattern, device=0: [2e-3, 3e-3, 4e-3])

    real = harness.Context.__init__

    def with_peaks(self, **kw):
        real(self, **dict(kw, peaks=PEAKS))

    monkeypatch.setattr(harness.Context, "__init__", with_peaks)
    doc = run_cell(workload=CELL, seed=11, seconds=1.0, trace=True,
                   t_start=time.perf_counter(), root=root, require_tpu=False)
    assert doc["correct"] is True
    per_layer = set(Manifest(root).cell(CELL).per_layer)
    assert set(NEW) | {"decode_step_ms", "decode_prefill_ms"} \
        <= set(doc["metrics"]) <= per_layer
    for name in NEW:
        m = doc["metrics"][name]
        assert m["unit"] == "%" and 0 < m["value"] < 100


@pytest.fixture(scope="module")
def tiny_model():
    import jax.numpy as jnp
    import numpy as np
    from chipbench.drivers import batch_decode_moe as drv
    from defer_tpu import models

    graph = models.olmoe(**OLMOE_ARGS)
    params = drv.make_weights(graph, 2 ** 31 + 77, jnp.float32, {})
    seqs = np.random.default_rng(5).integers(0, 211, (2, 24)).astype("int32")
    return drv, graph, params, seqs


def test_the_configurations_gains_scale_the_leaves_they_name(tiny_model):
    """``init_gain`` is the benchmark's choice, applied by the driver to
    the program's plain initialiser: the real cell's file makes embedding
    rows of unit variance and doubles every router, and nothing else."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    drv, graph, plain, _ = tiny_model
    gains = Manifest().cell("olmoe_batch_decode").config["init_gain"]
    assert gains == {"embeddings/wte": 50.0, "router/w": 2.0}
    scaled = drv.make_weights(graph, 2 ** 31 + 77, jnp.float32, gains)
    assert float(np.std(scaled["embeddings"]["wte"])) \
        == pytest.approx(1.0, rel=0.05)
    scaled_leaves = jax.tree.leaves(scaled)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(plain),
                            scaled_leaves, strict=True):
        name = "/".join(k.key for k in path)
        gain = (50.0 if name == "embeddings/wte"
                else 2.0 if name.endswith("router/w") else 1.0)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a) * gain,
                                   rtol=1e-6, err_msg=name)


def test_the_router_agrees_with_the_reference_and_a_wrong_one_does_not(
        tiny_model, monkeypatch):
    """In float32 the program's blocks make the reference's choices; a
    router that takes the smallest probabilities shares almost none, far
    under the limit (so ``check`` would say not correct)."""
    import importlib
    program = importlib.import_module("defer_tpu.models.olmoe")
    drv, graph, params, seqs = tiny_model
    shares = drv.router_agreement(graph, params, seqs, CONFIG["reference"])
    assert len(shares) == 2 and min(shares) >= 0.99
    right = program.route_top_k
    monkeypatch.setattr(program, "route_top_k",
                        lambda logits, k: right(-logits, k))
    wrong = drv.router_agreement(graph, params, seqs, CONFIG["reference"])
    assert wrong[0] < 0.5 < drv.ROUTER_TOL
    assert drv.GAP_TOL == 0.045


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.moe.*`` counters (or off the
    chip) a reader gives None and does not raise."""
    import types
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS)
    mf = Manifest()
    for name in NEW:
        assert mf.reader(name).read(run) is None


def test_decode_step_needs_against_a_hand_count():
    """8 layers, 16 rows at 1152 live positions, 56.4 of 64 experts hit
    (share 0.882): experts 5.68 GB + attention weights 0.27 + head 0.21
    + key/value rows 1.21 = 7.4 GB (ISSUE 26's sizing)."""
    flops, nbytes = roofline_moe.olmoe_decode_step_needs(
        n_layer=8, n_embd=2048, vocab=50304, n_experts=64,
        expert_width=1024, top_k=8, rows=16, live_positions=1152,
        experts_hit_share=0.882, weight_bytes=2, kv_bytes=2)
    experts = 8 * 0.882 * 64 * 3 * 2048 * 1024 * 2
    attn = 8 * 4 * 2048 * 2048 * 2
    router = 8 * 2048 * 64 * 2
    head = 2048 * 50304 * 2
    kv = 16 * 8 * 2 * 1152 * 2048 * 2
    logits = 16 * 50304 * 4
    assert nbytes == pytest.approx(experts + attn + router + head + kv
                                   + logits)
    assert round(experts / 1e9, 2) == 5.68 and round(kv / 1e9, 2) == 1.21
    assert round(nbytes / 1e9, 1) == 7.4
    # 2 flops a weight a row: 8 experts a token, never 64
    assert flops == pytest.approx(16 * (
        8 * (2 * (4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024)
             + 4 * 1152 * 2048) + 2 * 2048 * 50304))


def test_prefill_needs_against_a_hand_count():
    """16 x 1024 tokens through 8 layers: experts 13.2 + q/k/v/o 4.4 +
    causal attention 0.55 = 18.1 TFLOP; all 64 experts would be 110."""
    flops, _ = roofline_moe.olmoe_prefill_needs(
        n_layer=8, n_embd=2048, n_head=16, vocab=50304, n_experts=64,
        expert_width=1024, top_k=8, rows=16, prompt_len=1024,
        weight_bytes=2, kv_bytes=2)
    tokens = 16 * 1024
    experts = 8 * tokens * 8 * 2 * 3 * 2048 * 1024
    qkvo = 8 * tokens * 2 * 4 * 2048 * 2048
    attn = 8 * 16 * 2 * 1024 * 1024 * 2048
    assert round(experts / 1e12, 1) == 13.2 and round(qkvo / 1e12, 1) == 4.4
    assert round(attn / 1e12, 2) == 0.55
    assert round((experts + qkvo + attn) / 1e12, 1) == 18.1
    router = 8 * tokens * 2 * 2048 * 64
    head = 16 * 2 * 2048 * 50304
    assert flops == pytest.approx(experts + qkvo + attn + router + head)
    assert round(8 * experts / 1e12) == 106
