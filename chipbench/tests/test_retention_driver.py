"""The retention cell's driver, readers and counts at a tiny preset on
the CPU, through the harness; and ``roofline_retention`` against counts
made by hand."""

import json
import os
import time
import types

import pytest

import tiny
from chipbench import roofline_retention as rr
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

ARGS = {"num_layers": 2, "hidden": 64, "heads": 4, "kv_heads": 2,
        "mlp_hidden": 96, "seq_len": 64, "vocab": 211,
        "rope_theta": 1000000.0, "rms_eps": 1e-06}
CONFIG = {"model_args": ARGS, "init_gain": {"embeddings/wte": 50.0},
          "reference": {"module": "chipbench.reference.brumby",
                        "args": {"n_layer": 2, "n_head": 4, "n_kv": 2,
                                 "eps": 1e-06, "theta": 1000000.0}}}
TRAFFIC = {"driver": "batch_decode_retention", "batch": 4, "prompt_len": 8,
           "new_tokens": 16, "token_chunk": 2, "max_len": 32,
           "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "trace_seconds": 0.5}
CELL = "retention_tiny"
SHARED = ("tokens_per_s", "decode_step_ms", "decode_chunk_ms",
          "decoder_launch_ms", "decode_device_idle_share",
          "decode_prefill_ms", "decode_host_serial_ms")
NEW = ("retention_decode_step_roofline", "retention_step_kernel_roofline",
       "retention_prefill_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: what the configuration needs: 16 sequences x 8 layers x 8 KV heads x
#: 8256 rows (the symmetric square of 128) x (128 + 1) x 4 B; and what
#: the program holds, at the tiled square's 8704 rows
STATE = 16 * 8 * 8 * 8256 * 129 * 4
HELD = 16 * 8 * 8 * 8704 * 129 * 4
CELL_ARGS = dict(n_layer=8, n_embd=5120, n_head=40, n_kv=8,
                 mlp_width=17408, vocab=151936, rows=16, weight_bytes=2)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_ret_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "brumby-tiny", CONFIG),
                            ("traffic", "batch_ret_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "brumby-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/brumby-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "brumby-tiny", "traffic": "batch_ret_tiny",
        "chips": 1, "why": "tiny preset for the CPU tests"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in SHARED + NEW:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_real_manifest_gives_the_cell_its_files_and_metrics():
    m = Manifest()
    cell = m.cell("brumby_batch_decode")
    assert set(NEW) | set(SHARED[1:]) <= set(cell.per_layer)
    assert "moe_decode_step_roofline" not in cell.per_layer
    assert cell.end_to_end == ("tokens_per_s", "setup_s")
    assert cell.chips == 1
    assert cell.traffic["driver"] == "batch_decode_retention"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk", "max_len",
        "check_sequences", "trace_seconds")} == {
        "batch": 16, "prompt_len": 1024, "new_tokens": 512,
        "token_chunk": 8, "max_len": 1536, "check_sequences": 2,
        "trace_seconds": 6}
    # every published number under its own key, the depth alone reduced
    published = cell.config["published"]
    assert cell.config["num_hidden_layers"] == 8
    assert published["num_hidden_layers"] == 40
    assert all(cell.config[k] == v for k, v in published.items()
               if k != "num_hidden_layers")
    assert list(cell.config["reduced"]) == ["num_hidden_layers"]
    a = cell.config["model_args"]
    assert (a["hidden"], a["heads"], a["kv_heads"], a["mlp_hidden"],
            a["vocab"], a["rms_eps"], a["rope_theta"]) == (
        5120, 40, 8, 17408, 151936, 1e-6, 1e6)
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"] == ["brumby_batch_decode"]
    four = [w for w in m.doc["workloads"] if w["chips"] == 4]
    assert len(m.doc["workloads"]) >= 5 and len(four) == 1
    assert "brumby_batch_decode" in m.workload_names()[:5]


def test_an_untraced_run_checks_tokens_and_state(root):
    doc = run_cell(workload=CELL, seed=2 ** 31 + 4321, seconds=1.0,
                   trace=False, t_start=time.perf_counter(), root=root,
                   require_tpu=False)
    json.dumps(doc)
    assert doc["correct"] is True and doc["failed"] == 0
    assert set(doc["metrics"]) == {"tokens_per_s", "setup_s"}
    assert doc["metrics"]["tokens_per_s"]["value"] > 0


def test_a_traced_run_reports_the_new_metrics(root, monkeypatch):
    """Off the chip the harness has no peak table's row, the trace no
    program runs and no kernel events (the kernel is interpreted): give
    the run the v5e's peaks and stand-in times, and see the shares come
    out of the traced run's own counters, above 0."""
    import chipbench.harness as harness
    import chipbench.trace as trace

    monkeypatch.setattr(trace.TraceReduction, "module_runs",
                        lambda self, pattern, device=0: [2e-3, 3e-3, 4e-3])
    real_init = trace.TraceReduction.__init__

    def with_kernel(self, devices, spans):
        real_init(self, devices, spans)
        lo, _hi = self.window
        devices[0].ops.append(
            ("%retention_step.3 = (f32[]) custom-call()", lo, lo + 1e-4))

    monkeypatch.setattr(trace.TraceReduction, "__init__", with_kernel)
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


def test_the_window_counts_every_valid_update(root, monkeypatch):
    """``decode.retention.updates`` over a window is sequences x layers x
    the decode steps that were no bubble, and the state's bytes are the
    ring's own."""
    import importlib.util
    path = os.path.join(root, "chipbench", "drivers",
                        "batch_decode_retention.py")
    spec = importlib.util.spec_from_file_location("drv_ret_test", path)
    drv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(drv)
    cell = Manifest(root).cell(CELL)
    ctx = types.SimpleNamespace(
        cell=cell, seed=5, devices=[None], trace=True,
        span=lambda name: __import__("contextlib").nullcontext())
    state = drv.setup(ctx)
    out = drv.measure(state, 0.3, ctx)
    c = out["counters"]
    steps = c["decode.retention.updates"] / (4 * 2)
    assert steps == int(steps) and steps > 0
    assert c["retention_state_bytes"] == 2 * 4 * 2 * 192 * 17 * 4
    ok, detail = drv.check(state, ctx)
    assert ok and detail["state_rel_err"] < 1e-4 < drv.STATE_TOL_FIRST
    assert len(detail["state_rel_err_by_layer"]) == 2
    assert detail["long_memory_rel_err"] < 1e-4 < drv.MEMORY_TOL


def test_the_state_check_reads_what_the_decode_steps_wrote(root):
    """The state is read back behind the prefill *and* decode steps, and
    held to the reference over the prompt and the tokens fed back: with
    the prompt alone the comparison fails."""
    from chipbench.drivers import batch_decode_retention as drv
    cell = Manifest(root).cell(CELL)
    ctx = types.SimpleNamespace(
        cell=cell, seed=6, devices=[None], trace=False,
        span=lambda name: __import__("contextlib").nullcontext())
    state = drv.setup(ctx)
    tr = cell.traffic
    ids, got = drv.decoded_states(state["dec"], state["prompts"], 2, tr)
    steps = min(drv.STATE_STEPS, tr["new_tokens"] - 1)
    assert ids.shape == (2, tr["prompt_len"] + steps) and steps > 0
    ref = cell.config["reference"]
    assert max(drv.state_errors(got, state["params"], ids, ref)) < 1e-4
    assert min(drv.state_errors(got, state["params"],
                                ids[:, :tr["prompt_len"]], ref)) > 0.1


@pytest.mark.parametrize("groups", [None, 1])
def test_the_long_memory_probe_tells_a_bfloat16_state_apart(groups):
    """The probe drives the format's own step under decays near 1: in
    float32 it agrees with the reference to rounding, with the state
    rounded to bfloat16 after every step it misses ``MEMORY_TOL`` by an
    order of magnitude or more."""
    import jax.numpy as jnp
    from chipbench.drivers import batch_decode_retention as drv
    from chipbench.reference import brumby as ref
    from defer_tpu.ops.retention import RetentionFormat

    fmt = RetentionFormat(2, 16, groups=groups)
    sound = drv.long_memory_error(fmt, 4, 2 ** 31 + 5, ref, steps=1024)
    narrow = drv.long_memory_error(fmt, 4, 2 ** 31 + 5, ref, steps=1024,
                                   held=jnp.bfloat16)
    assert set(sound) == {"y", "S", "z"}
    assert max(sound.values()) < drv.MEMORY_TOL / 10
    assert max(narrow.values()) > 10 * drv.MEMORY_TOL


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.retention.*`` (the parent) or
    off the chip a reader gives None and does not raise."""
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS)
    mf = Manifest()
    for name in NEW:
        assert mf.reader(name).read(run) is None


def test_decode_step_needs_against_a_hand_count():
    """8 layers, the head and the state of 16 sequences: layer weights
    5.29 GB + head 1.56 + the state read and written 2 x 4.36 = 15.58
    GB, 19.0 ms at 819 GB/s; the state is 56% of it."""
    flops, nbytes = rr.brumby_decode_step_needs(**CELL_ARGS)
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408
    assert layer == rr.brumby_layer_params(5120, 40, 8, 17408)
    assert round(layer / 1e6, 1) == 330.3
    weights, head = 8 * layer * 2, 5120 * 151936 * 2
    assert round(weights / 1e9, 2) == 5.29 and round(head / 1e9, 2) == 1.56
    assert round(STATE / 1e9, 2) == 4.36
    assert STATE == rr.needed_state_bytes(n_layer=8, rows=16, n_kv=8,
                                          head_dim=128)
    assert nbytes == pytest.approx(weights + head + 2 * STATE
                                   + 16 * 151936 * 4)
    assert round(2 * STATE / nbytes, 2) == 0.56
    assert round(1e3 * nbytes / 819e9, 1) == 19.0
    kernel = 16 * 2 * 8256 * 128 * (8 + 40)
    assert flops == pytest.approx(16 * 2 * (8 * layer + 5120 * 151936)
                                  + 8 * kernel)
    assert rr.state_rows(128) == 8256


def test_a_fatter_layout_is_refused_and_cannot_raise_a_share():
    """The need is the configuration's: what the program holds is only
    held against it.  The tiled square (8704 rows, 1.054 of the need)
    passes; the full outer product (16384) is refused; a program with
    no gauge is not judged."""
    rr.check_held(HELD, STATE, 128)
    rr.check_held(None, STATE, 128)
    assert rr.held_over_needed(128) == pytest.approx(1.0595, abs=1e-4)
    with pytest.raises(ValueError, match="1.98"):
        rr.check_held(HELD * 16384 / 8704, STATE, 128)


def test_kernel_and_prefill_needs_against_a_hand_count():
    """One kernel call moves a layer's ``S`` twice (0.541 GB each way,
    1.32 ms at the memory peak); the prefill is 90 TFLOP, 0.46 s at the
    matrix peak: matrices 86.6, the attention form 1.4, the state's
    build 2.2."""
    flops, nbytes = rr.retention_step_needs(rows=16, n_head=40, n_kv=8,
                                            head_dim=128)
    s_bytes = 16 * 8 * 8256 * 128 * 4
    assert nbytes == pytest.approx(2 * s_bytes)
    assert round(1e3 * nbytes / 819e9, 2) == 1.32
    assert flops == pytest.approx(16 * 2 * 8256 * 128 * 48)
    args = {k: v for k, v in CELL_ARGS.items()}
    flops, _ = rr.brumby_prefill_needs(prompt_len=1024, **args)
    tokens = 16 * 1024
    matrices = 8 * tokens * 2 * rr.brumby_layer_params(5120, 40, 8, 17408)
    attention = 8 * tokens * 40 * 2 * 1024 * 128
    build = 8 * tokens * 8 * 2 * 8256 * 129
    head = 16 * 2 * 5120 * 151936
    assert round(matrices / 1e12, 1) == 86.6
    assert round(attention / 1e12, 1) == 1.4
    assert round(build / 1e12, 1) == 2.2
    assert flops == pytest.approx(matrices + attention + build + head)
    assert round(flops / 197e12, 2) == 0.46


def test_weights_made_a_node_at_a_time_are_the_initialisers_own():
    """The driver draws a node at a time (the whole tree in float32
    passes the one-chip machine's host memory): the leaves are
    ``graph.init``'s own, the embedding scaled by the configuration's
    gain, everything cast."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.drivers import batch_decode_retention as drv
    from defer_tpu import models

    graph = models.brumby(**ARGS)
    seed = 2 ** 31 + 77
    got = drv.make_weights(graph, seed, jnp.bfloat16,
                           {"embeddings/wte": 50.0})
    want = graph.init(jax.random.key(seed % (2 ** 31 - 1)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got), strict=True):
        name = "/".join(k.key for k in path)
        gain = 50.0 if name == "embeddings/wte" else 1.0
        assert b.dtype == jnp.bfloat16 and isinstance(b, np.ndarray)
        np.testing.assert_array_equal(
            b, np.asarray((a * gain).astype(jnp.bfloat16)), err_msg=name)
