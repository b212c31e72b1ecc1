"""The window-and-full, held-share cell's driver, readers and counts at
a tiny preset on the CPU, through the harness; and
``roofline_window_moe`` against counts made by hand."""

import json
import os
import time
import types

import numpy as np
import pytest

import tiny
from chipbench import roofline_window_moe as rw
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

PATTERN = ["sliding_attention"] * 3 + ["full_attention"]
ARGS = {"num_layers": 4, "hidden": 64, "heads": 8, "kv_heads": 2,
        "head_dim": 8, "seq_len": 64, "vocab": 211, "num_experts": 16,
        "experts_per_tok": 4, "expert_hidden": 32, "num_shared": 2,
        "layer_types": PATTERN, "window": 8, "experts_held": [0, 4],
        "rope_theta": 50000.0, "ln_eps": 1e-05, "logit_scale": 1.0}
CONFIG = {"model_args": ARGS, "init_gain": {"router/w": 2.0},
          "reference": {"module": "chipbench.reference.cohere2_moe",
                        "args": {"n_layer": 4, "n_head": 8, "n_kv": 2,
                                 "head_dim": 8, "top_k": 4, "n_shared": 2,
                                 "layer_types": PATTERN, "window": 8,
                                 "held": [0, 4], "eps": 1e-05,
                                 "theta": 50000.0, "logit_scale": 1.0}}}
TRAFFIC = {"driver": "batch_decode_window_moe", "batch": 4, "prompt_len": 12,
           "new_tokens": 18, "token_chunk": 2, "max_len": 32,
           "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "check_tokens": 12, "trace_seconds": 0.5}
CELL = "window_moe_tiny"
SHARED = ("tokens_per_s", "decode_step_ms", "decode_chunk_ms",
          "decoder_launch_ms", "decode_device_idle_share",
          "decode_prefill_ms", "decode_host_serial_ms")
NEW = ("window_moe_decode_step_roofline", "window_attend_kernel_roofline",
       "window_moe_prefill_roofline", "window_flash_kernel_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: the real cell's model, as its configuration file gives it
REAL = Manifest().cell("commandaplus_batch_decode").config["model_args"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_wmoe_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "cohere-tiny", CONFIG),
                            ("traffic", "batch_wmoe_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "cohere-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/cohere-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "cohere-tiny", "traffic": "batch_wmoe_tiny",
        "chips": 1, "why": "tiny preset for the CPU tests"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in SHARED + NEW:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_real_manifest_gives_the_cell_its_files_and_metrics():
    m = Manifest()
    cell = m.cell("commandaplus_batch_decode")
    assert set(NEW) | set(SHARED[1:]) <= set(cell.per_layer)
    # the ring's idle readers of PR 36, which this count kept the cell
    # off until PR 52, and that PR's two counters: 10 + 4 + 2
    assert {"decode_idle_wake_ms", "decode_idle_launch_ms",
            "decode_upload_ms", "decode_pause_share",
            "weights_relaid_leaves", "prefill_flash_live_share"} \
        <= set(cell.per_layer)
    assert len(cell.per_layer) >= 16
    assert cell.end_to_end == ("tokens_per_s", "setup_s")
    assert cell.chips == 1
    assert cell.traffic["driver"] == "batch_decode_window_moe"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk", "max_len",
        "compute_dtype", "kv_cache", "check_sequences")} == {
        "batch": 16, "prompt_len": 8192, "new_tokens": 4096,
        "token_chunk": 64, "max_len": 12288, "compute_dtype": "bfloat16",
        "kv_cache": "buffer", "check_sequences": 2}
    # every published number under its own key; depth, held experts and
    # vocabulary rows alone reduced
    published = cell.config["published"]
    reduced = {"num_hidden_layers": (32, 4), "num_experts": (128, 16),
               "vocab_size": (262144, 32768)}
    for key, (was, now) in reduced.items():
        assert (published[key], cell.config[key]) == (was, now)
    assert all(cell.config[k] == v for k, v in published.items()
               if k not in reduced)
    assert list(cell.config["reduced"]) == list(reduced)
    a = cell.config["model_args"]
    assert (a["hidden"], a["heads"], a["kv_heads"], a["head_dim"],
            a["expert_hidden"], a["experts_per_tok"], a["num_experts"],
            a["num_shared"], a["window"], a["rope_theta"], a["vocab"],
            a["experts_held"]) == (
        4096, 128, 8, 128, 4096, 8, 128, 4, 4096, 50000.0, 32768, [0, 16])
    assert a["layer_types"] == published["layer_types"][:4]
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"] == ["commandaplus_batch_decode"]
    four = [w for w in m.doc["workloads"] if w["chips"] == 4]
    assert len(m.doc["workloads"]) >= 6 and len(four) == 1
    assert "commandaplus_batch_decode" in m.workload_names()[:6]


def test_an_untraced_run_checks_tokens_router_and_window(root):
    doc = run_cell(workload=CELL, seed=2 ** 31 + 4321, seconds=1.0,
                   trace=False, t_start=time.perf_counter(), root=root,
                   require_tpu=False)
    json.dumps(doc)
    assert doc["correct"] is True and doc["failed"] == 0
    assert set(doc["metrics"]) == {"tokens_per_s", "setup_s"}
    assert doc["metrics"]["tokens_per_s"]["value"] > 0


def test_a_traced_run_reports_the_new_metrics(root, monkeypatch):
    """Off the chip the harness has no peak table's row, the trace no
    program runs and no kernel events (the kernels are interpreted):
    give the run the v5e's peaks and stand-in times, and see the four
    shares come out of the traced run's own counters, above 0."""
    import chipbench.harness as harness
    import chipbench.trace as trace

    monkeypatch.setattr(trace.TraceReduction, "module_runs",
                        lambda self, pattern, device=0: [2e-3, 3e-3, 4e-3])
    real_init = trace.TraceReduction.__init__

    def with_kernels(self, devices, spans):
        real_init(self, devices, spans)
        lo, _hi = self.window
        for name in ("kv_attend.3", "flash_band.7", "flash_grouped.2"):
            devices[0].ops.append(
                (f"%{name} = (f32[]) custom-call()", lo, lo + 1e-4))

    monkeypatch.setattr(trace.TraceReduction, "__init__", with_kernels)
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


def _driver(root):
    import importlib.util
    path = os.path.join(root, "chipbench", "drivers",
                        "batch_decode_window_moe.py")
    spec = importlib.util.spec_from_file_location("drv_wmoe_test", path)
    drv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(drv)
    return drv


def test_the_window_counts_assignments_and_the_buffers_bytes(root):
    drv = _driver(root)
    cell = Manifest(root).cell(CELL)
    ctx = types.SimpleNamespace(
        cell=cell, seed=5, devices=[None], trace=True,
        span=lambda name: __import__("contextlib").nullcontext())
    state = drv.setup(ctx)
    # the head is the embedding's table
    assert state["params"]["lm_head"]["w"] is state["params"][
        "embeddings"]["wte"]
    c = drv.measure(state, 0.3, ctx)["counters"]
    steps = c["decode.moe.assignments"] / (4 * 4 * 4)  # rows, k, layers
    assert steps == int(steps) and steps > 0
    assert 0 < c["decode.moe.held_assignments"] < c["decode.moe.assignments"]
    assert 0 < c["held_share"] < 1 and 0 < c["experts_hit_share"] <= 1
    # 2 groups (one scratch) x 4 rows x (8 + 1 | 32 + 1) positions x 2 KV
    # heads x 8 x k and v x f32
    assert c["cache_window_bytes"] == 3 * 2 * 4 * 9 * 2 * 8 * 2 * 4
    assert c["cache_full_bytes"] == 2 * 4 * 33 * 2 * 8 * 2 * 4
    assert c["cache_window_positions"] == 8
    assert c["prefill_piece_rows"] == 4 and c["max_len"] == 32
    ok, detail = drv.check(state, ctx)
    assert ok, detail
    assert detail["worst_logit_gap_share"] < 1e-3 < drv.GAP_TOL
    assert detail["router_agreement_share"] > 0.99 > drv.ROUTER_TOL
    assert len(detail["router_agreement_by_layer"]) == 4
    assert detail["window_probe_rel_err"] < 1e-4 < drv.PROBE_TOL
    assert set(detail["window_probe_rel_err_by_part"]) == {
        "flash_window", "flash_full", "decode_window", "decode_full"}


@pytest.mark.parametrize("dtype,joined", [("float32", False),
                                          ("bfloat16", True)])
def test_the_window_probe_tells_a_wrong_window_and_a_wrong_row_apart(
        root, dtype, joined):
    """At a small window the probe passes in the cell's own types and
    fails — by every part it touches — a window off by one either way,
    a decode row written one row off, and inputs rounded to float8."""
    import importlib

    import jax.numpy as jnp
    drv = _driver(root)
    ref = importlib.import_module("chipbench.reference.cohere2_moe")
    geometry = dict(heads=32 if joined else 4, kv=2, hd=128, window=64)
    kw = dict(geometry, dtype=jnp.dtype(dtype), ref=ref, sequences=1)
    good = drv.window_probe(3, **kw)
    limit = 1e-4 if dtype == "float32" else drv.PROBE_TOL
    assert max(good.values()) < limit
    for wrong in (63, 65):
        bad = drv.window_probe(3, ref_window=wrong, **kw)
        assert min(bad["flash_window"], bad["decode_window"]) \
            > drv.PROBE_TOL
        assert max(bad["flash_full"], bad["decode_full"]) < limit
    off = drv.window_probe(3, slot_shift=1, **kw)
    assert off["decode_window"] > drv.PROBE_TOL
    low = drv.window_probe(3, inputs=jnp.float8_e4m3fn, **kw)
    assert min(low.values()) > max(3 * max(good.values()), drv.PROBE_TOL)


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.cache.*`` gauges (the parent's)
    or off the chip a reader gives None and does not raise."""
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS,
                                cell=types.SimpleNamespace(chips=1))
    mf = Manifest()
    for name in NEW:
        assert mf.reader(name).read(run) is None


def _run(counters, ops=(), runs=(1.0,)):
    lo, hi = 0.0, 100.0
    dev = types.SimpleNamespace(ops=[(n, s, e) for n, s, e in ops])
    trace = types.SimpleNamespace(
        window=(lo, hi), devices=[dev],
        module_runs=lambda pattern, device=0: list(runs))
    return types.SimpleNamespace(trace=trace, counters=counters, peaks=PEAKS,
                                 cell=types.SimpleNamespace(chips=1))


def _counters(**over):
    row = 2 * 8 * 128 * 2
    c = {"model_args": REAL, "rows": 16, "max_len": 12288,
         "live_positions": 9400.0, "experts_hit_share": 10.3 / 16,
         "held_share": 0.125, "weight_bytes": 2, "kv_bytes": 2,
         "steps_per_reading": 64, "prefill_tokens": 16 * 8192,
         "prefill_piece_rows": 1,
         "cache_window_bytes": 2 * 3 * 16 * 4112 * row,
         "cache_full_bytes": 2 * 16 * 12304 * row,
         "cache_window_positions": 4096}
    c.update(over)
    return c


def test_a_reader_raises_on_a_share_over_100_and_on_a_fat_layout():
    mf = Manifest()
    step = mf.reader("window_moe_decode_step_roofline")
    # 64 steps in 1 s: 15.6 ms a step, ~2/3 of the bytes' 10.5 ms
    assert 60 < step.read(_run(_counters(), runs=(1.0,))) < 75
    with pytest.raises(ValueError, match="too high"):
        step.read(_run(_counters(), runs=(0.5,)))
    # a layout that holds the window layers as full ones is refused
    row = 2 * 8 * 128 * 2
    with pytest.raises(ValueError, match="holds"):
        step.read(_run(_counters(
            cache_window_bytes=2 * 3 * 16 * 12304 * row)))
    attend = mf.reader("window_attend_kernel_roofline")
    ops = [("%kv_attend.1 = x", 1.0, 1.0 + 400e-6)] * 3 \
        + [("%kv_attend.2 = x", 2.0, 2.0 + 900e-6)]
    share = attend.read(_run(_counters(), ops=ops))
    # (3 x 4096 + 9400) / 4 rows x 16 x 4096 B = 0.355 GB: 433 us; the
    # mean call 525 us
    assert share == pytest.approx(100 * 433.5 / 525, rel=0.02)
    with pytest.raises(ValueError, match="too high"):
        attend.read(_run(_counters(),
                         ops=[("%kv_attend.1 = x", 1.0, 1.0 + 100e-6)]))
    flash = mf.reader("window_flash_kernel_roofline")
    ops = [("%flash_band.1 = x", 1.0, 1.3), ("%flash_grouped.1 = x", 2, 2.1)]
    # one sequence's band: 4 x 128 x 128 x 25.2 M pairs = 1.65 TFLOP
    assert flash.read(_run(_counters(), ops=ops)) == pytest.approx(
        100 * 1.65e12 / 197e12 / 0.3, rel=0.01)
    with pytest.raises(ValueError, match="too high"):
        flash.read(_run(_counters(),
                        ops=[("%flash_band.1 = x", 1.0, 1.005)]))
    prefill = mf.reader("window_moe_prefill_roofline")
    assert 20 < prefill.read(_run(_counters(), runs=(10.0,))) < 35
    with pytest.raises(ValueError, match="too high"):
        prefill.read(_run(_counters(), runs=(2.0,)))


def test_decode_step_needs_against_a_hand_count():
    """ISSUE 34's sizing at ~9.4k positions and 10.3 of 16 held experts
    hit: held experts 4.15 GB, shared 1.61, attention matrices 1.14,
    window caches 0.81, the full layer's 0.62, head 0.27: 8.6 GB."""
    _flops, nbytes = rw.decode_step_needs(
        REAL, rows=16, positions=9400, experts_hit_share=10.3 / 16,
        held_share=0.125, weight_bytes=2, kv_bytes=2)
    expert = 3 * 4096 * 4096 * 2
    held = 4 * 10.3 * expert
    shared = 4 * 4 * expert
    attn = 4 * 2 * 4096 * 128 * (128 + 8) * 2
    router = 4 * 4096 * 128 * 2
    window = 3 * 16 * 4096 * 4096
    full = 16 * 9400 * 4096
    head = 4096 * 32768 * 2
    q_and_out = 4 * 16 * 2 * 16384 * 2
    logits = 16 * 32768 * 4
    assert nbytes == pytest.approx(held + shared + attn + router + window
                                   + full + head + q_and_out + logits)
    assert [round(x / 1e9, 2) for x in (held, shared, attn, window, full,
                                        head)] == [4.15, 1.61, 1.14, 0.81,
                                                   0.62, 0.27]
    assert round(nbytes / 1e9, 1) == 8.6
    assert rw.layer_kinds(REAL) == (3, 1) and rw.held_experts(REAL) == 16


def test_prefill_needs_against_a_hand_count():
    """16 x 8192 tokens through 4 layers: the matrices 414 TFLOP (held
    rows only for the routed experts: 1 of a token's 8 choices), banded
    and causal attention 114 TFLOP = 22%: 528 TFLOP; the window layers'
    attention 3/4 of a causal layer's."""
    flops, _ = rw.prefill_needs(REAL, rows=16, prompt_len=8192,
                                held_share=0.125, weight_bytes=2, kv_bytes=2)
    tokens = 16 * 8192
    attn = 2 * 4096 * 128 * (128 + 8)
    expert = 3 * 4096 * 4096
    mats = 4 * tokens * 2 * (attn + 4096 * 128 + 4 * expert + 1 * expert)
    full = rw.band_flops(REAL, rows=16, prompt_len=8192, window=None)
    band = rw.band_flops(REAL, rows=16, prompt_len=8192, window=4096)
    head = 16 * 2 * 4096 * 32768
    assert flops == pytest.approx(mats + 3 * band + full + head)
    assert round(mats / 1e12) == 414
    assert round((3 * band + full) / 1e12) == 114
    assert round(flops / 1e12) == 528
    assert band / full == pytest.approx(0.75, abs=0.001)
    # under the window a band is the causal triangle
    assert rw.band_flops(REAL, rows=1, prompt_len=100, window=4096) \
        == rw.band_flops(REAL, rows=1, prompt_len=100, window=None)
    np.testing.assert_allclose(
        rw.needed_cache_bytes(REAL, rows=16, max_len=12288, kv_bytes=2),
        (3 * 16 * 4096 * 4096, 16 * 12288 * 4096))
