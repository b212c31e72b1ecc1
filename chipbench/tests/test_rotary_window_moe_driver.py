"""The window-and-full, two-rotation cell's driver, readers and counts
at a tiny preset on the CPU, through the harness; its two probes at the
cell's kind of geometry (8 queries a KV head: joined buffers, bfloat16);
and ``roofline_rotary_window_moe`` against counts made by hand."""

import importlib
import importlib.util
import json
import os
import time
import types

import numpy as np
import pytest

import tiny
from chipbench import roofline_rotary_window_moe as rr
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

PATTERN = ["sliding_attention"] * 3 + ["full_attention"]
ARGS = {"num_layers": 8, "hidden": 64, "heads": 8, "kv_heads": 4,
        "head_dim": 32, "seq_len": 64, "vocab": 211, "num_experts": 8,
        "experts_per_tok": 2, "expert_hidden": 32, "layer_types": PATTERN,
        "window": 8, "rope_theta": 10000.0, "rope_factor": 4.0,
        "rope_original": 32, "beta_fast": 2.0, "beta_slow": 0.5,
        "rms_eps": 1e-06}
YARN = {"factor": 4.0, "original": 32, "beta_fast": 2.0, "beta_slow": 0.5}
CONFIG = {"model_args": ARGS,
          "init_gain": {"embeddings/wte": 50.0, "router/w": 2.0},
          "reference": {"module": "chipbench.reference.mellum",
                        "args": {"n_layer": 8, "n_head": 8, "n_kv": 4,
                                 "head_dim": 32, "top_k": 2,
                                 "layer_types": PATTERN, "window": 8,
                                 "eps": 1e-06, "theta": 10000.0,
                                 "yarn": YARN}}}
TRAFFIC = {"driver": "batch_decode_rotary_window_moe", "batch": 4,
           "prompt_len": 20, "new_tokens": 28, "token_chunk": 2,
           "max_len": 48, "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "check_tokens": 12, "trace_seconds": 0.5}
CELL = "rotary_window_moe_tiny"
SHARED = ("tokens_per_s", "decode_step_ms", "decode_chunk_ms",
          "decoder_launch_ms", "decode_device_idle_share",
          "decode_prefill_ms", "decode_host_serial_ms",
          "prefill_flash_live_share")
NEW = ("rotary_window_moe_decode_step_roofline",
       "rotary_window_moe_prefill_roofline", "full_attend_kernel_roofline",
       "band_flash_kernel_roofline", "full_rows_step_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: the real cell's model, as its configuration file gives it
REAL = Manifest().cell("mellum2_batch_decode").config["model_args"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_rwmoe_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "mellum-tiny", CONFIG),
                            ("traffic", "batch_rwmoe_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "mellum-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/mellum-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "mellum-tiny", "traffic": "batch_rwmoe_tiny",
        "chips": 1, "why": "tiny preset for the CPU tests"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in SHARED + NEW:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_real_manifest_gives_the_cell_its_files_and_metrics():
    m = Manifest()
    cell = m.cell("mellum2_batch_decode")
    assert set(NEW) | set(SHARED[1:]) <= set(cell.per_layer)
    assert {"decode_idle_wake_ms", "decode_idle_launch_ms",
            "decode_upload_ms", "decode_pause_share",
            "weights_relaid_leaves"} <= set(cell.per_layer)
    assert cell.end_to_end == ("tokens_per_s", "setup_s")
    assert cell.chips == 1
    assert cell.traffic["driver"] == "batch_decode_rotary_window_moe"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk", "max_len",
        "compute_dtype", "kv_cache", "check_sequences")} == {
        "batch": 16, "prompt_len": 24576, "new_tokens": 4096,
        "token_chunk": 32, "max_len": 28672, "compute_dtype": "bfloat16",
        "kv_cache": "buffer", "check_sequences": 2}
    # every published number under its own key, the depth alone reduced
    published = cell.config["published"]
    assert (published["num_hidden_layers"],
            cell.config["num_hidden_layers"]) == (28, 8)
    assert all(cell.config[k] == v for k, v in published.items()
               if k != "num_hidden_layers")
    assert list(cell.config["reduced"]) == ["num_hidden_layers"]
    entry = next(c for c in m.doc["configs"]
                 if c["name"] == "mellum2-12b-a2.5b-8l")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cell.config["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
        "blob/main/config.json")
    assert {"qk_norm", "rope_pairs", "intermediate_size", "mtp", "weights",
            "init_gain", "deployment"} <= set(cell.config["assumed"])
    a = cell.config["model_args"]
    y = published["rope_parameters"]["full_attention"]
    assert (a["hidden"], a["heads"], a["kv_heads"], a["head_dim"],
            a["expert_hidden"], a["experts_per_tok"], a["num_experts"],
            a["window"], a["rope_theta"], a["vocab"], a["seq_len"],
            a["rope_factor"], a["rope_original"], a["beta_fast"],
            a["beta_slow"], a["attention_factor"], a["rms_eps"]) == (
        2304, 32, 4, 128, 896, 8, 64, 1024, 500000.0, 98304, 131072,
        y["factor"], y["original_max_position_embeddings"], y["beta_fast"],
        y["beta_slow"], y["attention_factor"], published["rms_norm_eps"])
    assert a["layer_types"] == published["layer_types"][:4]
    assert published["layer_types"] == a["layer_types"] * 7
    ref_args = cell.config["reference"]["args"]
    assert ref_args["yarn"] == {
        "factor": 16.0, "original": 8192, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.2772588722239782}
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"] == ["mellum2_batch_decode"]
        assert entry["unit"] == "%"
    four = [w for w in m.doc["workloads"] if w["chips"] == 4]
    assert len(m.doc["workloads"]) == 11 and len(four) == 1


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the model-configs catalog is not on this machine")
def test_published_is_the_catalogs_config_whole():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    cfg = Manifest().cell("mellum2_batch_decode").config
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]


def test_an_untraced_run_checks_tokens_router_window_and_rotation(root):
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
    give the run the v5e's peaks and stand-in times, and see the five
    shares come out of the traced run's own counters, above 0."""
    import chipbench.harness as harness
    import chipbench.trace as trace

    monkeypatch.setattr(trace.TraceReduction, "module_runs",
                        lambda self, pattern, device=0: [2e-3, 3e-3, 4e-3])
    real_init = trace.TraceReduction.__init__

    def with_kernels(self, devices, spans):
        real_init(self, devices, spans)
        lo, _hi = self.window
        for name in ("kv_attend_full.3", "kv_attend_window.4",
                     "flash_band.7", "flash_grouped.2"):
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
    # (``prefill_flash_live_share`` stays silent here: off the chip the
    # blocks' prompts take the plain attention, not the flash kernels)
    assert set(NEW) | {"decode_step_ms", "decode_prefill_ms"} \
        <= set(doc["metrics"]) <= per_layer
    for name in NEW:
        m = doc["metrics"][name]
        assert m["unit"] == "%" and 0 < m["value"] < 100


def _driver(root):
    path = os.path.join(root, "chipbench", "drivers",
                        "batch_decode_rotary_window_moe.py")
    spec = importlib.util.spec_from_file_location("drv_rwmoe_test", path)
    drv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(drv)
    return drv


def test_the_window_counts_assignments_rows_and_the_buffers_bytes(root):
    drv = _driver(root)
    cell = Manifest(root).cell(CELL)
    ctx = types.SimpleNamespace(
        cell=cell, seed=5, devices=[None], trace=True,
        span=lambda name: __import__("contextlib").nullcontext())
    state = drv.setup(ctx)
    c = drv.measure(state, 0.3, ctx)["counters"]
    steps = c["decode.moe.assignments"] / (4 * 2 * 8)  # rows, k, layers
    assert steps == int(steps) and steps > 0
    assert 0 < c["experts_hit_share"] <= 1
    # 2 groups (one scratch) x 4 rows x (8 + 1 | 48 + 1) positions x 4 KV
    # heads x 32 x k and v x f32
    assert c["cache_window_bytes"] == 6 * 2 * 4 * 9 * 4 * 32 * 2 * 4
    assert c["cache_full_bytes"] == 2 * 2 * 4 * 49 * 4 * 32 * 2 * 4
    assert c["cache_window_positions"] == 8
    # the newest step's rows: p in the 2 full layers, 8 in the 6 others
    p = c["cache_full_rows_read"] / (2 * 4)
    assert 20 < p <= 47 and c["cache_window_rows_read"] == 6 * 4 * 8
    assert c["prefill_piece_rows"] == 4 and c["max_len"] == 48
    assert c["prefill_flash_grid_steps"] == c["prefill_flash_live_steps"]
    ok, detail = drv.check(state, ctx)
    assert ok, detail
    assert detail["worst_logit_gap_share"] < 1e-3 < drv.GAP_TOL
    assert detail["router_agreement_share"] > 0.99 > drv.ROUTER_TOL
    assert len(detail["router_agreement_by_layer"]) == 8
    assert detail["window_probe_rel_err"] < 1e-4 < drv.WINDOW_TOL
    assert set(detail["window_probe_rel_err_by_part"]) == {
        "flash_window", "flash_full", "decode_window", "decode_full"}
    assert detail["rotation_probe_rms_err"] < 1e-4 < drv.ROTATION_TOL
    assert set(detail["rotation_probe_rms_err_by_kind"]) == {"window",
                                                             "full"}
    run = types.SimpleNamespace(trace=None, counters=c, peaks=PEAKS,
                                cell=cell)
    share = Manifest(root).reader("full_rows_step_share").read(run)
    parts = rr.step_bytes_by_part(
        ARGS, rows=4, positions=p,
        experts_hit_share=c["experts_hit_share"], weight_bytes=4, kv_bytes=4)
    assert share == pytest.approx(
        100 * parts["full_rows"] / sum(parts.values()))


def test_the_window_probe_at_four_kv_heads_of_eight_queries(root):
    """(c) at the cell's kind of geometry — 4 KV heads, 8 queries each,
    joined bfloat16 buffers — passes, and fails a window off by one
    either way, a decode row written one row off and float8 inputs."""
    import jax.numpy as jnp
    drv = _driver(root)
    ref = importlib.import_module("chipbench.reference.mellum")
    kw = dict(heads=32, kv=4, hd=128, window=64, dtype=jnp.bfloat16,
              ref=ref, sequences=1)
    good = drv.window_probe(3, **kw)
    assert max(good.values()) < drv.WINDOW_TOL
    for wrong in (63, 65):
        bad = drv.window_probe(3, ref_window=wrong, **kw)
        assert min(bad["flash_window"], bad["decode_window"]) \
            > drv.WINDOW_TOL
    assert drv.window_probe(3, slot_shift=1, **kw)["decode_window"] \
        > drv.WINDOW_TOL
    low = drv.window_probe(3, inputs=jnp.float8_e4m3fn, **kw)
    assert min(low.values()) > max(3 * max(good.values()), drv.WINDOW_TOL)


@pytest.mark.parametrize("kind", ["window", "full"])
def test_the_rotation_probe_at_the_published_tables(root, kind):
    """(d) with the published head (128 wide, theta 500000, YaRN by 16
    from 8192, the config's factor), bfloat16, joined buffers, at 2048
    positions: the program passes; float8 inputs (in the full layer,
    whose reading decides) and each fault of the kind's rotation read
    over the limit."""
    import jax.numpy as jnp
    from defer_tpu.models.mellum import MellumBlock
    from defer_tpu.models.rotary import yarn_inv_freq
    drv = _driver(root)
    ref = importlib.import_module("chipbench.reference.mellum")
    yarn = Manifest().cell("mellum2_batch_decode").config[
        "reference"]["args"]["yarn"]
    if kind == "full":
        op = MellumBlock(32, 8, 2, 32, num_kv_heads=4, head_dim=128,
                         rope_freqs=yarn_inv_freq(128, 5e5, 16.0, 8192),
                         rope_factor=yarn["attention_factor"])
    else:
        op = MellumBlock(32, 8, 2, 32, num_kv_heads=4, head_dim=128,
                         window=256)
    freqs, c = ref.layer_rotation(
        "full_attention" if kind == "full" else "sliding_attention",
        head_dim=128, theta=5e5, yarn=yarn)

    def probe(**control):
        kw = dict(freqs=freqs, c=c)
        kw.update(control)
        return drv.rotation_probe(5, op, d_model=256, positions=2048,
                                  dtype=jnp.bfloat16, ref=ref, steps=8, **kw)

    good = probe()
    assert good < drv.ROTATION_TOL
    # float8 inputs: the full layer's reading is the one a run is
    # judged by (the larger of the two kinds'); the window layer's
    # stands clear of the program's own and no more
    low = probe(inputs=jnp.float8_e4m3fn)
    assert low > 3 * good
    faults = {"interleaved": dict(pairing="interleaved")}
    if kind == "full":
        assert low > drv.ROTATION_TOL
        faults.update({
            "plain table": dict(freqs=ref.plain_frequencies(128, 5e5)),
            "ramp + 1": dict(freqs=ref.yarn_frequencies(
                128, 5e5, 16.0, 8192, 32.0, 1.0, shift=1)),
            "ramp - 1": dict(freqs=ref.yarn_frequencies(
                128, 5e5, 16.0, 8192, 32.0, 1.0, shift=-1)),
            "no factor": dict(c=1.0)})
    for name, control in faults.items():
        assert probe(**control) > max(drv.ROTATION_TOL, 3 * good), name


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.cache.*_rows_read`` gauges (the
    parent's) or off the chip a reader gives None and does not raise."""
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS,
                                cell=types.SimpleNamespace(chips=1))
    mf = Manifest()
    for name in NEW:
        assert mf.reader(name).read(run) is None
    # the parent's gauges read 0
    run.counters = {"cache_full_rows_read": 0.0, "experts_hit_share": 0.8}
    assert mf.reader("full_rows_step_share").read(run) is None


def _run(counters, ops=(), runs=(1.0,)):
    lo, hi = 0.0, 100.0
    dev = types.SimpleNamespace(ops=[(n, s, e) for n, s, e in ops])
    trace = types.SimpleNamespace(
        window=(lo, hi), devices=[dev],
        module_runs=lambda pattern, device=0: list(runs))
    return types.SimpleNamespace(trace=trace, counters=counters, peaks=PEAKS,
                                 cell=types.SimpleNamespace(chips=1))


def _counters(**over):
    c = {"model_args": REAL, "rows": 16, "max_len": 28672,
         "live_positions": 26600.0, "experts_hit_share": 55.5 / 64,
         "weight_bytes": 2, "kv_bytes": 2, "steps_per_reading": 32,
         "prefill_tokens": 16 * 24576, "prefill_piece_rows": 1,
         "cache_window_bytes": 2 * 6 * 16 * 1040 * 2048,
         "cache_full_bytes": 2 * 2 * 16 * 28688 * 2048,
         "cache_window_positions": 1024,
         "cache_full_rows_read": 2 * 16 * 26600.0,
         "cache_window_rows_read": 6 * 16 * 1024.0}
    c.update(over)
    return c


def test_a_reader_raises_on_a_share_over_100_and_on_a_fat_layout():
    mf = Manifest()
    step = mf.reader("rotary_window_moe_decode_step_roofline")
    # 32 steps in 0.5 s: 15.6 ms a step, ~2/3 of the bytes' 10.0 ms
    assert 58 < step.read(_run(_counters(), runs=(0.5,))) < 70
    with pytest.raises(ValueError, match="too high"):
        step.read(_run(_counters(), runs=(0.25,)))
    # a layout that holds the window layers as full ones is refused
    with pytest.raises(ValueError, match="holds"):
        step.read(_run(_counters(
            cache_window_bytes=2 * 6 * 16 * 28688 * 2048)))
    attend = mf.reader("full_attend_kernel_roofline")
    ops = [("%kv_attend_full.1 = x", 1.0, 1.0 + 1500e-6),
           ("%kv_attend_full.2 = x", 2.0, 2.0 + 1700e-6),
           ("%kv_attend_window.1 = x", 3.0, 3.0 + 100e-6)]
    # 16 x 26600 rows x 2048 B = 0.872 GB: 1064 us; the mean call 1600
    assert attend.read(_run(_counters(), ops=ops)) == pytest.approx(
        100 * 1064.5 / 1600, rel=0.01)
    with pytest.raises(ValueError, match="too high"):
        attend.read(_run(_counters(),
                         ops=[("%kv_attend_full.1 = x", 1.0, 1.0005)]))
    # the window layers' calls alone are not the full layers'
    assert attend.read(_run(_counters(), ops=ops[2:])) is None
    flash = mf.reader("band_flash_kernel_roofline")
    ops = [("%flash_band.1 = x", 1.0, 1.008),
           ("%flash_grouped.1 = x", 2, 2.1)]
    # one sequence's band: 4 x 32 x 128 x 24.64 M pairs = 0.404 TFLOP
    assert flash.read(_run(_counters(), ops=ops)) == pytest.approx(
        100 * 0.4037e12 / 197e12 / 0.008, rel=0.01)
    with pytest.raises(ValueError, match="too high"):
        flash.read(_run(_counters(),
                        ops=[("%flash_band.1 = x", 1.0, 1.001)]))
    prefill = mf.reader("rotary_window_moe_prefill_roofline")
    assert 25 < prefill.read(_run(_counters(), runs=(10.0,))) < 40
    with pytest.raises(ValueError, match="too high"):
        prefill.read(_run(_counters(), runs=(3.0,)))
    share = mf.reader("full_rows_step_share")
    assert share.read(_run(_counters())) == pytest.approx(21.2, abs=0.3)


def test_decode_step_needs_against_a_hand_count():
    """ISSUE 55's sizing at ~26.6k positions and 55.5 of 64 experts hit:
    experts 5.50 GB, attention matrices 0.34, head 0.45, the full
    layers' rows 1.74, the window layers' 0.20: 8.2 GB, a fifth of it
    the two full layers' rows."""
    _flops, nbytes = rr.decode_step_needs(
        REAL, rows=16, positions=26600, experts_hit_share=55.5 / 64,
        weight_bytes=2, kv_bytes=2)
    expert = 3 * 2304 * 896 * 2
    experts = 8 * 55.5 * expert
    attn = 8 * 2 * 2304 * 128 * (32 + 4) * 2
    router = 8 * 2304 * 64 * 2
    full = 2 * 16 * 26600 * 2048
    window = 6 * 16 * 1024 * 2048
    head = 2304 * 98304 * 2
    q_and_out = 8 * 16 * 2 * 4096 * 2
    logits = 16 * 98304 * 4
    assert nbytes == pytest.approx(experts + attn + router + full + window
                                   + head + q_and_out + logits)
    assert [round(x / 1e9, 2) for x in (experts, attn, head, full, window)] \
        == [5.5, 0.34, 0.45, 1.74, 0.2]
    assert round(nbytes / 1e9, 1) == 8.2
    assert full / (full + window) == pytest.approx(0.9, abs=0.005)
    assert rr.layer_kinds(REAL) == (6, 2)
    assert rr.row_bytes(REAL, 2) == 2048
    parts = rr.step_bytes_by_part(
        REAL, rows=16, positions=26600, experts_hit_share=55.5 / 64,
        weight_bytes=2, kv_bytes=2)
    assert sum(parts.values()) == pytest.approx(nbytes)
    assert (parts["full_rows"], parts["window_rows"]) == (full, window)
    # window rows are capped at the window, full rows are not
    early = rr.step_bytes_by_part(
        REAL, rows=16, positions=512, experts_hit_share=1.0,
        weight_bytes=2, kv_bytes=2)
    assert early["window_rows"] == 3 * early["full_rows"]


def test_prefill_needs_against_a_hand_count():
    """16 x 24576 tokens through 8 layers: the matrices 1.13 GFLOP a
    token (0.445 PFLOP), the two full layers' causal attention 0.40
    GFLOP a token, the six banded ones' 0.10: 0.64 PFLOP; a band is 8%
    of the causal triangle."""
    flops, nbytes = rr.prefill_needs(REAL, rows=16, prompt_len=24576,
                                     weight_bytes=2, kv_bytes=2)
    tokens = 16 * 24576
    attn = 2 * 2304 * 128 * (32 + 4)
    expert = 3 * 2304 * 896
    mats = 8 * tokens * 2 * (attn + 2304 * 64 + 8 * expert)
    full = rr.band_flops(REAL, rows=16, prompt_len=24576, window=None)
    band = rr.band_flops(REAL, rows=16, prompt_len=24576, window=1024)
    head = 16 * 2 * 2304 * 98304
    assert flops == pytest.approx(mats + 6 * band + 2 * full + head)
    assert mats / tokens / 1e9 == pytest.approx(1.13, abs=0.01)
    assert 2 * full / tokens / 1e9 == pytest.approx(0.40, abs=0.005)
    assert 6 * band / tokens / 1e9 == pytest.approx(0.10, abs=0.005)
    assert round(flops / 1e15, 2) == 0.64
    assert band / full == pytest.approx(0.0816, abs=0.001)
    # bytes: every matrix once (8 layers 6.68 GB and the head 0.45: the
    # embedding's rows are gathered, a row a token), the rows written once
    assert nbytes / 1e9 == pytest.approx(
        6.684 + 0.453 + 16 * (6 * 1024 + 2 * 24576) * 2048 / 1e9
        + 16 * 98304 * 4 / 1e9, abs=0.01)
    np.testing.assert_allclose(
        rr.needed_cache_bytes(REAL, rows=16, max_len=28672, kv_bytes=2),
        (6 * 16 * 1024 * 2048, 2 * 16 * 28672 * 2048))
    call_flops, call_bytes = rr.band_call_needs(
        REAL, rows=1, prompt_len=24576, window=1024, kv_bytes=2)
    assert call_flops == band / 16
    assert call_bytes == 24576 * (2 * 4096 * 2 + 2048)
