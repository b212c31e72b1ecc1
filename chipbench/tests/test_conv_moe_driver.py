"""The short-convolution / attention hybrid's cell (LFM2-MoE): its
driver, readers and counts at a tiny preset on the CPU, through the
harness; and ``roofline_conv_moe`` against the counts of the issue that
asked for the cell."""

import json
import os
import time
import types

import pytest

import tiny
from chipbench import roofline_conv_moe as rl
from chipbench.harness import run_cell
from chipbench.manifest import Manifest
from chipbench.trace import DeviceTrace, TraceReduction

TYPES = ["conv", "conv", "full_attention", "conv"]
ARGS = {"num_layers": 8, "hidden": 64, "heads": 4, "kv_heads": 2,
        "head_dim": 16, "dense_hidden": 96, "seq_len": 64, "vocab": 211,
        "layer_types": TYPES, "num_experts": 8, "experts_per_tok": 2,
        "expert_hidden": 32, "dense_layers": 2, "d_conv": 3,
        "routed_scale": 1.0, "rope_theta": 1000000.0, "rms_eps": 1e-05}
REF_ARGS = {"layer_types": TYPES, "dense_layers": 2, "n_head": 4, "n_kv": 2,
            "head_dim": 16, "top_k": 2, "routed_scale": 1.0,
            "theta": 1000000.0, "eps": 1e-05}
CONFIG = {"model_args": ARGS, "init_gain": {"router/w": 2.0},
          "reference": {"module": "chipbench.reference.lfm2_moe",
                        "args": REF_ARGS}}
TRAFFIC = {"driver": "batch_decode_conv_moe", "batch": 4, "prompt_len": 11,
           "new_tokens": 16, "token_chunk": 2, "max_len": 32,
           "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "check_tokens": 6, "trace_seconds": 0.5}
CELL = "conv_moe_tiny"
REAL = "lfm2moe_batch_decode"
SHARED = ("tokens_per_s", "decoder_launch_ms", "decode_chunk_ms",
          "decode_step_ms", "decode_device_idle_share", "decode_prefill_ms",
          "decode_host_serial_ms", "decode_idle_wake_ms",
          "decode_idle_launch_ms", "decode_upload_ms", "decode_pause_share",
          "weights_relaid_leaves")
NEW = ("conv_moe_decode_step_roofline", "conv_moe_prefill_roofline",
       "routed_step_kernel_roofline", "routed_rows_per_expert")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def lfm2_args():
    return Manifest().cell(REAL).config["model_args"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_conv_moe_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "lfm2-tiny", CONFIG),
                            ("traffic", "batch_conv_moe_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "lfm2-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/lfm2-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "lfm2-tiny",
        "traffic": "batch_conv_moe_tiny", "chips": 1,
        "why": "tiny preset for the CPU tests"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in SHARED + NEW:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_real_manifest_gives_the_cell_its_files_and_metrics():
    m = Manifest()
    cell = m.cell(REAL)
    assert set(NEW) | set(SHARED[1:]) <= set(cell.per_layer)
    assert not {n for n in cell.per_layer if n.endswith("_roofline")} \
        - set(NEW)
    assert cell.end_to_end == ("tokens_per_s", "setup_s")
    assert cell.chips == 1
    assert cell.traffic["driver"] == "batch_decode_conv_moe"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk", "max_len",
        "compute_dtype", "kv_cache", "check_sequences", "check_tokens",
        "trace_seconds")} == {
        # (the issue's 2048 / 2560, cut together by one: the file's
        # ``sized`` says why)
        "batch": 128, "prompt_len": 512, "new_tokens": 2047,
        "token_chunk": 32, "max_len": 2559, "compute_dtype": "bfloat16",
        "kv_cache": "buffer", "check_sequences": 2, "check_tokens": 512,
        "trace_seconds": 8}
    # every number of the catalog's entry under its own key but the
    # depth, whose published value stands beside it
    published = cell.config["published"]
    assert published["num_hidden_layers"] == 40
    assert cell.config["num_hidden_layers"] == 10
    for key, value in published.items():
        if key != "num_hidden_layers":
            assert cell.config[key] == value, key
    for key, value in {
            "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
            "intermediate_size": 11776, "moe_intermediate_size": 1536,
            "norm_eps": 1e-05, "norm_topk_prob": True,
            "num_attention_heads": 32, "num_dense_layers": 2,
            "num_experts": 64, "num_experts_per_tok": 4,
            "num_key_value_heads": 8, "routed_scaling_factor": 1,
            "use_expert_bias": True, "vocab_size": 65536,
            "max_position_embeddings": 128000,
            "model_type": "lfm2_moe"}.items():
        assert published[key] == value, key
    assert published["rope_parameters"] == {"rope_theta": 1000000,
                                            "rope_type": "default"}
    assert published["layer_types"] == (
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
        + ["full_attention", "conv"])
    assert list(cell.config["reduced"]) == ["num_hidden_layers"]
    entry = next(c for c in m.doc["configs"]
                 if c["name"] == "lfm2-24b-a2b-10l")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cell.config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    # (a later PR appends behind: the eleventh configuration)
    assert m.doc["configs"][10] == entry
    a = cell.config["model_args"]
    assert (a["num_layers"], a["hidden"], a["heads"], a["kv_heads"],
            a["head_dim"], a["dense_hidden"], a["vocab"], a["num_experts"],
            a["experts_per_tok"], a["expert_hidden"], a["dense_layers"],
            a["d_conv"], a["routed_scale"], a["rope_theta"],
            a["seq_len"]) == (
        10, 2048, 32, 8, 64, 11776, 65536, 64, 4, 1536, 2, 3, 1.0, 1e6,
        128000)
    assert a["layer_types"] == published["layer_types"][:10]
    assert cell.config["reference"]["args"]["layer_types"] \
        == a["layer_types"]
    assert {"tied_head", "head_dim", "rope_pairs", "qk_layernorm",
            "conv_mixer", "dense_width", "router", "text_only",
            "init_gain"} <= set(cell.config["assumed"])
    assert "four-chip" in cell.config["deployment"]
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"] == [REAL]
    names = [e["name"] for e in m.doc["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 4] == list(NEW)            # together, in order
    # fourteen cells, this one the last; still one on four chips
    assert [w["name"] for w in m.doc["workloads"]][13:14] == [REAL]
    assert len(m.doc["workloads"]) >= 14
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    pairs = [(w["config"], w["traffic"]) for w in m.doc["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_an_untraced_run_checks_tokens_windows_logits_and_router(root):
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
    give the run the v5e's peaks and stand-in times, and see the shares
    come out of the traced run's own counters, above 0."""
    import chipbench.harness as harness
    import chipbench.trace as trace

    monkeypatch.setattr(trace.TraceReduction, "module_runs",
                        lambda self, pattern, device=0: [2e-3, 3e-3, 4e-3])
    real_init = trace.TraceReduction.__init__

    def with_kernels(self, devices, spans):
        real_init(self, devices, spans)
        lo, _hi = self.window
        for i in range(4):
            devices[0].ops.append(
                (f"%grouped_experts.{i} = (f32[]) custom-call()",
                 lo + i * 1e-5, lo + (i + 0.5) * 1e-5))

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
    for name in NEW[:3]:
        m = doc["metrics"][name]
        assert m["unit"] == "%" and 0 < m["value"] < 100
    rows = doc["metrics"]["routed_rows_per_expert"]
    # 4 sequences x 2 choices over at most 8 experts: 1 to 8 rows each
    assert rows["unit"] == "rows" and 1 <= rows["value"] <= 8


def _context(cell, seed, trace=False):
    return types.SimpleNamespace(
        cell=cell, seed=seed, devices=[None], trace=trace,
        span=lambda name: __import__("contextlib").nullcontext())


def test_the_window_counts_updates_and_pairs_and_the_check_holds(root):
    """Over a window ``decode.moe.assignments`` is rows x 2 x routed
    layers x steps and ``decode.conv.updates`` rows x convolution layers
    x the same steps; the gauges count a two-row window a sequence a
    convolution layer and pass the reader's check of what is held."""
    from chipbench.drivers import batch_decode_conv_moe as drv
    cell = Manifest(root).cell(CELL)
    ctx = _context(cell, 5, trace=True)
    state = drv.setup(ctx)
    out = drv.measure(state, 0.3, ctx)
    c = out["counters"]
    steps = c["decode.moe.assignments"] / (4 * 2 * 6)
    assert steps == int(steps) and steps > 0
    assert c["decode.conv.updates"] == 4 * 6 * steps
    assert c["conv_layers"] == 6
    assert 0 < c["experts_hit_share"] <= 1
    # six convolution layers, a group of 4 sequences, 2 rows of 64
    # float32 values: the need to the byte (no scratch group)
    assert c["conv_window_bytes"] == c["conv_window_state_bytes"] \
        == 6 * 4 * 2 * 64 * 4 == rl.needed_window_bytes(ARGS, 4, 4)
    # two attention layers: a group and the scratch group of 4
    # sequences, 32 rows and the scratch row, 2 KV heads of 16, k and v
    assert c["cache_full_bytes"] == 2 * 2 * 4 * 2 * 33 * 16 * 4 * 2
    assert c["prefill_piece_rows"] == 4 and c["max_len"] == 32
    rl.check_held(dict(c, weight_bytes=4, kv_bytes=4), ARGS)
    with pytest.raises(ValueError, match="convolution windows, 2.000 times"):
        rl.check_held(dict(c, weight_bytes=4, kv_bytes=4,
                           conv_window_bytes=2 * c["conv_window_bytes"]),
                      ARGS)
    ok, detail = drv.check(state, ctx)
    assert ok and detail["tokens_compared"] == 2 * 6
    assert detail["worst_logit_gap_share"] <= 0
    assert detail["router_agreement_share"] > 0.99
    assert sorted(detail["router_agreement_by_layer"]) == [2, 3, 4, 5, 6, 7]
    assert sorted(detail["window_rel_err_by_layer"]) == [0, 1, 3, 4, 5, 7]
    assert detail["window_rel_err"] < 1e-4 < drv.STATE_TOL_FIRST
    assert detail["leading_window_rel_err"] < 1e-4
    assert detail["logits_rms_err"] < 1e-4 < drv.LOGITS_TOL


def test_the_windows_and_the_probe_tell_the_controls(root):
    """The windows are read back behind the prefill *and* decode steps;
    one position off, without the ``B`` gate, or with a ``silu`` left
    in, the reference's are another's: each fails a window's limit, and
    the two that change the mixer's output fail the logits' too."""
    import jax.numpy as jnp
    from chipbench.agreement import rel_err
    from chipbench.drivers import batch_decode_conv_moe as drv
    cell = Manifest(root).cell(CELL)
    state = drv.setup(_context(cell, 6))
    tr, ref = cell.traffic, cell.config["reference"]
    ids, got = drv.decoded_windows(state["dec"], state["prompts"], 2, tr)
    steps = min(drv.PROBE_STEPS, tr["new_tokens"] - 1)
    assert ids.shape == (2, tr["prompt_len"] + steps) and steps > 0
    assert [g is None for g in got] == [False, False, True, False] * 2
    assert got[0].shape == (2, 2, 64)
    want, extras = drv.reference_forward(state["params"], ids,
                                         tr["prompt_len"], ref)
    assert max(drv.window_errors(got, extras).values()) < 1e-4
    probe = drv.decode_probe(state["graph"], state["params"], ids,
                             tr["prompt_len"], jnp.float32)
    assert probe[0].shape == (2, steps + 1, 211)
    assert sorted(probe[1]) == [2, 3, 4, 5, 6, 7]
    assert probe[1][2].shape == (2, steps, 2)
    # the probe's own last windows are the ring's: the same tokens
    # through the same blocks and formats
    assert sorted(probe[2]) == [0, 1, 3, 4, 5, 7]
    for l, window in probe[2].items():
        assert rel_err(window, got[l]) < 1e-5
    shares, logits = drv.probe_agreement(probe, want, extras,
                                         tr["prompt_len"])
    assert min(shares.values()) > 0.99 and logits < 1e-4
    for control in ({"window_shift": 1}, {"b_gate": False},
                    {"conv_silu": True}):
        want, extras = drv.reference_forward(
            state["params"], ids, tr["prompt_len"], ref, **control)
        wrong = drv.window_errors(got, extras)
        assert max(wrong.values()) > drv.STATE_TOL, control
        # (a silu behind the first layer's convolution is downstream of
        # that layer's window: the later layers' windows see it)
        if "conv_silu" in control:
            assert wrong[0] < 1e-4 and wrong[1] > drv.STATE_TOL
        else:
            assert wrong[0] > drv.STATE_TOL_FIRST, control
        if "window_shift" not in control:
            _, moved = drv.probe_agreement(probe, want, extras,
                                           tr["prompt_len"])
            assert moved > drv.LOGITS_TOL, control


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_sum_probe_tells_a_sum_kept_below_float32(dtype):
    """On operands the type holds whole the program's convolution and
    router are the reference's to float32's last digits; the
    reference's own sum or logits kept in bfloat16, or its bias let
    into the weights, read ten times their limit and more."""
    import jax.numpy as jnp
    from chipbench.drivers import batch_decode_conv_moe as drv
    from chipbench.drivers.batch_decode_hybrid_moe import make_weights
    from defer_tpu import models
    graph = models.lfm2_moe(**ARGS)
    params = make_weights(graph, 9, jnp.dtype(dtype), CONFIG["init_gain"])
    ref = CONFIG["reference"]
    sound = drv.sum_probe(graph, params, 9, jnp.dtype(dtype), ref)
    assert sound["conv"] < drv.CONV_SUM_TOL / 10
    assert sound["router"] < drv.ROUTER_SUM_TOL / 10
    assert sound["router_same_choice_share"] > 0.99
    narrow = drv.sum_probe(graph, params, 9, jnp.dtype(dtype), ref,
                           conv_dtype=jnp.bfloat16)
    assert narrow["conv"] > 10 * drv.CONV_SUM_TOL
    assert narrow["router"] == sound["router"]
    for control in ({"router_dtype": jnp.bfloat16}, {"bias_weighs": True}):
        narrow = drv.sum_probe(graph, params, 9, jnp.dtype(dtype), ref,
                               **control)
        assert narrow["router"] > 10 * drv.ROUTER_SUM_TOL, control
        assert narrow["conv"] == sound["conv"]


def _run(counters, ops=(), modules=()):
    chip = DeviceTrace("/device:TPU:0")
    chip.ops, chip.modules = list(ops), list(modules)
    return types.SimpleNamespace(
        trace=TraceReduction([chip], [("window", 0.0, 1.0)]),
        counters=counters, peaks=PEAKS)


def test_the_readers_on_a_trace_made_by_hand(lfm2_args):
    """The step's share from ``device_decode`` runs over
    ``steps_per_reading``; the prefill's from ``device_prefill``; the
    kernel's from two consecutive ``grouped_experts`` events added up,
    wherever the window cuts the series; rows an expert from the
    counters."""
    mf = Manifest()
    c = {"model_args": lfm2_args, "rows": 128, "live_positions": 1500.0,
         "weight_bytes": 2, "kv_bytes": 2, "steps_per_reading": 32,
         "conv_layers": 8, "experts_hit_share": 1.0,
         "prefill_tokens": 128 * 512, "max_len": 2560,
         "decode.moe.assignments": 512 * 8 * 64,
         "decode.moe.experts_hit": 64 * 8 * 64,
         "conv_window_bytes": 8 * 128 * 2 * 2048 * 2,
         "cache_full_bytes": 2 * 2 * 128 * 2576 * 2 * 8 * 64 * 2}
    # a layer's calls: gate-and-up 1.1 ms, down 0.6 ms; the window opens
    # on a down call and one event straddles its end
    calls, at = [], 0.0
    for i in range(9):
        d = 0.6e-3 if i % 2 == 0 else 1.1e-3
        calls.append((f"%grouped_experts.{i} = custom-call()", at, at + d))
        at += 2e-3
    calls.append(("%grouped_experts.77 = custom-call()", 0.9999, 1.0003))
    run = _run(c, ops=calls + [("%fusion.1 = fusion()", 0.5, 0.6)],
               modules=[("jit_device_decode(1)", 0.0, 0.5),
                        ("jit_device_decode(1)", 0.5, 0.98),
                        ("jit_device_prefill(2)", 0.1, 0.9)])
    flops, nbytes = rl.routed_step_needs(lfm2_args, 128, 2, 64.0)
    assert mf.reader("routed_step_kernel_roofline").read(run) \
        == pytest.approx(100 * (nbytes / 819e9) / 1.7e-3)
    flops, nbytes = rl.decode_step_needs(
        lfm2_args, rows=128, live_positions=1500.0, weight_bytes=2,
        kv_bytes=2)
    assert mf.reader("conv_moe_decode_step_roofline").read(run) \
        == pytest.approx(100 * (nbytes / 819e9) / (0.49 / 32))
    flops, nbytes = rl.prefill_needs(lfm2_args, rows=128, prompt_len=512,
                                     weight_bytes=2, kv_bytes=2)
    assert mf.reader("conv_moe_prefill_roofline").read(run) \
        == pytest.approx(100 * (flops / 197e12) / 0.8)
    assert mf.reader("routed_rows_per_expert").read(run) == 8.0
    # the program holding twice the windows it needs: the reader raises
    run.counters = dict(c, conv_window_bytes=2 * c["conv_window_bytes"])
    with pytest.raises(ValueError, match="convolution windows"):
        mf.reader("conv_moe_decode_step_roofline").read(run)


def test_the_readers_on_the_recorded_trace_find_nothing_to_read():
    """``chipbench/testdata/small.xplane.pb`` was recorded on a chip by
    a program that has neither the ring's programs nor the routed
    kernel: with this cell's counters every reader that reads the trace
    gives None and does not raise."""
    from chipbench import trace as tr
    red = tr.load(os.path.join(tiny.PKG, "testdata", "small.xplane.pb"))
    c = {"model_args": Manifest().cell(REAL).config["model_args"],
         "rows": 128, "live_positions": 1500.0, "weight_bytes": 2,
         "kv_bytes": 2, "steps_per_reading": 32, "conv_layers": 8,
         "experts_hit_share": 1.0, "prefill_tokens": 128 * 512,
         "max_len": 2560}
    run = types.SimpleNamespace(trace=red, counters=c, peaks=PEAKS)
    mf = Manifest()
    for name in NEW[:3]:
        assert mf.reader(name).read(run) is None
    assert mf.reader("routed_rows_per_expert").read(run) is None


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.conv.updates`` and no
    ``decode.conv.window_bytes`` (the parent) or off the chip a reader
    gives None and does not raise — with a sibling's counters too."""
    mf = Manifest()
    granite = {"mamba2_layers": 9, "experts_hit_share": 0.5,
               "held_share": 0.5, "decode.moe.assignments": 100,
               "decode.moe.experts_hit": 10}
    for counters in ({}, granite):
        run = types.SimpleNamespace(
            trace=types.SimpleNamespace(
                module_runs=lambda pattern: [1e-3], window=(0.0, 1.0),
                devices=[types.SimpleNamespace(ops=[])]),
            counters=counters, peaks=PEAKS)
        for name in NEW:
            assert mf.reader(name).read(run) is None
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS)
    for name in NEW:
        assert mf.reader(name).read(run) is None


def test_the_models_size_against_the_issues_count(lfm2_args):
    """16,783,360 parameters a convolution mixer, 10,485,888 an
    attention mixer, 72,351,744 a dense SwiGLU, 9,437,184 an expert,
    131,136 the router and its bias; 5.27 B held, 10.53 GB, 10.80 as the
    ring's two ends each hold the tied matrix."""
    a = lfm2_args
    assert rl.layer_kinds(a) == (8, 2) and rl.routed_layers(a) == 8
    assert rl.conv_mixer_params(a) == 16_783_360
    assert rl.attention_mixer_params(a) == 10_485_888
    assert rl.expert_params(a) == 9_437_184
    assert 64 * rl.expert_params(a) == 603_979_776
    assert rl.dense_params(a) == (
        2 * 89_139_200 + 2 * (10_485_888 + 131_136 + 4_096)
        + 6 * (16_783_360 + 131_136 + 4_096))
    assert rl.held_params(a) == 5_267_090_176
    assert round(2 * rl.held_params(a) / 1e9, 2) == 10.53
    # the ring's two ends each hold the tied table
    assert round(2 * (rl.held_params(a) + 65536 * 2048) / 1e9, 2) == 10.80
    whole = dict(a, num_layers=40, layer_types=Manifest().cell(
        REAL).config["published"]["layer_types"])
    assert rl.layer_kinds(whole) == (30, 10)
    assert rl.held_params(whole) == 23_843_661_440
    # a sequence: 8 KB a convolution layer, 2 KB a position an
    # attention layer
    assert rl.needed_window_bytes(a, 1, 2) == 8 * 8192
    assert rl.needed_cache_bytes(a, 1, 1, 2) == 2 * 2048
    assert rl.needed_window_bytes(a, 128, 2) == 8 * 1_048_576
    assert round(rl.needed_cache_bytes(a, 128, 2560, 2) / 1e9, 2) == 1.34


def test_decode_step_needs_against_the_issues_count(lfm2_args):
    """A step of 128 rows at ~1500 positions with every expert touched:
    the experts 9.66 GB, mixers, dense layers, routers and head 0.87,
    the live rows 0.79, windows and logits 0.05: ~11.4 GB, 13.9 ms at
    the memory peak; 0.19 TFLOP beside it, so memory-bound."""
    a = lfm2_args
    kw = dict(rows=128, live_positions=1500.0, weight_bytes=2, kv_bytes=2)
    flops, nbytes = rl.decode_step_needs(a, **kw)
    experts = 2 * 8 * 64 * 9_437_184
    assert round(experts / 1e9, 2) == 9.66
    outside = 2 * (rl.dense_params(a) + 2048 + 65536 * 2048)
    assert round(outside / 1e9, 2) == 0.87
    rows = rl.needed_cache_bytes(a, 128, 1500.0, 2)
    assert round(rows / 1e9, 2) == 0.79
    windows, logits = 2 * 8 * 1_048_576, 128 * 65536 * 4
    assert nbytes == experts + outside + rows + windows + logits
    assert round(nbytes / 1e9, 1) == 11.4
    assert round(1e3 * nbytes / 819e9, 1) == 13.9
    assert 0.84 < experts / nbytes < 0.86
    assert round(flops / 1e12, 2) == 0.19
    assert flops / 197e12 < 0.1 * nbytes / 819e9
    # an untouched expert is not read
    _, fewer = rl.decode_step_needs(a, experts_hit_share=0.5, **kw)
    assert nbytes - fewer == experts / 2
    # one routed layer's two kernel calls: the 64 experts' 1.21 GB and
    # 512 pairs' rows in, hidden out and in, result out
    kf, kb = rl.routed_step_needs(a, 128, 2, 64.0)
    assert kb == 2 * (64 * 9_437_184 + 512 * (2 * 2048 + 2 * 1536))
    assert kf == 2 * 512 * 9_437_184
    assert rl.routed_step_needs(a, 128, 2) == (kf, kb)


def test_prefill_needs_against_the_issues_count(lfm2_args):
    """128 x 512 tokens: 79.3 TFLOP, 0.40 s at the matrix peak (the
    issue's ~80), compute-bound; 10.8 GB, every held weight once."""
    a = lfm2_args
    flops, nbytes = rl.prefill_needs(a, rows=128, prompt_len=512,
                                     weight_bytes=2, kv_bytes=2)
    tokens = 128 * 512
    assert flops == (
        tokens * 2 * (rl.dense_params(a) + 8 * 4 * 9_437_184)
        + 2 * tokens * 2 * 512 * 2048 + 128 * 2 * 2048 * 65536)
    assert round(flops / 1e12, 1) == 79.3
    assert round(flops / 197e12, 2) == 0.40
    assert flops / 197e12 > nbytes / 819e9
    assert nbytes == (2 * rl.held_params(a) + 8 * 1_048_576
                      + rl.needed_cache_bytes(a, 128, 512, 2)
                      + 128 * 65536 * 4)
