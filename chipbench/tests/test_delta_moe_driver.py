"""The delta-rule / attention hybrid's cell (Solar-Open2): its driver,
readers and counts at a tiny preset on the CPU, through the harness; and
``roofline_delta_moe`` against the counts of the issue that asked for
the cell.  The cell, its configuration and its metrics are found in the
manifest *by name*: a later PR appends behind them."""

import json
import os
import time
import types

import pytest

import tiny
from chipbench import roofline_delta_moe as rl
from chipbench.harness import run_cell
from chipbench.manifest import Manifest
from chipbench.trace import DeviceTrace, TraceReduction

ARGS = {"num_layers": 8, "hidden": 64, "heads": 4, "kv_heads": 2,
        "head_dim": 16, "seq_len": 64, "vocab": 211, "gqa_layers": [0, 4],
        "num_experts": 16, "experts_per_tok": 2, "expert_hidden": 32,
        "kda_heads": 4, "kda_head_dim": 16, "d_conv": 4, "gate_rank": 8,
        "chunk": 8, "routed_scale": 1.0, "experts_held": [0, 4],
        "rms_eps": 1e-05}
REF_ARGS = {"gqa_layers": [0, 4], "n_head": 4, "n_kv": 2, "head_dim": 16,
            "kda_heads": 4, "kda_head_dim": 16, "top_k": 2,
            "routed_scale": 1.0, "held": [0, 4], "eps": 1e-05}
CONFIG = {"model_args": ARGS,
          "reference": {"module": "chipbench.reference.solar_open2",
                        "args": REF_ARGS}}
TRAFFIC = {"driver": "batch_decode_delta_moe", "batch": 4, "prompt_len": 11,
           "new_tokens": 16, "token_chunk": 2, "max_len": 32,
           "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "check_tokens": 6, "trace_seconds": 0.5}
CELL = "delta_moe_tiny"
REAL = "solaropen2_batch_decode"
REAL_CONFIG = "solar-open2-250b-4l-ep8"
SHARED = ("tokens_per_s", "decoder_launch_ms", "decode_chunk_ms",
          "decode_step_ms", "decode_device_idle_share", "decode_prefill_ms",
          "decode_host_serial_ms", "decode_idle_wake_ms",
          "decode_idle_launch_ms", "decode_upload_ms", "decode_pause_share",
          "weights_relaid_leaves")
NEW = ("delta_moe_decode_step_roofline", "delta_moe_prefill_roofline",
       "delta_step_kernel_roofline", "delta_chunk_kernel_roofline",
       "delta_state_step_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
KDA = [1, 2, 3, 5, 6, 7]


@pytest.fixture(scope="module")
def solar_args():
    return Manifest().cell(REAL).config["model_args"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_delta_moe_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "solar-tiny", CONFIG),
                            ("traffic", "batch_delta_moe_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "solar-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/solar-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "solar-tiny",
        "traffic": "batch_delta_moe_tiny", "chips": 1,
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
    entry = next(w for w in m.doc["workloads"] if w["name"] == REAL)
    assert entry["traffic"] == "batch192_512in_1536out_chunk32"
    for words in ("4 of 48 layers", "40 of 320", "an eighth"):
        assert words in entry["why"]
    assert cell.traffic["driver"] == "batch_decode_delta_moe"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk", "max_len",
        "compute_dtype", "kv_cache", "check_sequences",
        "trace_seconds")} == {
        "batch": 192, "prompt_len": 512, "new_tokens": 1535,
        "token_chunk": 32, "max_len": 2047, "compute_dtype": "bfloat16",
        "kv_cache": "buffer", "check_sequences": 2, "trace_seconds": 8}
    # every number of the catalog's entry under its own key but the four
    # that were cut, whose published values stand beside them
    published = cell.config["published"]
    cut = {"num_hidden_layers": (48, 4),
           "gqa_layers": (list(range(0, 48, 4)), [0]),
           "n_routed_experts": (320, 40), "vocab_size": (196608, 24576)}
    for key, value in published.items():
        if key in cut:
            assert (value, cell.config[key]) == cut[key], key
        else:
            assert cell.config[key] == value, key
    for key, value in {
            "model_type": "solar_open2", "hidden_size": 4096,
            "num_attention_heads": 64, "head_dim": 128,
            "num_key_value_heads": 8, "intermediate_size": 10240,
            "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
            "tie_word_embeddings": False, "first_k_dense_replace": 0,
            "use_rope": False, "use_gqa_gate": True,
            "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
            "n_shared_experts": 1, "norm_topk_prob": True,
            "routed_scaling_factor": 1, "num_experts_per_tok": 8,
            "max_position_embeddings": 1048576}.items():
        assert published[key] == value, key
    assert published["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert list(cell.config["reduced"]) == list(cut)
    entry = next(c for c in m.doc["configs"] if c["name"] == REAL_CONFIG)
    assert entry["reduced"] == list(cut)
    assert entry["source"] == cell.config["source"] == (
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
        "config.json")
    a = cell.config["model_args"]
    assert (a["num_layers"], a["hidden"], a["heads"], a["kv_heads"],
            a["head_dim"], a["vocab"], a["num_experts"],
            a["experts_per_tok"], a["expert_hidden"], a["kda_heads"],
            a["kda_head_dim"], a["d_conv"], a["gate_rank"],
            a["routed_scale"], a["seq_len"]) == (
        4, 4096, 64, 8, 128, 24576, 320, 8, 1280, 64, 128, 4, 128, 1.0,
        1048576)
    assert a["gqa_layers"] == [0] and a["experts_held"] == [0, 40]
    ref = cell.config["reference"]["args"]
    assert ref["gqa_layers"] == [0] and ref["held"] == [0, 40]
    assert {"conv_activation", "qk_norm_and_scale", "gate_rank", "decay",
            "beta", "output_norm", "state_dtype", "gqa_gate", "gqa_plain",
            "router", "shared_expert", "intermediate_size",
            "no_mtp"} <= set(cell.config["assumed"])
    assert "8 v5e chips a layer" in cell.config["deployment"]
    assert "3,308,316,096" in cell.config["size"] \
        and "250.29 B" in cell.config["size"]
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"][0] == REAL
    names = [e["name"] for e in m.doc["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 5] == list(NEW)            # together, in order
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    pairs = [(w["config"], w["traffic"]) for w in m.doc["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_an_untraced_run_checks_tokens_states_logits_and_router(root):
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

    real_init = trace.TraceReduction.__init__

    def with_kernels(self, devices, spans):
        real_init(self, devices, spans)
        lo, _hi = self.window
        for i in range(4):
            at = lo + i * 1e-3
            devices[0].modules.append(
                ("jit_device_decode(1)", at, at + 0.9e-3))
            devices[0].ops.append(
                (f"%delta_step.{i} = (f32[]) custom-call()",
                 at + 1e-5, at + 4e-4))
        devices[0].modules.append(
            ("jit_device_prefill(2)", lo + 5e-3, lo + 9e-3))
        devices[0].ops.append(
            ("%while.3 = (s32[], f32[4,4,16,16]{3,2,1,0}, f32[2,4]) "
             "while((s32[], f32[4,4,16,16]) %tuple.1), body=%b",
             lo + 5e-3, lo + 6e-3))

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
        assert m["unit"] == "%" and 0 < m["value"] < 100, name


def _context(cell, seed, trace=False):
    return types.SimpleNamespace(
        cell=cell, seed=seed, devices=[None], trace=trace,
        span=lambda name: __import__("contextlib").nullcontext())


def test_the_window_counts_updates_and_pairs_and_the_check_holds(root):
    """Over a window ``decode.moe.assignments`` is rows x 2 x layers x
    steps and ``decode.delta.updates`` rows x KDA layers x the same
    steps; the gauges count a state and a three-row window a sequence a
    KDA layer to the byte and pass the reader's check of what is held."""
    from chipbench.drivers import batch_decode_delta_moe as drv
    cell = Manifest(root).cell(CELL)
    ctx = _context(cell, 5, trace=True)
    state = drv.setup(ctx)
    out = drv.measure(state, 0.3, ctx)
    c = out["counters"]
    steps = c["decode.moe.assignments"] / (4 * 2 * 8)
    assert steps == int(steps) and steps > 0
    assert c["decode.delta.updates"] == 4 * 6 * steps
    assert c["delta_layers"] == 6
    assert 0 < c["experts_hit_share"] <= 1
    assert 0 < c["held_pairs_share"] < 1
    assert c["decode.moe.held_assignments"] \
        == c["held_pairs_share"] * c["decode.moe.assignments"]
    # six KDA layers, a group of 4 sequences: 4 heads of 16 x 16 float32
    # values, and 3 rows of 3 x 64 float32 values: the need to the byte
    # (no scratch group)
    assert c["delta_state_bytes"] == 6 * 4 * 4 * 16 * 16 * 4 \
        == rl.needed_state_bytes(ARGS, 4)
    assert c["delta_window_bytes"] == 6 * 4 * 3 * 192 * 4 \
        == rl.needed_window_bytes(ARGS, 4, 4)
    assert c["delta_rule_state_bytes"] \
        == c["delta_state_bytes"] + c["delta_window_bytes"]
    # two attention layers: a group and the scratch group of 4
    # sequences, 32 rows and the scratch row, 2 KV heads of 16, k and v
    assert c["cache_full_bytes"] == 2 * 2 * 4 * 2 * 33 * 16 * 4 * 2
    assert c["prefill_piece_rows"] == 4 and c["max_len"] == 32
    rl.check_held(dict(c, weight_bytes=4, kv_bytes=4), ARGS)
    with pytest.raises(ValueError, match="delta-rule states, 1.070 times"):
        rl.check_held(dict(c, weight_bytes=4, kv_bytes=4,
                           delta_state_bytes=1.07 * c["delta_state_bytes"]),
                      ARGS)
    ok, detail = drv.check(state, ctx)
    assert ok and detail["tokens_compared"] == 2 * 6
    assert detail["worst_logit_gap_share"] <= 0
    assert detail["router_agreement_share"] > 0.99
    assert sorted(detail["router_agreement_by_layer"]) == list(range(8))
    assert sorted(detail["state_rms_err_by_layer"]) == KDA
    assert sorted(detail["window_rel_err_by_layer"]) == KDA
    assert detail["state_rms_err"] < 1e-4 < drv.STATE_TOL_FIRST
    assert detail["first_state_rms_err"] < 1e-4
    assert detail["window_rel_err"] < 1e-4 < drv.WINDOW_TOL
    assert detail["logits_rms_err"] < 1e-4 < drv.LOGITS_TOL
    assert detail["state_sum_rms_err"] < drv.STATE_SUM_TOL


def test_the_states_and_the_probe_tell_the_controls(root):
    """The states are read back behind the prefill *and* decode steps;
    under a decay a head, ``beta`` in (0, 1) or a write that does not
    read the state the reference's are another's; a window one position
    off is another's window; a rotation let into the attention layer or
    its gate dropped move every logit behind it."""
    import jax.numpy as jnp
    from chipbench.agreement import rel_err
    from chipbench.drivers import batch_decode_delta_moe as drv
    cell = Manifest(root).cell(CELL)
    state = drv.setup(_context(cell, 6))
    tr, ref = cell.traffic, cell.config["reference"]
    ids, got = drv.decoded_memory(state["dec"], state["prompts"], 2, tr)
    steps = min(drv.PROBE_STEPS, tr["new_tokens"] - 1)
    assert ids.shape == (2, tr["prompt_len"] + steps) and steps > 0
    assert [g is None for g in got] == [True, False, False, False] * 2
    assert got[1][0].shape == (2, 4, 16, 16)
    assert got[1][1].shape == (2, 3, 192)
    want, extras = drv.reference_forward(state["params"], ids,
                                         tr["prompt_len"], ref)
    states, windows = drv.memory_errors(got, extras)
    assert max(states.values()) < 1e-4 and max(windows.values()) < 1e-4
    probe = drv.decode_probe(state["graph"], state["params"], ids,
                             tr["prompt_len"], jnp.float32)
    assert probe[0].shape == (2, steps + 1, 211)
    assert sorted(probe[1]) == list(range(8))
    assert probe[1][2].shape == (2, steps, 2)
    # the probe's own last states are the ring's: the same tokens
    # through the same blocks and formats
    assert sorted(probe[2]) == KDA
    for l, (s, w) in probe[2].items():
        assert drv.rms_err(s, got[l][0]) < 1e-5
        assert rel_err(w, got[l][1]) < 1e-5
    shares, logits = drv.probe_agreement(probe, want, extras,
                                         tr["prompt_len"])
    assert min(shares.values()) > 0.99 and logits < 1e-4
    for control in ({"decay_a_head": True}, {"beta_scale": 1.0},
                    {"delta_reads": False}, {"window_shift": 1},
                    {"gqa_theta": 10000.0}, {"gqa_gate": False}):
        want, extras = drv.reference_forward(
            state["params"], ids, tr["prompt_len"], ref, **control)
        states, windows = drv.memory_errors(got, extras)
        _, moved = drv.probe_agreement(probe, want, extras, tr["prompt_len"])
        if "window_shift" in control:
            assert min(windows.values()) > drv.WINDOW_TOL
            continue
        assert moved > drv.LOGITS_TOL, control
        if not any(k.startswith("gqa") for k in control):
            # the first KDA layer's own state, upstream of nothing else
            assert states[1] > drv.STATE_TOL_FIRST, control


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_sum_probe_tells_a_state_kept_below_float32(dtype):
    """On operands the type holds whole the program's state — chunked
    prefill, then steps — and router are the reference's to float32's
    last digits; the reference's own state or logits kept in bfloat16,
    or its bias let into the weights, read ten times their limit and
    more."""
    import jax.numpy as jnp
    from chipbench.drivers import batch_decode_delta_moe as drv
    from defer_tpu import models
    graph = models.solar_open2(**ARGS)
    params = drv.make_weights(graph, 9, jnp.dtype(dtype), {})
    ref = CONFIG["reference"]
    sound = drv.sum_probe(graph, params, 9, jnp.dtype(dtype), ref)
    assert sound["state"] < drv.STATE_SUM_TOL / 10
    assert sound["router"] < drv.ROUTER_SUM_TOL / 10
    assert sound["router_same_choice_share"] > 0.99
    narrow = drv.sum_probe(graph, params, 9, jnp.dtype(dtype), ref,
                           state_dtype=jnp.bfloat16)
    assert narrow["state"] > 10 * drv.STATE_SUM_TOL
    assert narrow["router"] == sound["router"]
    for control in ({"router_dtype": jnp.bfloat16}, {"bias_weighs": True}):
        narrow = drv.sum_probe(graph, params, 9, jnp.dtype(dtype), ref,
                               **control)
        assert narrow["router"] > 10 * drv.ROUTER_SUM_TOL, control
        assert narrow["state"] == sound["state"]


def test_the_drivers_weights_are_the_graphs_own_untied():
    import jax
    import jax.numpy as jnp
    from chipbench.drivers import batch_decode_delta_moe as drv
    from defer_tpu import models
    graph = models.solar_open2(**ARGS)
    params = drv.make_weights(graph, 2 ** 31 + 5, jnp.float32,
                              {"router/w": 2.0})
    want = graph.init(jax.random.key((2 ** 31 + 5) % (2 ** 31 - 1)))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert not (params["lm_head"]["w"] == params["embeddings"]["wte"]).all()
    assert (params["block_1"]["router"]["w"]
            == 2.0 * want["block_1"]["router"]["w"]).all()
    assert (params["block_1"]["in_proj"]["w"]
            == want["block_1"]["in_proj"]["w"]).all()


def _run(counters, ops=(), modules=()):
    chip = DeviceTrace("/device:TPU:0")
    chip.ops, chip.modules = list(ops), list(modules)
    return types.SimpleNamespace(
        trace=TraceReduction([chip], [("window", 0.0, 1.0)]),
        counters=counters, peaks=PEAKS)


def _counters(a):
    return {"model_args": a, "rows": 192, "live_positions": 1280.0,
            "weight_bytes": 2, "kv_bytes": 2, "steps_per_reading": 32,
            "delta_layers": 3, "experts_hit_share": 0.99,
            "held_pairs_share": 0.125, "prefill_tokens": 192 * 512,
            "prefill_piece_rows": 8, "max_len": 2047,
            "delta_state_bytes": 3 * 192 * 64 * 128 * 128 * 4,
            "delta_window_bytes": 3 * 192 * 3 * 24576 * 2,
            "cache_full_bytes": 2 * 192 * 2048 * 2 * 8 * 128 * 2}


def test_the_readers_on_a_trace_made_by_hand(solar_args):
    """The step's share from ``device_decode`` runs over
    ``steps_per_reading``; the prefill's from ``device_prefill``; the
    step kernel's from the median ``delta_step`` event; the chunked
    form's from the ``while`` events that carry a piece's states, and
    from no other loop; the state's share of the decode programs from
    the kernel's calls inside their runs."""
    mf = Manifest()
    c = _counters(solar_args)
    calls = [(f"%delta_step.{i} = custom-call()", 0.01 * i,
              0.01 * i + d) for i, d in enumerate(
                  (2.5e-3, 2.6e-3, 2.4e-3, 2.5e-3, 2.5e-3))]
    # a call behind the last decode run's end: in the window, in no run
    calls.append(("%delta_step.9 = custom-call()", 0.985, 0.9875))
    state = "f32[8,64,128,128]{3,2,1,0:T(8,128)}"
    loops = [(f"%while.{i} = (s32[], {state}, f32[8,64,8,64,128]) "
              f"while((s32[], {state}) %t), body=%b", 0.6 + 0.05 * i,
              0.6 + 0.05 * i + 0.02) for i in range(3)]
    # the ring's own loop and a dispatcher's: another carry
    others = [("%while.7 = (s32[], bf16[8,512,4096]) while()", 0.2, 0.5),
              (f"%while.8 = (s32[], f32[2,64,128,128]) while()", 0.3, 0.31)]
    run = _run(c, ops=calls + loops + others
               + [("%fusion.1 = fusion()", 0.5, 0.6)],
               modules=[("jit_device_decode(1)", 0.0, 0.5),
                        ("jit_device_decode(1)", 0.5, 0.98),
                        ("jit_device_prefill(2)", 0.1, 0.9)])
    flops, nbytes = rl.delta_step_needs(solar_args, 192)
    assert mf.reader("delta_step_kernel_roofline").read(run) \
        == pytest.approx(100 * (nbytes / 819e9) / 2.5e-3)
    flops, nbytes = rl.delta_chunk_needs(solar_args, 8, 512, 64, 2)
    assert nbytes / 819e9 > flops / 197e12
    assert mf.reader("delta_chunk_kernel_roofline").read(run) \
        == pytest.approx(100 * (nbytes / 819e9) / 0.02)
    assert mf.reader("delta_state_step_share").read(run) \
        == pytest.approx(100 * 12.5e-3 / 0.98)
    flops, nbytes = rl.decode_step_needs(
        solar_args, rows=192, live_positions=1280.0, weight_bytes=2,
        kv_bytes=2, experts_hit_share=0.99, held_pairs_share=0.125)
    assert mf.reader("delta_moe_decode_step_roofline").read(run) \
        == pytest.approx(100 * (nbytes / 819e9) / (0.49 / 32))
    flops, nbytes = rl.prefill_needs(solar_args, rows=192, prompt_len=512,
                                     weight_bytes=2, kv_bytes=2,
                                     held_pairs_share=0.125)
    assert mf.reader("delta_moe_prefill_roofline").read(run) \
        == pytest.approx(100 * (flops / 197e12) / 0.8)
    # a kernel named delta_chunk is read in the loops' place
    run.trace.devices[0].ops.append(
        ("%delta_chunk.1 = custom-call()", 0.7, 0.71))
    assert mf.reader("delta_chunk_kernel_roofline").read(run) \
        == pytest.approx(100 * (rl.delta_chunk_needs(
            solar_args, 8, 512, 64, 2)[1] / 819e9) / 0.01)
    # the program holding a fatter state than it needs: the readers raise
    run.counters = dict(c, delta_state_bytes=1.07 * c["delta_state_bytes"])
    for name in ("delta_moe_decode_step_roofline",
                 "delta_step_kernel_roofline"):
        with pytest.raises(ValueError, match="delta-rule states"):
            mf.reader(name).read(run)


def test_the_readers_on_the_recorded_trace_find_nothing_to_read():
    """``chipbench/testdata/small.xplane.pb`` was recorded on a chip by
    a program that has neither the ring's programs nor the new kernel:
    with this cell's counters every reader gives None and does not
    raise."""
    from chipbench import trace as tr
    red = tr.load(os.path.join(tiny.PKG, "testdata", "small.xplane.pb"))
    c = _counters(Manifest().cell(REAL).config["model_args"])
    run = types.SimpleNamespace(trace=red, counters=c, peaks=PEAKS)
    mf = Manifest()
    for name in NEW:
        assert mf.reader(name).read(run) is None


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.delta.updates`` and no
    ``decode.delta.state_bytes`` (the parent) or off the chip a reader
    gives None and does not raise — with a sibling's counters too."""
    mf = Manifest()
    lfm2 = {"conv_layers": 8, "experts_hit_share": 1.0,
            "decode.moe.assignments": 100, "decode.moe.experts_hit": 10}
    for counters in ({}, lfm2):
        run = types.SimpleNamespace(
            trace=types.SimpleNamespace(
                module_runs=lambda pattern: [1e-3], window=(0.0, 1.0),
                devices=[types.SimpleNamespace(ops=[], modules=[])]),
            counters=counters, peaks=PEAKS)
        for name in NEW:
            assert mf.reader(name).read(run) is None
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS)
    for name in NEW:
        assert mf.reader(name).read(run) is None


def test_the_models_size_against_the_issues_count(solar_args):
    """137,732,160 parameters a KDA mixer, 109,051,904 the GQA mixer,
    15,728,640 an expert, 1,311,040 the router and its bias;
    3,308,316,096 held, 6.62 GB; the whole model by the same shapes
    250.29 B."""
    a = solar_args
    assert rl.layer_kinds(a) == (3, 1) and rl.kda_shape(a) == (64, 128)
    assert rl.held_experts(a) == 40
    assert rl.kda_mixer_params(a) == 137_732_160
    assert rl.attention_mixer_params(a) == 109_051_904
    assert rl.expert_params(a) == 15_728_640
    assert rl.dense_params(a) == (3 * 137_732_160 + 109_051_904
                                  + 4 * (15_728_640 + 1_311_040))
    assert rl.held_params(a) == 3_308_316_096
    assert round(2 * rl.held_params(a) / 1e9, 2) == 6.62
    assert round(rl.whole_model_params(a, 48, 12, 196608) / 1e9, 2) \
        == 250.29
    # a sequence: 4.19 MB of state a KDA layer, 147,456 B of windows,
    # 4,096 B a position of rows
    assert rl.needed_state_bytes(a, 1) == 3 * 4_194_304
    assert rl.needed_window_bytes(a, 1, 2) == 3 * 147_456
    assert rl.needed_cache_bytes(a, 1, 1, 2) == 4096
    assert round(rl.needed_state_bytes(a, 192) / 1e9, 2) == 2.42
    assert round(rl.needed_cache_bytes(a, 192, 2048, 2) / 1e9, 2) == 1.61


def test_the_programs_tree_has_the_issues_count(solar_args):
    """The graph's own parameter tree, from shapes: the hand count and
    the norms' 37,248 weights it leaves out."""
    import jax
    from defer_tpu import models
    graph = models.solar_open2(**solar_args)
    shapes = jax.eval_shape(graph.init, jax.random.key(0))
    total = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    norms = sum(leaf.size for path, leaf in
                jax.tree_util.tree_leaves_with_path(shapes)
                if path[-1].key == "scale")
    assert norms == 4 * 2 * 4096 + 3 * 128 + 4096
    assert total - norms == 3_308_316_096 == rl.held_params(solar_args)


def test_decode_step_needs_against_the_issues_count(solar_args):
    """A step of 192 rows at ~1280 positions with 39.7 of 40 held
    experts touched: the states 4.83 GB, the experts 5.0, mixers, shared
    experts, routers and head 1.38, the live rows 1.0, windows 0.17:
    ~12.4 GB, 15 ms at the memory peak; memory-bound."""
    a = solar_args
    kw = dict(rows=192, live_positions=1280.0, weight_bytes=2, kv_bytes=2)
    parts = rl.step_bytes_by_part(a, experts_hit_share=39.7 / 40, **kw)
    assert round(parts["states"] / 1e9, 2) == 4.83
    assert round(parts["experts"] / 1e9, 1) == 5.0
    assert round(parts["dense"] / 1e9, 2) == 1.38
    assert round(parts["rows"] / 1e9, 1) == 1.0
    assert round(parts["windows"] / 1e9, 2) == 0.17
    flops, nbytes = rl.decode_step_needs(a, experts_hit_share=39.7 / 40,
                                         **kw)
    assert nbytes == sum(parts.values())
    assert round(nbytes / 1e9, 1) == 12.4
    assert 15.0 < 1e3 * nbytes / 819e9 < 15.3
    assert flops / 197e12 < 0.2 * nbytes / 819e9
    # an untouched expert is not read
    _, fewer = rl.decode_step_needs(a, experts_hit_share=0.5, **kw)
    assert fewer == nbytes - parts["experts"] \
        + 0.5 * 4 * 40 * 15_728_640 * 2
    # the state kernel's one call: a layer's 805 MB read and written
    kf, kb = rl.delta_step_needs(a, 192)
    assert kb == 2 * 192 * 4_194_304
    assert round(1e3 * kb / 819e9, 2) == 1.97
    assert kf == 7 * 192 * 64 * 128 * 128


def test_prefill_needs_against_the_issues_count(solar_args):
    """192 x 512 tokens: ~1.34 GFLOP a token, 0.67 s at the matrix peak
    (the issue's 0.7), compute-bound; every held weight once."""
    a = solar_args
    flops, nbytes = rl.prefill_needs(a, rows=192, prompt_len=512,
                                     weight_bytes=2, kv_bytes=2)
    tokens = 192 * 512
    chunked = rl.delta_chunk_needs(a, 192, 512, 64, 2)[0]
    assert chunked == tokens * 64 * (6 * 128 * 128 + 4 * 64 * 128)
    assert flops == (
        tokens * 2 * (rl.dense_params(a) + 4 * 1 * 15_728_640)
        + tokens * 2 * 512 * 8192 + 3 * chunked + 192 * 2 * 4096 * 24576)
    assert 1.3e9 < flops / tokens < 1.4e9
    assert round(flops / 197e12, 2) == 0.67
    assert flops / 197e12 > nbytes / 819e9
    assert nbytes == (2 * rl.held_params(a) + rl.needed_state_bytes(a, 192)
                      + rl.needed_window_bytes(a, 192, 2)
                      + rl.needed_cache_bytes(a, 192, 512, 2)
                      + 192 * 24576 * 4)
