"""The Mamba-2 / attention / LatentMoE hybrid's cell (Nemotron-3-Super):
its driver, readers and counts at a tiny preset on the CPU, through the
harness; and ``roofline_ssd_latent_moe`` against the counts of the issue
that asked for the cell.  The cell, its configuration and its metrics
are found in the manifest *by name*: a later PR appends behind them."""

import json
import os
import time
import types

import pytest

import tiny
from chipbench import roofline_ssd_latent_moe as rl
from chipbench.harness import run_cell
from chipbench.manifest import Manifest
from chipbench.trace import DeviceTrace, TraceReduction

ARGS = {"hidden": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
        "seq_len": 64, "vocab": 211, "layer_pattern": "*EMEME",
        "mamba_heads": 8, "mamba_head_dim": 32, "mamba_d_state": 16,
        "mamba_groups": 2, "num_experts": 8, "experts_per_tok": 3,
        "latent": 32, "expert_hidden": 48, "shared_hidden": 96,
        "routed_scale": 2.5, "mamba_d_conv": 4, "mamba_chunk": 8,
        "experts_held": [0, 4], "rms_eps": 1e-05}
REF_ARGS = {"layer_pattern": "*EMEME", "n_head": 4, "n_kv": 2,
            "head_dim": 16, "mamba_heads": 8, "d_state": 16, "groups": 2,
            "top_k": 3, "held": [0, 4], "routed_scale": 2.5, "eps": 1e-05}
CONFIG = {"model_args": ARGS,
          "reference": {"module": "chipbench.reference.nemotron_h",
                        "args": REF_ARGS}}
TRAFFIC = {"driver": "batch_decode_ssd_latent_moe", "batch": 4,
           "prompt_len": 11, "new_tokens": 16, "token_chunk": 2,
           "max_len": 32, "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "check_tokens": 6, "trace_seconds": 0.5}
CELL = "ssd_latent_moe_tiny"
REAL = "nemotron3super_batch_decode"
REAL_CONFIG = "nemotron-3-super-120b-a12b-11l-ep4"
SHARED = ("tokens_per_s", "decoder_launch_ms", "decode_chunk_ms",
          "decode_step_ms", "decode_device_idle_share", "decode_prefill_ms",
          "decode_host_serial_ms", "decode_idle_wake_ms",
          "decode_idle_launch_ms", "decode_upload_ms", "decode_pause_share",
          "weights_relaid_leaves")
NEW = ("ssd_latent_moe_decode_step_roofline",
       "ssd_latent_moe_prefill_roofline", "latent_experts_kernel_roofline",
       "grouped_ssd_step_kernel_roofline",
       "grouped_ssd_scan_kernel_roofline", "latent_experts_step_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MAMBA, ROUTED = [2, 4], [1, 3, 5]


@pytest.fixture(scope="module")
def nemotron_args():
    return Manifest().cell(REAL).config["model_args"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_ssd_latent_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "nemotron-tiny", CONFIG),
                            ("traffic", "batch_ssd_latent_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "nemotron-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/nemotron-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "nemotron-tiny",
        "traffic": "batch_ssd_latent_tiny", "chips": 1,
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
    assert entry["traffic"] == "batch128_512in_3072out_chunk32"
    for words in ("11 of 88 layers", "128 of 512", "5.5 rows"):
        assert words in entry["why"]
    assert cell.traffic["driver"] == "batch_decode_ssd_latent_moe"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk",
        "compute_dtype", "kv_cache", "check_sequences", "check_tokens",
        "trace_seconds")} == {
        "batch": 128, "prompt_len": 512, "new_tokens": 3072,
        "token_chunk": 32, "compute_dtype": "bfloat16",
        "kv_cache": "buffer", "check_sequences": 2, "check_tokens": 256,
        "trace_seconds": 8}
    assert cell.traffic["max_len"] == 512 + 3072
    # every number of the catalog's entry under its own key but the
    # three that were cut (and the pattern's slice), whose published
    # values stand beside them
    published = cell.config["published"]
    pattern = published["hybrid_override_pattern"]
    cut = {"num_hidden_layers": (88, 11),
           "hybrid_override_pattern": (pattern, "*EMEMEMEMEM"),
           "n_routed_experts": (512, 128), "vocab_size": (131072, 32768)}
    for key, value in published.items():
        if key in cut:
            assert (value, cell.config[key]) == cut[key], key
        else:
            assert cell.config[key] == value, key
    assert len(pattern) == 88 and pattern[25:36] == "*EMEMEMEMEM"
    assert [i for i, c in enumerate(pattern) if c == "*"] == [
        7, 16, 25, 36, 47, 58, 69, 78]
    for key, value in {
            "model_type": "nemotron_h", "hidden_size": 4096,
            "num_attention_heads": 32, "head_dim": 128,
            "num_key_value_heads": 2, "mamba_num_heads": 128,
            "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
            "conv_kernel": 4, "chunk_size": 128, "moe_latent_size": 1024,
            "moe_intermediate_size": 2688,
            "moe_shared_expert_intermediate_size": 5376,
            "num_experts_per_tok": 22, "routed_scaling_factor": 5,
            "mlp_hidden_act": "relu2", "norm_topk_prob": True,
            "tie_word_embeddings": False,
            "max_position_embeddings": 262144}.items():
        assert published[key] == value, key
    assert list(cell.config["reduced"]) == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    entry = next(c for c in m.doc["configs"] if c["name"] == REAL_CONFIG)
    assert entry["reduced"] == list(cell.config["reduced"])
    assert entry["source"] == cell.config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
        "BF16/blob/main/config.json")
    a = cell.config["model_args"]
    assert (a["hidden"], a["heads"], a["kv_heads"], a["head_dim"],
            a["vocab"], a["layer_pattern"], a["mamba_heads"],
            a["mamba_head_dim"], a["mamba_d_state"], a["mamba_groups"],
            a["num_experts"], a["experts_per_tok"], a["latent"],
            a["expert_hidden"], a["shared_hidden"], a["routed_scale"],
            a["mamba_chunk"], a["seq_len"]) == (
        4096, 32, 2, 128, 32768, "*EMEMEMEMEM", 128, 64, 128, 8, 512, 22,
        1024, 2688, 5376, 5.0, 128, 262144)
    assert a["experts_held"] == [0, 128]
    assert cell.config["layers_published"] == [25, 35]
    ref = cell.config["reference"]["args"]
    assert ref["layer_pattern"] == "*EMEMEMEMEM" and ref["held"] == [0, 128]
    assert {"no_positions", "no_mtp", "gate_then_norm", "dt", "latent_moe",
            "router", "state", "precision", "init",
            "init_gain"} <= set(cell.config["assumed"])
    assert "4 chips a layer" in cell.config["deployment"] \
        and "8 pipeline stages" in cell.config["deployment"]
    assert "4,648,163,712" in cell.config["size"] \
        and "120.67 B" in cell.config["size"]
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"][0] == REAL
    names = [e["name"] for e in m.doc["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 6] == list(NEW)            # together, in order
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    pairs = [(w["config"], w["traffic"]) for w in m.doc["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_the_models_size_against_the_issues_count(nemotron_args):
    a = nemotron_args
    assert rl.layer_kinds(a) == (5, 1, 5)
    assert rl.conv_width(a) == 10240 and rl.channels_of(a) == 8192
    assert rl.mamba_params(a) == 109_640_064
    assert rl.attention_params(a) == 35_655_680
    assert rl.routed_rest_params(a) == 54_530_560
    assert rl.expert_params(a) == 5_505_024 and rl.held_experts(a) == 128
    assert rl.held_params(a) == 4_648_163_712        # 9.30 GB
    # a sequence's state and windows
    h, conv = rl.needed_state_bytes(a, 1, 2)
    assert h == 5 * 128 * 64 * 128 * 4 and conv == 5 * 3 * 10240 * 2


def test_the_programs_tree_has_the_issues_count(nemotron_args):
    import jax
    from defer_tpu.models import nemotron_h
    graph = nemotron_h(**nemotron_args)
    tree = jax.eval_shape(graph.init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) \
        == rl.held_params(nemotron_args)
    kinds = [graph.nodes[f"block_{i}"].op.memory for i in range(11)]
    assert kinds == ["kv_cache"] + [None, "ssm"] * 5


def test_decode_step_needs_against_the_issues_count(nemotron_args):
    """The issue's reckoning of a step: ~14.65 GB, 17.9 ms at 819 GB/s
    — the held experts 7.05, the states in and out 5.37, the mixers'
    matrices 1.10, the E layers' other matrices 0.55, the head 0.27."""
    a = nemotron_args
    flops, nbytes = rl.decode_step_needs(
        a, rows=128, live_positions=2048, weight_bytes=2, kv_bytes=2)
    assert 14.4e9 < nbytes < 14.9e9
    assert abs(5 * 128 * rl.expert_params(a) * 2 - 7.05e9) < 0.01e9
    h, conv = rl.needed_state_bytes(a, 128, 2)
    assert abs(2 * h - 5.37e9) < 0.01e9
    assert flops / PEAKS["bf16_flops_per_s"] \
        < nbytes / PEAKS["hbm_bytes_per_s"]           # bound by memory
    # a layer's routed experts: 128 touched, 704 held pairs
    f, b = rl.latent_experts_needs(a, 128, 2, 128, 704)
    assert abs(b - (128 * 5_505_024 + 704 * (2048 + 5376)) * 2) < 1
    assert f == 2 * 704 * 5_505_024


def test_prefill_needs_against_the_issues_count(nemotron_args):
    a = nemotron_args
    flops, nbytes = rl.prefill_needs(a, rows=128, prompt_len=512,
                                     weight_bytes=2, kv_bytes=2)
    # 65,536 tokens x 2 x (0.86 B dense + 5 x 22/4 experts) and the scans
    assert 1.2e14 < flops < 1.5e14
    assert flops / PEAKS["bf16_flops_per_s"] \
        > nbytes / PEAKS["hbm_bytes_per_s"]           # bound by compute
    sf, sb = rl.ssd_scan_needs(a, 8, 512)
    assert sf == 8 * 512 * (2 * 128 * 128 * 8 + 2 * 128 * 8192
                            + 4 * 128 * 8192)
    assert rl.ssd_step_needs(a, 128)[1] == 4.0 * (
        2 * 128 * 8192 * 128 + 3 * 128 * 8192 + 2 * 128 * 1024)


def _run(counters, ops=(), modules=(), peaks=PEAKS):
    dev = DeviceTrace("/device:TPU:0")
    dev.ops, dev.modules = list(ops), list(modules)
    red = TraceReduction([dev], [("window", 0.0, 1.0)])
    return types.SimpleNamespace(trace=red, counters=counters, peaks=peaks,
                                 readings=[])


def _counters(a):
    return {"model_args": a, "rows": 128, "live_positions": 2048.0,
            "weight_bytes": 2, "kv_bytes": 2, "steps_per_reading": 32,
            "latent_moe_layers": 5, "mamba2_layers": 5,
            "experts_hit_share": 0.996, "held_share": 0.25,
            "experts_hit_a_layer_step": 127.5,
            "held_pairs_a_layer_step": 704.0,
            "prefill_tokens": 128 * 512, "prefill_piece_rows": 8,
            "max_len": 3584,
            "scope_ops": {"latent_down": ["fusion.7"],
                          "latent_experts": ["grouped_experts.1",
                                             "grouped_experts.2",
                                             "fusion.9"],
                          "latent_up": ["fusion.11"], "shared_expert": []}}


def test_the_readers_on_a_trace_made_by_hand(nemotron_args):
    """A step of 24 ms, a prefill of 3 s, kernels at twice their least
    time: the shares come out as the arithmetic says, all under 100."""
    m = Manifest()
    c = _counters(nemotron_args)
    step = 0.024
    f, b = rl.ssd_step_needs(nemotron_args, 128)
    t_step = 2 * b / PEAKS["hbm_bytes_per_s"]
    f, b = rl.latent_experts_needs(nemotron_args, 128, 2, 127.5, 704.0)
    t_pair = 2 * b / PEAKS["hbm_bytes_per_s"]
    ops = [("%ssd_step.3 = f32[] custom-call()", 0.1, 0.1 + t_step),
           ("%grouped_experts.1 = bf16[] custom-call()", 0.2,
            0.2 + 0.6 * t_pair),
           ("%grouped_experts.2 = bf16[] custom-call()", 0.3,
            0.3 + 0.4 * t_pair),
           ("%fusion.7 = bf16[] fusion()", 0.4, 0.401),
           ("%fusion.11 = bf16[] fusion()", 0.41, 0.411),
           ("%fusion.12 = bf16[] fusion()", 0.42, 0.45),
           ("%ssd_scan.2 = f32[] custom-call()", 0.9, 0.95)]
    modules = [("jit_device_decode(3)", 0.05, 0.05 + 32 * step),
               ("jit_device_prefill(4)", 0.88, 0.88 + 0.1)]
    run = _run(c, ops, modules)
    got = {name: m.reader(name).read(run) for name in NEW}
    assert abs(got["grouped_ssd_step_kernel_roofline"] - 50.0) < 1e-6
    assert abs(got["latent_experts_kernel_roofline"] - 50.0) < 1e-6
    _, need = rl.decode_step_needs(
        nemotron_args, rows=128, live_positions=2048.0, weight_bytes=2,
        kv_bytes=2, experts_hit_share=0.996)
    assert abs(got["ssd_latent_moe_decode_step_roofline"]
               - 100 * need / PEAKS["hbm_bytes_per_s"] / step) < 1e-6
    assert 70 < got["ssd_latent_moe_decode_step_roofline"] < 80
    assert 0 < got["ssd_latent_moe_prefill_roofline"] < 100 * 12
    assert 0 < got["grouped_ssd_scan_kernel_roofline"] < 100
    want = 100 * (t_pair + 0.002) / (32 * step)
    assert abs(got["latent_experts_step_share"] - want) < 1e-6
    # a program that holds more state than the configuration needs
    with pytest.raises(ValueError, match="state-space state"):
        m.reader(NEW[0]).read(_run(dict(
            c, ssm_state_bytes=1.2 * 2.72e9, ssm_conv_bytes=0.0), ops,
            modules))


def test_the_readers_return_nothing_without_their_counters(nemotron_args):
    """On a program that lacks what this cell's program has (the parent
    of the PR that added it: no such counters, no such scopes) every new
    reader returns None and raises nothing."""
    m = Manifest()
    bare = {"model_args": nemotron_args, "rows": 128, "weight_bytes": 2,
            "kv_bytes": 2, "steps_per_reading": 32,
            "live_positions": 600.0}
    ops = [("%ssd_step.3 = f32[] custom-call()", 0.1, 0.2),
           ("%grouped_experts.1 = bf16[] custom-call()", 0.2, 0.25)]
    modules = [("jit_device_decode(3)", 0.05, 0.8)]
    for run in (_run(bare, ops, modules), _run(bare),
                types.SimpleNamespace(trace=None, counters=bare, peaks=PEAKS,
                                      readings=[])):
        for name in NEW:
            assert m.reader(name).read(run) is None, name


def test_an_untraced_run_checks_tokens_router_memory_and_probe(root):
    doc = run_cell(workload=CELL, seed=2 ** 31 + 4321, seconds=1.0,
                   trace=False, t_start=time.perf_counter(), root=root,
                   require_tpu=False)
    json.dumps(doc)
    assert doc["correct"] is True and doc["failed"] == 0
    assert set(doc["metrics"]) == {"tokens_per_s", "setup_s"}
    assert doc["metrics"]["tokens_per_s"]["value"] > 0


def _context(cell, seed, trace=False):
    return types.SimpleNamespace(
        cell=cell, seed=seed, devices=[None], trace=trace,
        span=lambda name: __import__("contextlib").nullcontext())


def test_the_window_counts_and_the_check_holds_and_tells_the_controls(root):
    """Over a window ``decode.moe.assignments`` is rows x 3 x E layers x
    steps, ``decode.ssm.updates`` rows x Mamba layers x the same steps;
    the gauges pass the reader's check of what is held; the compiled
    decode program names operations under every scope; and each of the
    reference's controls fails one of the check's parts."""
    import jax.numpy as jnp
    from chipbench.drivers import batch_decode_ssd_latent_moe as drv
    cell = Manifest(root).cell(CELL)
    ctx = _context(cell, 5, trace=True)
    state = drv.setup(ctx)
    assert set(state["scope_ops"]) == set(drv.SCOPES)
    assert all(state["scope_ops"][s] for s in drv.SCOPES)
    out = drv.measure(state, 0.3, ctx)
    c = out["counters"]
    steps = c["decode.moe.assignments"] / (4 * 3 * 3)
    assert steps == int(steps) and steps > 0
    assert c["decode.ssm.updates"] == 4 * 2 * steps
    assert c["decode.moe.latent_rows"] == 4 * 3 * steps
    assert (c["mamba2_layers"], c["attention_layers"],
            c["latent_moe_layers"]) == (2, 1, 3)
    assert 0 < c["experts_hit_share"] <= 1 and 0 < c["held_share"] < 1
    assert c["ssm_bc_groups"] == 2 and c["moe_latent_width"] == 32
    assert c["memoryless_layers"] == 3
    assert c["ssm_conv_bytes"] == 2 * 4 * 3 * 320 * 4
    assert c["ssm_state_bytes"] - c["ssm_conv_bytes"] \
        == 2 * 4 * 16 * 256 * 4 == rl.needed_state_bytes(ARGS, 4, 4)[0]
    rl.check_held(dict(c, weight_bytes=4, kv_bytes=4), ARGS)
    tr, ref = cell.traffic, cell.config["reference"]
    dec = state["dec"]
    kinds = dec.memory
    ids, got = drv.decoded_memory(dec, state["prompts"], 2, tr)
    assert [g is None for g in got] == [False, True] * 3
    assert got[2][0].shape == (2, 8, 32, 16)
    assert got[2][1].shape == (2, 3, 320)
    assert got[0][0].shape == (2, 2, ids.shape[1], 16)
    states, rows = drv.memory_errors(got, kinds, state["params"], ids, ref)
    assert sorted(states) == MAMBA and sorted(rows) == [0]
    assert max(states.values()) < 1e-4 and max(rows.values()) < 1e-4
    for control, part in (({"one_bc_group": True}, states),
                          ({"window_shift": 1}, states),
                          ({"rotation_theta": 10000.0}, rows)):
        s, r = drv.memory_errors(got, kinds, state["params"], ids, ref,
                                 **control)
        moved = s if part is states else r
        assert max(moved.values()) > 0.05, control
    seqs = ids[:, :20]
    shares, branches, latents, mixers = drv.router_agreement(
        state["graph"], state["params"], seqs, ref)
    assert sorted(shares) == sorted(branches) == sorted(latents) == ROUTED
    assert sorted(mixers) == MAMBA and max(mixers.values()) < 1e-4
    # (one B/C group for all heads is the states' to fail, above: at
    # this size the skip term is most of a mixer's output)
    moved = drv.router_agreement(state["graph"], state["params"], seqs,
                                 ref, norm_one_group=True)[3]
    assert min(moved.values()) > drv.MIXER_TOL
    assert min(shares.values()) > 0.99 and max(branches.values()) < 1e-4
    assert max(latents.values()) < 1e-4
    for control in ({"activation": "silu"}, {"activation": "relu"},
                    {"routed_scale": 1.0}, {"drop_last": True}):
        moved = drv.router_agreement(state["graph"], state["params"],
                                     seqs, ref, **control)[2]
        assert min(moved.values()) > drv.LATENT_TOL, control
    weights = drv.router_weights_error(state["graph"], state["params"], 5,
                                       ref)
    assert weights < 1e-5 < drv.WEIGHTS_TOL
    # (a seeded bias of 0.001: let into the weights it moves them by its
    # own size)
    assert drv.router_weights_error(
        state["graph"], state["params"], 5, ref,
        bias_in_weights=True) > 3 * drv.WEIGHTS_TOL
    fmt = dec.state_formats[2]
    import importlib
    module = importlib.import_module(ref["module"])
    probe = drv.long_memory_error(fmt, 3, module, steps=64)
    assert max(probe.values()) < 1e-4
    held = drv.long_memory_error(fmt, 3, module, steps=64,
                                 held=jnp.bfloat16)
    assert max(held.values()) > 10 * max(probe.values())
    ok, detail = drv.check(state, ctx)
    assert ok and detail["tokens_compared"] == 2 * 6
    assert detail["worst_logit_gap_share"] <= 0
    assert sorted(detail["router_agreement_by_layer"]) == ROUTED
    assert sorted(detail["state_rel_err_by_layer"]) == MAMBA


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
                (f"%ssd_step.{i} = (f32[]) custom-call()",
                 at + 1e-5, at + 2e-4))
            devices[0].ops.append(
                (f"%grouped_experts.{i} = (f32[]) custom-call()",
                 at + 3e-4, at + 5e-4))
        devices[0].modules.append(
            ("jit_device_prefill(2)", lo + 5e-3, lo + 9e-3))
        devices[0].ops.append(
            ("%ssd_scan.3 = (f32[]) custom-call()", lo + 5e-3, lo + 6e-3))

    monkeypatch.setattr(trace.TraceReduction, "__init__", with_kernels)
    real = harness.Context.__init__

    def with_peaks(self, **kw):
        real(self, **dict(kw, peaks=PEAKS))

    monkeypatch.setattr(harness.Context, "__init__", with_peaks)
    doc = run_cell(workload=CELL, seed=11, seconds=1.0, trace=True,
                   t_start=time.perf_counter(), root=root, require_tpu=False)
    assert doc["correct"] is True
    per_layer = set(Manifest(root).cell(CELL).per_layer)
    assert {"decode_step_ms", "decode_prefill_ms"} \
        <= set(doc["metrics"]) <= per_layer
    # (the stand-in events carry none of the compiled program's own
    # operation names, so the scopes' share finds nothing to add up)
    for name in NEW[:5]:
        m = doc["metrics"][name]
        assert m["unit"] == "%" and 0 < m["value"], name
