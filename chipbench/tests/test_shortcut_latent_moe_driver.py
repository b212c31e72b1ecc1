"""The double-layer, shortcut-connected cell (LongCat-Flash): its driver,
readers and counts at a tiny preset on the CPU, through the harness; and
``roofline_shortcut_latent_moe`` against the counts of the issue that
asked for the cell."""

import json
import os
import time
import types

import pytest

import tiny
from chipbench import roofline_shortcut_latent_moe as rl
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

ARGS = {"num_layers": 2, "hidden": 64, "heads": 4, "q_rank": 24,
        "latent_dim": 112, "nope_dim": 16, "rope_dim": 16, "v_dim": 16,
        "dense_hidden": 96, "seq_len": 64, "vocab": 211, "num_experts": 16,
        "zero_experts": 8, "experts_per_tok": 4, "expert_hidden": 32,
        "routed_scale": 6.0, "experts_held": [0, 4],
        "rope_theta": 10000000.0, "rms_eps": 1e-05}
REF_ARGS = {"n_layer": 2, "n_head": 4, "nope": 16, "rope": 16, "latent": 112,
            "q_rank": 24, "n_experts": 16, "top_k": 4, "routed_scale": 6.0,
            "theta": 10000000.0, "held": [0, 4], "eps": 1e-05}
CONFIG = {"model_args": ARGS, "init_gain": {"embeddings/wte": 50.0},
          "reference": {"module": "chipbench.reference.longcat_flash",
                        "args": REF_ARGS}}
TRAFFIC = {"driver": "batch_decode_shortcut_latent_moe", "batch": 4,
           "prompt_len": 11, "new_tokens": 16, "token_chunk": 2,
           "max_len": 32, "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "check_tokens": 6, "trace_seconds": 0.5}
CELL = "shortcut_latent_moe_tiny"
REAL = "longcatflash_batch_decode"
SHARED = ("tokens_per_s", "decode_step_ms", "decode_chunk_ms",
          "decoder_launch_ms", "decode_device_idle_share",
          "decode_prefill_ms", "decode_host_serial_ms")
KIMIS = ("latent_attend_kernel_roofline", "latent_flash_kernel_roofline")
NEW = ("shortcut_latent_moe_decode_step_roofline",
       "shortcut_latent_moe_prefill_roofline", "zero_expert_pair_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def real_args():
    return Manifest().cell(REAL).config["model_args"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_shortcut_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "longcat-tiny", CONFIG),
                            ("traffic", "batch_shortcut_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "longcat-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/longcat-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "longcat-tiny",
        "traffic": "batch_shortcut_tiny", "chips": 1,
        "why": "tiny preset for the CPU tests"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in SHARED + KIMIS + NEW:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_real_manifest_gives_the_cell_its_files_and_metrics():
    m = Manifest()
    cell = m.cell(REAL)
    assert set(NEW) | set(KIMIS) | set(SHARED[1:]) <= set(cell.per_layer)
    assert {"decode_idle_wake_ms", "decode_idle_launch_ms",
            "decode_upload_ms", "decode_pause_share", "weights_relaid_leaves",
            "prefill_flash_live_share"} <= set(cell.per_layer)
    assert "latent_moe_decode_step_roofline" not in cell.per_layer
    assert cell.end_to_end == ("tokens_per_s", "setup_s")
    assert cell.chips == 1
    assert cell.traffic["driver"] == "batch_decode_shortcut_latent_moe"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk", "max_len",
        "compute_dtype", "kv_cache", "check_sequences",
        "check_tokens")} == {
        "batch": 16, "prompt_len": 6144, "new_tokens": 4096,
        "token_chunk": 32, "max_len": 10240, "compute_dtype": "bfloat16",
        "kv_cache": "buffer", "check_sequences": 2, "check_tokens": 512}
    # every number of the catalog's entry under its own key but the
    # three reduced, whose published values stand beside them
    cut = {"num_layers": (28, 4), "n_routed_experts": (512, 16),
           "vocab_size": (131072, 16384)}
    assert cell.config["published"] == {k: v[0] for k, v in cut.items()}
    assert {k: cell.config[k] for k in cut} == {
        k: v[1] for k, v in cut.items()}
    assert sorted(cell.config["reduced"]) == sorted(cut)
    for key, value in {
            "attention_bias": False, "hidden_size": 6144,
            "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
            "num_attention_heads": 64, "kv_lora_rank": 512,
            "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
            "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
            "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
            "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
            "rope_theta": 10000000, "attention_method": "MLA",
            "zero_expert_num": 256, "zero_expert_type": "identity",
            "moe_topk": 12}.items():
        assert cell.config[key] == value, key
    entry = next(c for c in m.doc["configs"]
                 if c["name"] == "longcat-flash-chat-4l-ep32")
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == cell.config["source"]
    assert m.doc["configs"][-1] == entry
    a = cell.config["model_args"]
    assert (a["num_layers"], a["hidden"], a["heads"], a["q_rank"],
            a["latent_dim"], a["nope_dim"], a["rope_dim"], a["v_dim"],
            a["dense_hidden"], a["vocab"], a["num_experts"],
            a["zero_experts"], a["experts_per_tok"], a["expert_hidden"],
            a["routed_scale"], a["experts_held"], a["rope_theta"],
            a["seq_len"]) == (
        4, 6144, 64, 1536, 512, 128, 64, 128, 12288, 16384, 512, 256, 12,
        2048, 6, [0, 16], 1e7, 131072)
    assert {"text_only", "double_layer", "mla_scale_q_lora",
            "mla_scale_kv_lora", "rope_pairs", "cache_rows", "router",
            "e_score_correction_bias", "untied_head", "no_mtp",
            "initialisation", "residuals"} <= set(cell.config["assumed"])
    assert "cached row" in cell.config["assumed"]["mla_scale_kv_lora"]
    assert "32 chips" in cell.config["deployment"]
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"] == [REAL]
    assert [e["name"] for e in m.doc["per_layer"][-3:]] == list(NEW)
    assert len(m.doc["workloads"]) >= 13
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1


def test_an_untraced_run_checks_logits_router_shortcut_and_rows(root):
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
    come out of the traced run's own counters, above 0 — Kimi's two
    kernel readers among them, on this family's counters."""
    import chipbench.harness as harness
    import chipbench.trace as trace

    monkeypatch.setattr(trace.TraceReduction, "module_runs",
                        lambda self, pattern, device=0: [2e-3, 3e-3, 4e-3])
    real_init = trace.TraceReduction.__init__

    def with_kernels(self, devices, spans):
        real_init(self, devices, spans)
        lo, _hi = self.window
        devices[0].ops.append(
            ("%latent_attend.3 = (f32[]) custom-call()", lo, lo + 1e-5))
        devices[0].ops.append(
            ("%flash_latent.7 = (f32[]) custom-call()", lo, lo + 1e-5))

    monkeypatch.setattr(trace.TraceReduction, "__init__", with_kernels)
    real = harness.Context.__init__

    def with_peaks(self, **kw):
        real(self, **dict(kw, peaks=PEAKS))

    monkeypatch.setattr(harness.Context, "__init__", with_peaks)
    doc = run_cell(workload=CELL, seed=11, seconds=1.0, trace=True,
                   t_start=time.perf_counter(), root=root, require_tpu=False)
    assert doc["correct"] is True
    per_layer = set(Manifest(root).cell(CELL).per_layer)
    assert set(NEW) | set(KIMIS) | {"decode_step_ms", "decode_prefill_ms"} \
        <= set(doc["metrics"]) <= per_layer
    for name in NEW + KIMIS:
        m = doc["metrics"][name]
        assert m["unit"] == "%" and 0 < m["value"] < 100
    # 8 zero columns of 24: about a third of the pairs
    assert 15 < doc["metrics"]["zero_expert_pair_share"]["value"] < 55


def _context(cell, seed, trace=False):
    return types.SimpleNamespace(
        cell=cell, seed=seed, devices=[None], trace=trace,
        span=lambda name: __import__("contextlib").nullcontext())


def test_the_window_counts_the_three_fates_and_both_sublayers_rows(root):
    """Over a window ``decode.moe.assignments`` is rows x 4 x layers x
    steps; every pair is a zero pair or a real one, a held pair is a
    real one; the gauges count two row buffers a layer and pass the
    reader's check of a row's bytes."""
    from chipbench.drivers import batch_decode_shortcut_latent_moe as drv
    cell = Manifest(root).cell(CELL)
    ctx = _context(cell, 5, trace=True)
    state = drv.setup(ctx)
    out = drv.measure(state, 0.3, ctx)
    c = out["counters"]
    steps = c["decode.moe.assignments"] / (4 * 4 * 2)
    assert steps == int(steps) and steps > 0
    assert c["decode.moe.zero_assignments"] \
        + c["decode.moe.real_assignments"] == c["decode.moe.assignments"]
    assert c["zero_share"] + c["real_share"] == pytest.approx(1.0)
    assert 0.15 < c["zero_share"] < 0.55
    assert 0 < c["held_share"] <= c["real_share"]
    assert 0 < c["experts_hit_share"] <= 1
    # two double layers of two sublayers, a group and the scratch group
    # of 4 sequences, 32 rows and the scratch row in whole sublane
    # tiles, 128 float32 columns
    assert c["cache_latent_sublayers"] == 4
    assert c["cache_latent_positions"] == 4 * 2 * 4 * 48
    assert c["cache_latent_bytes"] == 4 * 2 * 4 * 48 * 128 * 4
    assert "cache_window_bytes" not in c
    assert c["prefill_piece_rows"] == 4 and c["max_len"] == 32
    rl.check_row_bytes(c["cache_latent_bytes"], c["cache_latent_positions"],
                       ARGS, 4)
    ok, detail = drv.check(state, ctx)
    assert ok and detail["tokens_compared"] == 2 * 6
    assert detail["router_agreement_share"] > 0.99
    assert len(detail["router_agreement_by_layer"]) == 2
    assert detail["router_weights_rms_err"] < 1e-5 < drv.WEIGHTS_TOL
    assert detail["shortcut_rms_err"] < 1e-5 < drv.SHORTCUT_TOL
    assert detail["latent_probe_rel_err"] < 1e-4 < drv.LATENT_TOL_FIRST
    assert set(detail["latent_probe_rel_err_by_part"]) == {
        "0.0", "0.1", "1.0", "1.1"}
    assert detail["latent_probe_rel_err_upstream"] < 1e-4
    assert 0.15 < detail["zero_choice_share"] < 0.55


def test_the_probe_and_the_shortcut_tell_the_controls(root):
    """Both sublayers' rows are read back behind the prefill *and*
    decode steps; a cache kept in float8 shows in the first block's
    rows, a reference without zero-compute experts (or one that
    renormalises, or whose bias weighs) in the shortcut's and the
    weights' checks, and a reference without the LoRA scales in the
    rows."""
    import jax.numpy as jnp
    from chipbench.agreement import rel_err
    from chipbench.drivers import batch_decode_shortcut_latent_moe as drv
    cell = Manifest(root).cell(CELL)
    state = drv.setup(_context(cell, 6))
    tr, ref = cell.traffic, cell.config["reference"]
    ids, got = drv.cached_rows(state["dec"], state["prompts"], 2, tr, (0, 1))
    steps = min(drv.PROBE_STEPS, tr["new_tokens"] - 1)
    assert ids.shape == (2, tr["prompt_len"] + steps) and steps > 0
    assert len(got[0]) == len(got[1]) == 2
    assert got[0][1].shape == got[1][0].shape == (2, ids.shape[1], 128)
    sound = drv.reference_extras(state["params"], ids, ref)
    for l in (0, 1):
        for sub, key in enumerate(("rows", "rows_1")):
            assert rel_err(got[l][sub], sound[l][key]) < 1e-4
    # the two sublayers' rows are not each other's
    assert rel_err(got[0][0], sound[0]["rows_1"]) > 0.1
    narrow = drv.reference_extras(state["params"], ids, ref,
                                  row_dtype=jnp.float8_e4m3fn)
    assert rel_err(got[0][0], narrow[0]["rows"]) > drv.LATENT_TOL_FIRST
    plain = drv.reference_extras(state["params"], ids, ref, plain_lora=True)
    assert rel_err(got[0][0], plain[0]["rows"]) > 10 * drv.LATENT_TOL_FIRST
    agreement = drv.program_agreement(state["graph"], state["params"], ids,
                                      sound)
    assert min(agreement["shares"]) > 0.99
    assert max(agreement["weights"]) < 1e-5
    assert max(agreement["shortcut"]) < 1e-5
    assert max(agreement["rows"]) < 1e-4
    for control, part in (("no_zero_experts", "shortcut"),
                          ("renormalise", "weights"),
                          ("bias_weighs", "weights")):
        wrong = drv.reference_extras(state["params"], ids, ref,
                                     **{control: True})
        other = drv.program_agreement(state["graph"], state["params"], ids,
                                      wrong)
        # (the preset's bias of 0.001 stands beside probabilities of
        # 1 / 24 where the cell's are 1 / 768: held to 1000x the sound
        # reading, not to the cell's limit)
        limit = {"no_zero_experts": drv.SHORTCUT_TOL,
                 "renormalise": drv.WEIGHTS_TOL,
                 "bias_weighs": 1000 * max(agreement["weights"])}[control]
        assert min(other[part]) > limit, control


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.moe.zero_assignments`` and no
    ``decode.cache.latent_sublayers`` (the parent) or off the chip a
    reader gives None and does not raise — with Kimi's counters too."""
    mf = Manifest()
    kimi = {"cache_latent_bytes": 1.0, "cache_latent_positions": 1.0,
            "experts_hit_share": 0.5, "held_share": 0.03,
            "decode.moe.assignments": 100}
    for counters in ({}, kimi):
        run = types.SimpleNamespace(
            trace=types.SimpleNamespace(module_runs=lambda pattern: [1e-3]),
            counters=counters, peaks=PEAKS)
        for name in NEW:
            assert mf.reader(name).read(run) is None
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS)
    for name in NEW:
        assert mf.reader(name).read(run) is None


def test_the_models_size_against_the_issues_count(real_args):
    """90,572,800 parameters an attention sublayer, 226,492,416 a dense
    SwiGLU, 4,719,360 the router, 638,874,368 a double layer outside its
    routed experts, 37,748,736 an expert; 5,172,749,312 held, 10.35
    GB."""
    a = real_args
    p = rl.layer_params(a)
    assert p["attention"] == 90_572_800 and p["dense"] == 226_492_416
    assert p["router"] == 4_719_360 and p["expert"] == 37_748_736
    assert 2 * (p["attention"] + p["dense"]) + p["norms"] + p["router"] \
        == 638_874_368
    assert rl.held_experts(a) == 16 and rl.SUBLAYERS == 2
    assert rl.held_params(a) == 5_172_749_312
    assert round(2 * rl.held_params(a) / 1e9, 2) == 10.35
    assert rl.row_values(a) == 576


def test_decode_step_needs_against_the_issues_count(real_args):
    """A step of 16 rows at 8192 positions, 3.5 of 16 held experts
    touched a layer: dense halves 3.62 GB (48%), attention matrices
    1.45, rows 1.18 (1.23 with queries and outputs), touched experts
    1.06, head 0.20: 7.6 GB, 9.3 ms at the memory peak; 0.23 TFLOP, 1.2
    ms at the matrix peak."""
    a = real_args
    kw = dict(rows=16, positions=8192, experts_hit_share=3.5 / 16,
              weight_bytes=2, kv_bytes=2)
    parts = rl.step_bytes_by_part(a, **kw)
    assert round(parts["dense"] / 1e9, 2) == 3.62
    assert round(parts["attention"] / 1e9, 2) == 1.45
    assert round(parts["experts"] / 1e9, 2) == 1.06
    assert round(parts["head"] / 1e9, 2) == 0.20
    rows = 16 * 8192 * 8 * 1152
    assert round(rows / 1e9, 2) == 1.21
    queries = 8 * 16 * 64 * (576 + 512) * 2
    assert parts["rows"] == rows + queries
    flops, nbytes = rl.decode_step_needs(a, held_share=16 / 768,
                                         real_share=2 / 3, **kw)
    assert nbytes == sum(parts.values())
    assert round(nbytes / 1e9, 1) == 7.6
    assert 0.47 < parts["dense"] / nbytes < 0.49
    assert round(1e3 * nbytes / 819e9, 1) == 9.3
    call_flops, _ = rl.attend_call_needs(a, rows=16, positions=8192,
                                         kv_bytes=2)
    held_pairs = 16 / 768 * 12                  # a token a layer
    assert flops == pytest.approx(
        16 * 2 * (rl.fixed_params(a) + 4 * held_pairs * 37_748_736)
        + 8 * call_flops)
    assert round(1e3 * flops / 197e12, 1) == 1.2
    # zero pairs cost nothing: more of them change no count
    again = rl.decode_step_needs(a, held_share=16 / 768, real_share=0.5,
                                 **kw)
    assert again == (flops, nbytes)
    with pytest.raises(ValueError, match="held_share 0.5000 over "
                       "real_share 0.4000"):
        rl.decode_step_needs(a, held_share=0.5, real_share=0.4, **kw)


def test_prefill_needs_against_the_issues_count(real_args):
    """16 x 6144 tokens: 0.61 PFLOP, 3.1 s at the matrix peak; the
    causal attention of one sequence in one sublayer 0.77 TFLOP, 8 x 16
    of them."""
    a = real_args
    one = rl.flash_flops(a, rows=1, prompt_len=6144)
    assert one == 6144 * 6145 / 2 * 64 * 2 * 320
    assert round(one / 1e12, 2) == 0.77
    flops, nbytes = rl.prefill_needs(a, rows=16, prompt_len=6144,
                                     held_share=16 / 768, real_share=2 / 3,
                                     weight_bytes=2, kv_bytes=2)
    assert round(flops / 1e15, 2) == 0.61
    assert round(flops / 197e12, 1) == 3.1
    assert flops / 197e12 > nbytes / 819e9
    held_pairs = 16 / 768 * 12
    assert flops == pytest.approx(
        16 * 6144 * 2 * (rl.fixed_params(a) - 100_663_296
                         + 4 * held_pairs * 37_748_736)
        + 8 * 16 * one + 16 * 2 * 100_663_296)


def test_weights_made_a_node_at_a_time_are_the_initialisers_own():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.drivers import batch_decode_shortcut_latent_moe as drv
    from defer_tpu import models

    graph = models.longcat_flash(**ARGS)
    seed = 2 ** 31 + 77
    got = drv.make_weights(graph, seed, jnp.bfloat16,
                           {"embeddings/wte": 50.0})
    want = graph.init(jax.random.key(seed % (2 ** 31 - 1)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert got["block_0"]["router"]["w"].shape == (64, 24)
    assert got["block_0"]["experts"]["gate"].shape == (4, 64, 32)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got), strict=True):
        name = "/".join(k.key for k in path)
        gain = 50.0 if name == "embeddings/wte" else 1.0
        assert b.dtype == jnp.bfloat16 and isinstance(b, np.ndarray)
        np.testing.assert_allclose(
            b.astype(np.float32), np.asarray(a * gain), rtol=2 ** -7,
            atol=1e-30, err_msg=name)
