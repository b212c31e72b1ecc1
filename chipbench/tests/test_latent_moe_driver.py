"""The latent-attention cell with routed experts: its driver, readers and
counts at a tiny preset on the CPU, through the harness; and
``roofline_latent_moe`` against the counts of the issue that asked for
the cell."""

import json
import os
import time
import types

import pytest

import tiny
from chipbench import roofline_latent_moe as rl
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

ARGS = {"num_layers": 5, "hidden": 64, "heads": 4, "q_rank": 24,
        "latent_dim": 112, "nope_dim": 16, "rope_dim": 16, "v_dim": 16,
        "dense_hidden": 96, "seq_len": 64, "vocab": 211, "num_experts": 16,
        "experts_per_tok": 4, "expert_hidden": 32, "num_shared": 1,
        "routed_scale": 2.827, "dense_layers": 1, "experts_held": [0, 4],
        "rope_theta": 50000.0, "rope_factor": 4.0, "rope_original": 8,
        "rms_eps": 1e-05}
REF_ARGS = {"n_layer": 5, "n_head": 4, "nope": 16, "rope": 16, "latent": 112,
            "top_k": 4, "routed_scale": 2.827, "theta": 50000.0,
            "factor": 4.0, "original": 8, "held": [0, 4], "eps": 1e-05}
CONFIG = {"model_args": ARGS, "init_gain": {"embeddings/wte": 50.0},
          "reference": {"module": "chipbench.reference.kimi_k2",
                        "args": REF_ARGS}}
TRAFFIC = {"driver": "batch_decode_latent_moe", "batch": 4, "prompt_len": 11,
           "new_tokens": 16, "token_chunk": 2, "max_len": 32,
           "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "check_tokens": 6, "trace_seconds": 0.5}
CELL = "latent_moe_tiny"
REAL = "kimik2_batch_decode"
SHARED = ("tokens_per_s", "decode_step_ms", "decode_chunk_ms",
          "decoder_launch_ms", "decode_device_idle_share",
          "decode_prefill_ms", "decode_host_serial_ms")
NEW = ("latent_moe_decode_step_roofline", "latent_attend_kernel_roofline",
       "latent_moe_prefill_roofline", "latent_flash_kernel_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def real_args():
    return Manifest().cell(REAL).config["model_args"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_latent_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "kimi-tiny", CONFIG),
                            ("traffic", "batch_latent_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "kimi-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/kimi-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "kimi-tiny", "traffic": "batch_latent_tiny",
        "chips": 1, "why": "tiny preset for the CPU tests"})
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
    assert {"decode_idle_wake_ms", "decode_idle_launch_ms",
            "decode_upload_ms", "decode_pause_share"} <= set(cell.per_layer)
    assert "window_moe_decode_step_roofline" not in cell.per_layer
    assert cell.end_to_end == ("tokens_per_s", "setup_s")
    assert cell.chips == 1
    assert cell.traffic["driver"] == "batch_decode_latent_moe"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk", "max_len",
        "compute_dtype", "kv_cache", "check_sequences",
        "check_tokens")} == {
        "batch": 32, "prompt_len": 8192, "new_tokens": 4096,
        "token_chunk": 32, "max_len": 12288, "compute_dtype": "bfloat16",
        "kv_cache": "buffer", "check_sequences": 2, "check_tokens": 512}
    # every number of the catalog's entry under its own key but the
    # three reduced, whose published values stand beside them
    cut = {"num_hidden_layers": (61, 5), "n_routed_experts": (384, 12),
           "vocab_size": (163840, 20480)}
    assert cell.config["published"] == {k: v[0] for k, v in cut.items()}
    assert {k: cell.config[k] for k in cut} == {
        k: v[1] for k, v in cut.items()}
    assert sorted(cell.config["reduced"]) == sorted(cut)
    for key, value in {
            "hidden_size": 7168, "q_lora_rank": 1536, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "intermediate_size": 18432,
            "moe_intermediate_size": 2048, "num_attention_heads": 64,
            "num_experts_per_tok": 8, "routed_scaling_factor": 2.827,
            "n_shared_experts": 1, "first_k_dense_replace": 1,
            "rope_theta": 50000, "rms_norm_eps": 1e-05,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "max_position_embeddings": 262144}.items():
        assert cell.config[key] == value, key
    assert cell.config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert "head_dim" not in cell.config
    entry = next(c for c in m.doc["configs"]
                 if c["name"] == "kimi-k2.7-code-5l-ep32")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == cell.config["source"]
    a = cell.config["model_args"]
    assert (a["num_layers"], a["hidden"], a["heads"], a["q_rank"],
            a["latent_dim"], a["nope_dim"], a["rope_dim"], a["v_dim"],
            a["dense_hidden"], a["vocab"], a["num_experts"],
            a["experts_per_tok"], a["expert_hidden"], a["num_shared"],
            a["routed_scale"], a["dense_layers"], a["experts_held"]) == (
        5, 7168, 64, 1536, 512, 128, 64, 128, 18432, 20480, 384, 8, 2048, 1,
        2.827, 1, [0, 12])
    assert (a["rope_theta"], a["rope_factor"], a["rope_original"],
            a["beta_fast"], a["beta_slow"], a["mscale_all_dim"]) == (
        50000, 64, 4096, 32, 1, 1)
    assert {"text_only", "rope_pairs", "yarn", "cache_rows", "kv_b_proj",
            "e_score_correction_bias", "initialisation",
            "head_dim"} <= set(cell.config["assumed"])
    assert "32 chips" in cell.config["deployment"]
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"] == [REAL]
    # (a later PR may add cells: here only that this one is among at
    # least nine, on one chip, and that one cell alone takes four)
    assert len(m.doc["workloads"]) >= 9
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1


def test_an_untraced_run_checks_logits_router_and_rows(root):
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
    assert set(NEW) | {"decode_step_ms", "decode_prefill_ms"} \
        <= set(doc["metrics"]) <= per_layer
    for name in NEW:
        m = doc["metrics"][name]
        assert m["unit"] == "%" and 0 < m["value"] < 100


def _context(cell, seed, trace=False):
    return types.SimpleNamespace(
        cell=cell, seed=seed, devices=[None], trace=trace,
        span=lambda name: __import__("contextlib").nullcontext())


def test_the_window_counts_routing_and_the_ring_s_rows(root):
    """Over a window ``decode.moe.assignments`` is rows x 4 x routed
    layers x steps (the dense layer sows zeros) and about a quarter
    fall to the four held experts; the gauges are the ring's own and
    pass the reader's check of a row's bytes."""
    from chipbench.drivers import batch_decode_latent_moe as drv
    cell = Manifest(root).cell(CELL)
    ctx = _context(cell, 5, trace=True)
    state = drv.setup(ctx)
    out = drv.measure(state, 0.3, ctx)
    c = out["counters"]
    steps = c["decode.moe.assignments"] / (4 * 4 * 4)
    assert steps == int(steps) and steps > 0
    assert 0.05 < c["held_share"] < 0.6 and 0 < c["experts_hit_share"] <= 1
    # five layers, a group and the scratch group of 4 sequences, 32 rows
    # and the scratch row in whole sublane tiles, 128 float32 columns
    assert c["cache_latent_positions"] == 5 * 2 * 4 * 48
    assert c["cache_latent_bytes"] == 5 * 2 * 4 * 48 * 128 * 4
    assert "cache_window_bytes" not in c
    assert c["prefill_piece_rows"] == 4 and c["max_len"] == 32
    # (the preset's row is one whole lane tile: nothing is padded)
    rl.check_row_bytes(c["cache_latent_bytes"], c["cache_latent_positions"],
                       ARGS, 4)
    ok, detail = drv.check(state, ctx)
    assert ok and detail["tokens_compared"] == 2 * 6
    assert detail["router_agreement_share"] > 0.99
    assert len(detail["router_agreement_by_layer"]) == 4
    assert detail["router_weights_rms_err"] < 1e-5 < drv.WEIGHTS_TOL
    assert detail["latent_probe_rel_err"] < 1e-4 < drv.LATENT_TOL_FIRST
    assert set(detail["latent_probe_rel_err_by_part"]) == {"0", "1", "4"}
    assert detail["latent_probe_rel_err_upstream"] < 1e-4
    assert len(detail["forward_rows_rel_err_by_layer"]) == 5


def test_the_probe_reads_what_the_prefill_and_the_steps_wrote(root):
    """The rows are read back behind the prefill *and* decode steps and
    held to the reference over the prompt and the tokens fed back: a
    cache kept in float8, a score without ``m ** 2`` and a bias that
    weighs each show, by the part the controls name."""
    import jax.numpy as jnp
    from chipbench.agreement import rel_err
    from chipbench.drivers import batch_decode_latent_moe as drv
    cell = Manifest(root).cell(CELL)
    state = drv.setup(_context(cell, 6))
    tr, ref = cell.traffic, cell.config["reference"]
    ids, got = drv.cached_rows(state["dec"], state["prompts"], 2, tr, (0, 4))
    steps = min(drv.PROBE_STEPS, tr["new_tokens"] - 1)
    assert ids.shape == (2, tr["prompt_len"] + steps) and steps > 0
    assert got[0].shape == got[4].shape == (2, ids.shape[1], 128)
    sound = drv.reference_extras(state["params"], ids, ref)
    assert max(rel_err(got[l], sound[l]["rows"]) for l in (0, 4)) < 1e-4
    narrow = drv.reference_extras(state["params"], ids, ref,
                                  row_dtype=jnp.float8_e4m3fn)
    assert rel_err(got[0], narrow[0]["rows"]) > drv.LATENT_TOL_FIRST
    flat = drv.reference_extras(state["params"], ids, ref, plain_scale=True)
    assert rel_err(got[0], flat[0]["rows"]) < 1e-4      # one layer in: same
    assert rel_err(got[4], flat[4]["rows"]) > 1e-2
    agreement = drv.program_agreement(state["graph"], state["params"], ids,
                                      sound)
    assert min(agreement["shares"]) > 0.99
    assert max(agreement["weights"]) < 1e-5
    assert max(agreement["rows"]) < 1e-4
    weighs = drv.reference_extras(state["params"], ids, ref,
                                  bias_weighs=True)
    other = drv.program_agreement(state["graph"], state["params"], ids,
                                  weighs)
    assert min(other["weights"]) > drv.WEIGHTS_TOL


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.cache.latent_*`` (the parent) or
    off the chip a reader gives None and does not raise."""
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS)
    mf = Manifest()
    for name in NEW:
        assert mf.reader(name).read(run) is None


def test_the_models_size_against_the_issues_count(real_args):
    """101,124,096 parameters an attention, 147,931,520 a routed layer
    outside its routed experts, 44,040,192 an expert, 497,500,160 the
    dense layer; 3,496,756,736 held, 6.99 GB."""
    a = real_args
    p = rl.layer_params(a)
    assert p["attention"] == 101_124_096 and p["expert"] == 44_040_192
    assert p["router"] == 2_752_896 and p["shared"] == 44_040_192
    assert p["attention"] + p["norms"] + p["router"] + p["shared"] \
        == 147_931_520
    assert p["attention"] + p["norms"] + p["dense"] == 497_500_160
    assert rl.layer_counts(a) == (1, 4) and rl.held_experts(a) == 12
    assert rl.held_params(a) == 3_496_756_736
    assert round(2 * rl.held_params(a) / 1e9, 2) == 6.99
    assert rl.row_values(a) == 576 and rl.row_flops(a) == 139_264


def test_decode_step_needs_against_the_issues_count(real_args):
    """A step of 32 rows at 10240 positions: latent rows 1.89 GB, 6.02
    touched experts a layer 2.12 GB: 6.5 GB, 7.9 ms at the memory peak;
    the rows' products 0.23 TFLOP, 1.16 ms at the matrix peak, under
    their 2.3 ms of bytes."""
    a = real_args
    flops, nbytes = rl.decode_step_needs(
        a, rows=32, positions=10240, experts_hit_share=6.02 / 12,
        held_share=1 / 32, weight_bytes=2, kv_bytes=2)
    rows = 32 * 10240 * 5 * 1152
    assert round(rows / 1e9, 2) == 1.89
    experts = 4 * 6.02 * 44_040_192 * 2
    assert round(experts / 1e9, 2) == 2.12
    queries = 5 * 32 * 64 * (576 + 512) * 2
    assert nbytes == pytest.approx(2 * rl.fixed_params(a) + experts + rows
                                   + queries + 32 * 20480 * 4)
    assert round(nbytes / 1e9, 1) == 6.5
    assert round(1e3 * nbytes / 819e9, 1) == 7.9
    call_flops, call_bytes = rl.attend_call_needs(a, rows=32,
                                                  positions=10240,
                                                  kv_bytes=2)
    assert round(5 * call_flops / 1e12, 2) == 0.23
    assert round(1e3 * 5 * call_flops / 197e12, 2) == 1.16
    assert round(1e3 * 5 * call_bytes / 819e9, 1) == 2.3
    assert round(call_flops / call_bytes) == 119       # 121 of rows alone
    assert flops / 197e12 < nbytes / 819e9
    _, fewer = rl.decode_step_needs(
        a, rows=32, positions=10240, experts_hit_share=0.25,
        held_share=1 / 32, weight_bytes=2, kv_bytes=2)
    assert nbytes - fewer == pytest.approx(
        (6.02 / 12 - 0.25) * 4 * 12 * 44_040_192 * 2)


def test_prefill_and_kernel_needs_against_the_issues_count(real_args):
    """262,144 tokens x 2 x 1.133 B = 0.59 PFLOP of matrices and 32 x 5
    x 1.37 TFLOP = 0.22 of causal attention at 192 + 128 a pair: 0.81
    PFLOP, 4.1 s at the matrix peak."""
    a = real_args
    one = rl.flash_flops(a, rows=1, prompt_len=8192)
    assert round(one / 1e12, 2) == 1.37
    assert one == 8192 * 8193 / 2 * 64 * 2 * 320
    flops, nbytes = rl.prefill_needs(a, rows=32, prompt_len=8192,
                                     held_share=1 / 32, weight_bytes=2,
                                     kv_bytes=2)
    assert round((flops - 32 * 5 * one) / 1e15, 2) == 0.59
    assert round(flops / 1e15, 2) == 0.81
    assert round(flops / 197e12, 1) == 4.1
    assert flops / 197e12 > nbytes / 819e9
    more, _ = rl.prefill_needs(a, rows=32, prompt_len=8192, held_share=1.0,
                               weight_bytes=2, kv_bytes=2)
    assert more > flops


def test_a_fatter_row_is_refused_and_cannot_raise_a_share(real_args):
    """The need is the configuration's 1152 B a row: the program's 640
    columns (1.111 of it) pass, a row of 768 is refused, and a program
    with no gauges is not judged."""
    a = real_args
    rl.check_row_bytes(1280 * 1000, 1000, a, 2)
    rl.check_row_bytes(0, 0, a, 2)
    with pytest.raises(ValueError, match="1536 B, 1.3333 times the 1152 B"):
        rl.check_row_bytes(1536 * 1000, 1000, a, 2)


def test_weights_made_a_node_at_a_time_are_the_initialisers_own():
    """The driver draws each node as ``graph.init`` would, scaled where
    the configuration says and cast; the head stays the model's own."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.drivers import batch_decode_latent_moe as drv
    from defer_tpu import models

    graph = models.kimi_k2(**ARGS)
    seed = 2 ** 31 + 77
    got = drv.make_weights(graph, seed, jnp.bfloat16,
                           {"embeddings/wte": 50.0})
    want = graph.init(jax.random.key(seed % (2 ** 31 - 1)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert got["lm_head"]["w"].shape == (64, 211)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got), strict=True):
        name = "/".join(k.key for k in path)
        gain = 50.0 if name == "embeddings/wte" else 1.0
        assert b.dtype == jnp.bfloat16 and isinstance(b, np.ndarray)
        np.testing.assert_allclose(
            b.astype(np.float32), np.asarray(a * gain), rtol=2 ** -7,
            atol=1e-30, err_msg=name)
