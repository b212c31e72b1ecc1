"""The Mamba-2 / attention hybrid cell with routed experts: its driver,
readers and counts at a tiny preset on the CPU, through the harness; and
``roofline_hybrid_moe`` against the counts of the issue that asked for
the cell."""

import json
import os
import time
import types

import pytest

import tiny
from chipbench import roofline_hybrid_moe as rh
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

TYPES = ["mamba", "mamba", "attention", "mamba"]
ARGS = {"num_layers": 8, "hidden": 64, "heads": 4, "kv_heads": 2,
        "head_dim": 16, "seq_len": 64, "vocab": 211, "layer_types": TYPES,
        "mamba_heads": 8, "mamba_head_dim": 16, "mamba_d_state": 16,
        "num_experts": 8, "experts_per_tok": 3, "expert_hidden": 32,
        "shared_hidden": 64, "mamba_d_conv": 4, "mamba_chunk": 8,
        "experts_held": [0, 4], "embedding_multiplier": 6.0,
        "residual_multiplier": 0.35, "attention_multiplier": 0.1,
        "logits_scaling": 4.0, "rms_eps": 1e-05}
CONFIG = {"model_args": ARGS,
          "init_gain": {"q/w": 2.0, "k/w": 2.0, "embeddings/wte": 0.5},
          "reference": {"module": "chipbench.reference.granite_hybrid",
                        "args": {"layer_types": TYPES * 2, "n_head": 4,
                                 "n_kv": 2, "head_dim": 16, "mamba_heads": 8,
                                 "d_state": 16, "top_k": 3, "held": [0, 4],
                                 "attention_multiplier": 0.1,
                                 "residual_multiplier": 0.35,
                                 "embedding_multiplier": 6.0,
                                 "logits_scaling": 4.0, "eps": 1e-05}}}
TRAFFIC = {"driver": "batch_decode_hybrid_moe", "batch": 4, "prompt_len": 11,
           "new_tokens": 16, "token_chunk": 2, "max_len": 32,
           "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "check_tokens": 6, "trace_seconds": 0.5}
CELL = "hybrid_moe_tiny"
REAL = "granite4h_batch_decode"
SHARED = ("tokens_per_s", "decode_step_ms", "decode_chunk_ms",
          "decoder_launch_ms", "decode_device_idle_share",
          "decode_prefill_ms", "decode_host_serial_ms")
NEW = ("hybrid_moe_decode_step_roofline", "ssd_step_kernel_roofline",
       "hybrid_moe_prefill_roofline", "ssd_scan_kernel_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def real_args():
    return Manifest().cell(REAL).config["model_args"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_hmoe_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "granite-tiny", CONFIG),
                            ("traffic", "batch_hmoe_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "granite-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/granite-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "granite-tiny", "traffic": "batch_hmoe_tiny",
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
    assert "hybrid_ssm_decode_step_roofline" not in cell.per_layer
    assert cell.end_to_end == ("tokens_per_s", "setup_s")
    assert cell.chips == 1
    assert cell.traffic["driver"] == "batch_decode_hybrid_moe"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk", "max_len",
        "compute_dtype", "kv_cache", "check_sequences",
        "check_tokens")} == {
        "batch": 64, "prompt_len": 1024, "new_tokens": 2048,
        "token_chunk": 32, "max_len": 3072, "compute_dtype": "bfloat16",
        "kv_cache": "buffer", "check_sequences": 2, "check_tokens": 512}
    # every published number under its own key but the three reduced
    published = cell.config["published"]
    cut = {"num_hidden_layers": (40, 10), "num_local_experts": (72, 36),
           "vocab_size": (100352, 50176)}
    for key, value in published.items():
        if key in cut:
            assert (value, cell.config[key]) == cut[key]
        else:
            assert cell.config[key] == value, key
    assert sorted(cell.config["reduced"]) == sorted(cut)
    entry = next(c for c in m.doc["configs"]
                 if c["name"] == "granite-4.0-h-small-10l-ep2")
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts",
                                "vocab_size"]
    assert entry["source"] == cell.config["source"]
    a = cell.config["model_args"]
    assert (a["num_layers"], a["hidden"], a["heads"], a["kv_heads"],
            a["head_dim"], a["vocab"], a["mamba_heads"],
            a["mamba_head_dim"], a["mamba_d_state"], a["mamba_d_conv"],
            a["mamba_chunk"], a["num_experts"], a["experts_per_tok"],
            a["expert_hidden"], a["shared_hidden"], a["experts_held"]) == (
        10, 4096, 32, 8, 128, 50176, 128, 64, 128, 4, 256, 72, 10, 768,
        1536, [0, 36])
    assert a["layer_types"] == published["layer_types"][:10]
    assert a["layer_types"].index("attention") == 5
    assert (a["embedding_multiplier"], a["residual_multiplier"],
            a["attention_multiplier"], a["logits_scaling"]) == (
        12, 0.22, 0.0078125, 16)
    assert {"head_dim", "expert_width", "gate_up_order", "gate_then_norm",
            "state", "init", "init_gain", "weights", "untrained"} <= set(
                cell.config["assumed"])
    assert "8 chips" in cell.config["deployment"]
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"] == [REAL]
    # (the number of cells is other tests' to pin, and a later PR's to
    # move: here only that this one is among them, on one chip)
    assert len(m.doc["workloads"]) >= 8


def test_an_untraced_run_checks_tokens_router_state_and_memory(root):
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
            ("%ssd_step.3 = (f32[]) custom-call()", lo, lo + 1e-4))
        devices[0].ops.append(
            ("%ssd_scan.7 = (f32[]) custom-call()", lo, lo + 1e-4))

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


def test_the_window_counts_routing_updates_and_the_ring_s_bytes(root):
    """Over a window ``decode.moe.assignments`` is rows x 3 x layers x
    steps and half of them fall to the four held experts;
    ``decode.ssm.updates`` is sequences x Mamba layers x the decode
    steps; the gauges are the ring's own and pass the reader's check of
    what is held."""
    from chipbench.drivers import batch_decode_hybrid_moe as drv
    cell = Manifest(root).cell(CELL)
    ctx = _context(cell, 5, trace=True)
    state = drv.setup(ctx)
    out = drv.measure(state, 0.3, ctx)
    c = out["counters"]
    steps = c["decode.ssm.updates"] / (4 * 6)
    assert steps == int(steps) and steps > 0 and c["mamba2_layers"] == 6
    assert c["decode.moe.assignments"] == 4 * 3 * 8 * steps
    assert 0.3 < c["held_share"] < 0.7 and 0 < c["experts_hit_share"] <= 1
    conv = 6 * 4 * 3 * 160 * 4
    assert c["ssm_conv_bytes"] == conv
    assert c["ssm_state_bytes"] == 6 * 4 * 16 * 128 * 4 + conv
    assert c["cache_full_bytes"] == 2 * 2 * 4 * 33 * 2 * 16 * 4 * 2
    # no leaf rides a flat row since PR 44: the driver reads no such gauge
    assert "weights_row_bytes" not in c and c["weights_own_bytes"] > 0
    assert c["prefill_piece_rows"] == 4 and c["max_len"] == 32
    # float32 windows here: 4 bytes a value
    rh.check_held(dict(c, weight_bytes=4, kv_bytes=4), ARGS)
    ok, detail = drv.check(state, ctx)
    assert ok and detail["state_rel_err"] < 1e-4 < drv.STATE_TOL_FIRST
    assert sorted(detail["state_rel_err_by_layer"]) == [0, 1, 3, 4, 5, 7]
    assert detail["long_memory_rel_err"] < 1e-4 < drv.MEMORY_TOL
    assert detail["router_agreement_share"] > 0.99
    assert len(detail["router_agreement_by_layer"]) == 8
    assert detail["expert_half_rms_err"] < 1e-3 < drv.WEIGHTS_TOL
    assert detail["tokens_compared"] == 2 * 6


def test_the_state_check_reads_what_the_decode_steps_wrote(root):
    """The state is read back behind the prefill *and* decode steps, and
    held to the reference over the prompt and the tokens fed back:
    against a window one position off the comparison fails; so it does
    against a reference whose own state is kept in bfloat16 only by
    what that mantissa costs."""
    import jax.numpy as jnp
    from chipbench.drivers import batch_decode_hybrid_moe as drv
    cell = Manifest(root).cell(CELL)
    state = drv.setup(_context(cell, 6))
    tr = cell.traffic
    ids, got = drv.decoded_states(state["dec"], state["prompts"], 2, tr, 8)
    steps = min(drv.STATE_STEPS, tr["new_tokens"] - 1)
    assert ids.shape == (2, tr["prompt_len"] + steps) and steps > 0
    assert [g is None for g in got] == [False, False, True, False] * 2
    assert got[0][0].shape == (2, 8, 16, 16)
    assert got[0][1].shape == (2, 3, 160)
    ref = cell.config["reference"]
    sound = drv.state_errors(got, state["params"], ids, ref)
    assert max(sound.values()) < 1e-4
    assert min(drv.state_errors(got, state["params"], ids, ref,
                                window_shift=1).values()) > 0.1
    narrow = drv.state_errors(got, state["params"], ids, ref,
                              state_dtype=jnp.bfloat16)
    assert 1e-4 < narrow[0] < 0.1


def test_the_router_check_tells_a_softmax_over_all_experts_apart(root):
    """Any monotone rule chooses the same experts, so the choices agree
    with a reference that renormalises over all eight; the expert
    half's output does not, by more than ``WEIGHTS_TOL``."""
    from chipbench.drivers import batch_decode_hybrid_moe as drv
    cell = Manifest(root).cell(CELL)
    state = drv.setup(_context(cell, 7))
    seqs = state["prompts"][:2]
    ref = cell.config["reference"]
    shares, errors = drv.router_agreement(state["graph"], state["params"],
                                          seqs, ref)
    assert min(shares) > 0.99 and max(errors) < 1e-3
    _, other = drv.router_agreement(state["graph"], state["params"], seqs,
                                    ref, renormalise_over_all=True)
    assert min(other) > drv.WEIGHTS_TOL


@pytest.mark.parametrize("groups", [None, 1])
def test_the_long_memory_probe_tells_a_bfloat16_state_apart(groups):
    """The probe drives the format's own kernels under decays near 1,
    through a prefill of eight chunks: in float32 it agrees with the
    reference to rounding, with the state rounded to bfloat16 after
    every step it misses ``MEMORY_TOL``."""
    import jax.numpy as jnp
    from chipbench.drivers import batch_decode_hybrid_moe as drv
    from chipbench.reference import granite_hybrid as ref
    from defer_tpu.ops.ssm import SsdFormat

    fmt = SsdFormat(8, 16, 16, 4, 64, jnp.float32, groups=groups)
    sound = drv.long_memory_error(fmt, 2 ** 31 + 5, ref, steps=512)
    narrow = drv.long_memory_error(fmt, 2 ** 31 + 5, ref, steps=512,
                                   held=jnp.bfloat16)
    assert set(sound) == {"y_prefill", "y_decode", "H"}
    assert max(sound.values()) < drv.MEMORY_TOL / 10
    assert narrow["H"] > 2 * drv.MEMORY_TOL
    assert narrow["y_decode"] > 2 * drv.MEMORY_TOL


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.ssm.*`` / ``decode.moe.*`` (the
    parent) or off the chip a reader gives None and does not raise."""
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS)
    mf = Manifest()
    for name in NEW:
        assert mf.reader(name).read(run) is None


def test_the_models_size_against_the_issues_count(real_args):
    """102,286,976 parameters a Mamba-2 mixer, 9,437,184 an expert,
    121,464,448 a Mamba layer and 61,120,512 an attention layer outside
    their experts; 9.93 GB of weights held."""
    a = real_args
    assert rh.layer_kinds(a) == (9, 1) and rh.channels_of(a) == 8192
    assert rh.conv_width(a) == 8448 and rh.held_experts(a) == 36
    assert rh.mamba_mixer_params(a) == 102_286_976
    assert rh.attention_mixer_params(a) == 41_943_040
    assert rh.expert_params(a) == 9_437_184
    assert rh.layer_rest_params(a) == 18_874_368 + 294_912 + 8_192
    assert rh.mamba_mixer_params(a) + rh.layer_rest_params(a) == 121_464_448
    assert rh.attention_mixer_params(a) + rh.layer_rest_params(a) \
        == 61_120_512
    assert rh.dense_params(a) == 1_154_300_544
    assert rh.held_params(a) == (1_154_300_544 + 3_397_386_240
                                 + 205_520_896 + 4096)
    assert round(rh.held_weight_bytes(a, 2) / 1e9, 2) == 9.93


def test_decode_step_needs_against_the_issues_count(real_args):
    """A step at ~1800 positions: the held experts 6.79 GB, H read and
    written 4.83, the windows 0.06, the attention layer's live rows
    0.47, logits 0.01: 14.9 GB, 18.2 ms at the memory peak; 0.24 TFLOP,
    under the bytes.  Half of the held experts touched: 3.4 GB less."""
    a = real_args
    h, conv = rh.needed_state_bytes(a, 64, 2)
    assert h == 64 * 9 * 128 * 64 * 128 * 4 and conv == 64 * 9 * 3 * 8448 * 2
    assert round(2 * h / 1e9, 2) == 4.83 and round(2 * conv / 1e9, 2) == 0.06
    assert round((h + conv) / 1e9, 2) == 2.45
    live = rh.needed_cache_bytes(a, 64, 1800, 2)
    assert live == 64 * 1800 * 2 * 8 * 128 * 2
    assert round(live / 1e9, 2) == 0.47
    assert round(rh.needed_cache_bytes(a, 64, 3072, 2) / 1e9, 2) == 0.81
    flops, nbytes = rh.decode_step_needs(a, rows=64, live_positions=1800,
                                         weight_bytes=2, kv_bytes=2)
    routed = 2 * 10 * 36 * 9_437_184
    assert round(routed / 1e9, 2) == 6.79
    dense = 2 * (rh.dense_params(a) + 4096 + 50176 * 4096)
    assert nbytes == pytest.approx(dense + routed + 2 * (h + conv) + live
                                   + 64 * 50176 * 4)
    assert round(nbytes / 1e9, 1) == 14.9
    assert round(1e3 * nbytes / 819e9, 1) == 18.2
    assert round(routed / nbytes, 2) == 0.46
    assert round(flops / 1e12, 2) == 0.24 and flops / 197e12 < nbytes / 819e9
    _, half = rh.decode_step_needs(a, rows=64, live_positions=1800,
                                   weight_bytes=2, kv_bytes=2,
                                   experts_hit_share=0.5)
    assert nbytes - half == pytest.approx(routed / 2)


def test_kernel_and_prefill_needs_against_a_hand_count(real_args):
    """One ``ssd_step`` call moves a layer's ``H`` twice (268 MB each
    way) and 6 MB of inputs: 0.54 GB, 0.66 ms at the memory peak; one
    ``ssd_scan`` call over a piece of 4 prompts 34.6 GFLOP and 0.30 GB
    (bound by its bytes), all of a prefill's 5.0 TFLOP; the prefill 0.22
    PFLOP, 3.3 GFLOP a token, 1.1 s at the matrix peak."""
    a = real_args
    flops, nbytes = rh.ssd_step_needs(a, 64)
    h_layer = 64 * 128 * 8192 * 4
    assert nbytes == pytest.approx(2 * h_layer + 4 * (
        3 * 64 * 8192 + 2 * 64 * 128))
    assert round(nbytes / 1e9, 2) == 0.54
    assert round(1e3 * nbytes / 819e9, 2) == 0.66
    assert flops == 6 * 64 * 8192 * 128
    flops, scan = rh.ssd_scan_needs(a, 4, 1024)
    assert round(flops / 1e9, 1) == 34.6 and round(scan / 1e9, 2) == 0.30
    assert flops / 197e12 < scan / 819e9
    assert round(9 * 16 * flops / 1e12, 1) == 5.0
    flops, _ = rh.prefill_needs(a, rows=64, prompt_len=1024, weight_bytes=2,
                                kv_bytes=2)
    assert round(flops / 1e15, 2) == 0.22
    assert round(flops / 65536 / 1e9, 1) == 3.3
    assert round(flops / 197e12, 1) == 1.1
    more, _ = rh.prefill_needs(a, rows=64, prompt_len=1024, weight_bytes=2,
                               kv_bytes=2, held_share=1.0)
    assert more > flops


def test_a_fatter_layout_is_refused_and_cannot_raise_a_share(real_args):
    """The need is the configuration's: what the program holds is only
    held against it.  The program's own layout (whole tiles: 1.00 of
    the need) passes, 1.11 of it is refused — of the state, of the
    windows, of the attention layer's rows — and a program with no
    gauges is not judged."""
    a = real_args
    h, conv = rh.needed_state_bytes(a, 64, 2)
    rows = 2 * rh.needed_cache_bytes(a, 64, 3088, 2)
    base = {"rows": 64, "weight_bytes": 2, "kv_bytes": 2, "max_len": 3072}
    sound = dict(base, ssm_state_bytes=h + conv, ssm_conv_bytes=conv,
                 cache_full_bytes=rows)
    rh.check_held(sound, a)
    rh.check_held(base, a)
    rh.check_held(dict(sound, ssm_state_bytes=1.09 * h + conv), a)
    for key, fat, words in (
            ("ssm_state_bytes", 1.11 * h + conv, "state-space state"),
            ("cache_full_bytes", 1.11 * rows, "attention rows")):
        with pytest.raises(ValueError, match=f"{words}.*1.11"):
            rh.check_held(dict(sound, **{key: fat}), a)
    with pytest.raises(ValueError, match="convolution windows.*1.11"):
        rh.check_held(dict(sound, ssm_conv_bytes=1.11 * conv,
                           ssm_state_bytes=h + 1.11 * conv), a)
    # heads of 64 channels on the lanes would be padded to 128: 2x
    with pytest.raises(ValueError, match="2.000 times"):
        rh.check_held(dict(sound, ssm_state_bytes=2 * h + conv), a)


def test_weights_made_a_kind_of_node_at_a_time_are_the_initialisers_own():
    """The driver draws each node under the key ``graph.init`` would
    hand it, one program a kind of node: the leaves are ``graph.init``'s
    own, scaled where the configuration says, cast, and the head is the
    embedding's table."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.drivers import batch_decode_hybrid_moe as drv
    from defer_tpu import models

    graph = models.granite_hybrid(**ARGS)
    seed = 2 ** 31 + 77
    got = drv.make_weights(graph, seed, jnp.bfloat16,
                           {"q/w": 2.0, "embeddings/wte": 0.5})
    want = graph.init(jax.random.key(seed % (2 ** 31 - 1)))
    assert got["lm_head"]["w"] is got["embeddings"]["wte"]
    want = dict(want, lm_head={"w": want["embeddings"]["wte"]})
    assert jax.tree.structure(got) == jax.tree.structure(want)
    scaled = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got), strict=True):
        name = "/".join(k.key for k in path)
        gain = 2.0 if name.endswith("q/w") else \
            0.5 if name.endswith("/wte") or name == "lm_head/w" else 1.0
        scaled += gain != 1.0
        assert b.dtype == jnp.bfloat16 and isinstance(b, np.ndarray)
        # (a draw fused with its cast may round a value the other way:
        # one bfloat16 step at most)
        np.testing.assert_allclose(
            b.astype(np.float32), np.asarray(a * gain), rtol=2 ** -7,
            atol=1e-30, err_msg=name)
    assert scaled == 4          # two layers' queries, the table twice
