"""The hybrid state-space cell's driver, readers and counts at a tiny
preset on the CPU, through the harness; and ``roofline_hybrid_ssm``
against the counts of the issue that asked for the cell."""

import json
import os
import time
import types

import pytest

import tiny
from chipbench import roofline_hybrid_ssm as rh
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

ARGS = {"num_layers": 8, "hidden": 64, "heads": 4, "kv_heads": 1,
        "head_dim": 16, "mlp_hidden": 96, "seq_len": 64, "vocab": 211,
        "attn_layer_period": 4, "attn_layer_offset": 2, "mamba_expand": 2,
        "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_dt_rank": 4,
        "rms_eps": 1e-06}
CONFIG = {"model_args": ARGS,
          "reference": {"module": "chipbench.reference.jamba",
                        "args": {"n_layer": 8, "n_head": 4, "n_kv": 1,
                                 "head_dim": 16, "attn_period": 4,
                                 "attn_offset": 2, "d_state": 8,
                                 "dt_rank": 4, "eps": 1e-06}}}
TRAFFIC = {"driver": "batch_decode_hybrid_ssm", "batch": 4, "prompt_len": 8,
           "new_tokens": 16, "token_chunk": 2, "max_len": 32,
           "compute_dtype": "float32", "kv_cache": "buffer",
           "check_sequences": 2, "check_tokens": 6, "trace_seconds": 0.5}
CELL = "hybrid_ssm_tiny"
REAL = "jamba2_batch_decode"
SHARED = ("tokens_per_s", "decode_step_ms", "decode_chunk_ms",
          "decoder_launch_ms", "decode_device_idle_share",
          "decode_prefill_ms", "decode_host_serial_ms")
NEW = ("hybrid_ssm_decode_step_roofline", "ssm_step_kernel_roofline",
       "hybrid_ssm_prefill_roofline", "ssm_scan_kernel_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def real_args():
    return Manifest().cell(REAL).config["model_args"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_ssm_root")))
    bench = os.path.join(root, "chipbench")
    for sub, name, body in (("configs", "jamba-tiny", CONFIG),
                            ("traffic", "batch_ssm_tiny", TRAFFIC)):
        with open(os.path.join(bench, sub, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "jamba-tiny", "source": "none: a test preset",
        "file": "chipbench/configs/jamba-tiny.json", "reduced": [],
        "why": "tiny preset for the CPU tests"})
    doc["workloads"].append({
        "name": CELL, "config": "jamba-tiny", "traffic": "batch_ssm_tiny",
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
    assert "retention_decode_step_roofline" not in cell.per_layer
    assert cell.end_to_end == ("tokens_per_s", "setup_s")
    assert cell.chips == 1
    assert cell.traffic["driver"] == "batch_decode_hybrid_ssm"
    assert {k: cell.traffic[k] for k in (
        "batch", "prompt_len", "new_tokens", "token_chunk", "max_len",
        "compute_dtype", "kv_cache", "check_sequences",
        "check_tokens")} == {
        "batch": 256, "prompt_len": 256, "new_tokens": 4096,
        "token_chunk": 32, "max_len": 4352, "compute_dtype": "bfloat16",
        "kv_cache": "buffer", "check_sequences": 2, "check_tokens": 512}
    # every published number under its own key, nothing reduced
    published = cell.config["published"]
    assert all(cell.config[k] == v for k, v in published.items())
    assert not cell.config["reduced"]
    entry = next(c for c in m.doc["configs"] if c["name"] == "jamba2-3b")
    assert entry["reduced"] == [] and entry["source"] == cell.config["source"]
    a = cell.config["model_args"]
    assert (a["num_layers"], a["hidden"], a["heads"], a["kv_heads"],
            a["head_dim"], a["mlp_hidden"], a["vocab"],
            a["attn_layer_period"], a["attn_layer_offset"],
            a["mamba_expand"], a["mamba_d_state"], a["mamba_d_conv"],
            a["mamba_dt_rank"], a["rms_eps"]) == (
        28, 2560, 20, 1, 128, 8192, 65536, 14, 7, 2, 16, 4, 160, 1e-6)
    assert {"head_dim", "no_positions", "small_norms", "state", "init",
            "init_gain", "weights", "deployment"} <= set(
                cell.config["assumed"])
    for metric in NEW:
        reader, entry = m.reader(metric), m.metric(metric)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["source"], entry["moves"])
        assert entry["workloads"] == [REAL]
    # (the number of cells is other tests' to pin, and a later PR's to
    # move: here only that this one is among them, on one chip)
    assert len(m.doc["workloads"]) >= 7


def test_an_untraced_run_checks_tokens_state_and_memory(root):
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
            ("%ssm_step.3 = (f32[]) custom-call()", lo, lo + 1e-4))
        devices[0].ops.append(
            ("%ssm_scan.7 = (f32[]) custom-call()", lo, lo + 1e-4))

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


def test_the_window_counts_every_valid_update_and_the_ring_s_bytes(root):
    """``decode.ssm.updates`` over a window is sequences x Mamba layers x
    the decode steps that were no bubble; the gauges are the ring's own
    and pass the reader's check of what is held."""
    from chipbench.drivers import batch_decode_hybrid_ssm as drv
    cell = Manifest(root).cell(CELL)
    ctx = _context(cell, 5, trace=True)
    state = drv.setup(ctx)
    out = drv.measure(state, 0.3, ctx)
    c = out["counters"]
    steps = c["decode.ssm.updates"] / (4 * 6)
    assert steps == int(steps) and steps > 0 and c["mamba_layers"] == 6
    conv = 6 * 4 * 3 * 128 * 4
    assert c["ssm_conv_bytes"] == conv
    assert c["ssm_state_bytes"] == 6 * 4 * 8 * 128 * 4 + conv
    assert c["cache_full_bytes"] == 2 * 2 * 4 * 33 * 16 * 4 * 2
    # no leaf rides a flat row since PR 44: the driver reads no such gauge
    assert "weights_row_bytes" not in c and c["weights_own_bytes"] > 0
    assert c["prefill_piece_rows"] == 4 and c["max_len"] == 32
    # float32 windows here: 4 bytes a value
    rh.check_held(dict(c, weight_bytes=4, kv_bytes=4), ARGS)
    ok, detail = drv.check(state, ctx)
    assert ok and detail["state_rel_err"] < 1e-4 < drv.STATE_TOL_FIRST
    assert sorted(detail["state_rel_err_by_layer"]) == [0, 1, 3, 4, 5, 7]
    assert detail["long_memory_rel_err"] < 1e-4 < drv.MEMORY_TOL
    assert detail["tokens_compared"] == 2 * 6


def test_the_state_check_reads_what_the_decode_steps_wrote(root):
    """The state is read back behind the prefill *and* decode steps, and
    held to the reference over the prompt and the tokens fed back: with
    the last token left out — a window one position off — the
    comparison fails; so it does against a reference whose own state is
    kept in bfloat16 only by what that mantissa costs."""
    import jax.numpy as jnp
    from chipbench.drivers import batch_decode_hybrid_ssm as drv
    cell = Manifest(root).cell(CELL)
    state = drv.setup(_context(cell, 6))
    tr = cell.traffic
    ids, got = drv.decoded_states(state["dec"], state["prompts"], 2, tr)
    steps = min(drv.STATE_STEPS, tr["new_tokens"] - 1)
    assert ids.shape == (2, tr["prompt_len"] + steps) and steps > 0
    assert [g is None for g in got] == [False, False, True, False] * 2
    assert got[0][0].shape == (2, 128, 8) and got[0][1].shape == (2, 3, 128)
    ref = cell.config["reference"]
    sound = drv.state_errors(got, state["params"], ids, ref)
    assert max(sound.values()) < 1e-4
    assert min(drv.state_errors(got, state["params"], ids[:, :-1],
                                ref).values()) > 0.1
    narrow = drv.state_errors(got, state["params"], ids, ref,
                              state_dtype=jnp.bfloat16)
    assert 1e-3 < narrow[0] < 0.1


@pytest.mark.parametrize("groups", [None, 1])
def test_the_long_memory_probe_tells_a_bfloat16_state_apart(groups):
    """The probe drives the format's own kernels under decays near 1: in
    float32 it agrees with the reference to rounding, with the state
    rounded to bfloat16 after every step it misses ``MEMORY_TOL``."""
    import jax.numpy as jnp
    from chipbench.drivers import batch_decode_hybrid_ssm as drv
    from chipbench.reference import jamba as ref
    from defer_tpu.ops.ssm import SsmFormat

    fmt = SsmFormat(128, 8, 4, jnp.float32, groups=groups)
    sound = drv.long_memory_error(fmt, 2 ** 31 + 5, ref, steps=512)
    narrow = drv.long_memory_error(fmt, 2 ** 31 + 5, ref, steps=512,
                                   held=jnp.bfloat16)
    assert set(sound) == {"y_prefill", "y_decode", "H"}
    assert max(sound.values()) < drv.MEMORY_TOL / 10
    assert narrow["H"] > 2 * drv.MEMORY_TOL
    assert narrow["y_decode"] > 2 * drv.MEMORY_TOL


def test_the_readers_return_nothing_without_their_counters():
    """On a program that has no ``decode.ssm.*`` (the parent) or off the
    chip a reader gives None and does not raise."""
    run = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS)
    mf = Manifest()
    for name in NEW:
        assert mf.reader(name).read(run) is None


def test_the_models_size_against_the_issues_count(real_args):
    """41,241,792 parameters a Mamba mixer, 104,161,472 a Mamba layer,
    76,682,240 an attention layer, 3.03 B in all: 6.06 GB in bf16."""
    a = real_args
    assert rh.layer_kinds(a) == (26, 2) and rh.channels_of(a) == 5120
    assert rh.mamba_mixer_params(a) == 41_241_792
    assert rh.mlp_params(a) == 62_914_560
    assert rh.mamba_mixer_params(a) + rh.mlp_params(a) + 5120 == 104_161_472
    assert rh.attention_mixer_params(a) + rh.mlp_params(a) + 5120 \
        == 76_682_240
    layers = 26 * 104_161_472 + 2 * 76_682_240
    assert layers == 2_861_562_752
    assert rh.model_params(a) == layers + 2560 + 65536 * 2560
    assert round(rh.model_params(a) / 1e9, 2) == 3.03
    assert round(2 * rh.model_params(a) / 1e9, 2) == 6.06


def test_decode_step_needs_against_the_issues_count(real_args):
    """A step at ~1300 positions: H read and written 4.36 GB, the
    windows 0.41, the attention layers' live rows 0.34, logits 0.07:
    11.2 GB, 13.7 ms at the memory peak; 1.55 TFLOP, under the bytes."""
    a = real_args
    h, conv = rh.needed_state_bytes(a, 256, 2)
    assert h == 256 * 26 * 5120 * 16 * 4 and conv == 256 * 26 * 5120 * 3 * 2
    assert round(2 * h / 1e9, 2) == 4.36 and round(2 * conv / 1e9, 2) == 0.41
    assert round((h + conv) / 1e9, 2) == 2.39
    live = rh.needed_cache_bytes(a, 256, 1300, 2)
    assert live == 256 * 1300 * 2 * 2 * 128 * 2
    assert round(live / 1e9, 2) == 0.34
    assert round(rh.needed_cache_bytes(a, 256, 4352, 2) / 1e9, 2) == 1.14
    flops, nbytes = rh.decode_step_needs(a, rows=256, live_positions=1300,
                                         weight_bytes=2, kv_bytes=2)
    weights = 2 * rh.step_matrix_params(a)
    assert nbytes == pytest.approx(weights + 2 * (h + conv) + live
                                   + 256 * 65536 * 4)
    assert round(nbytes / 1e9, 1) == 11.2
    assert round(1e3 * nbytes / 819e9, 1) == 13.7
    assert round((2 * (h + conv) + 2 * 26 * rh.mamba_mixer_params(a))
                 / nbytes, 2) == 0.62
    assert round(flops / 1e12, 2) == 1.56 and flops / 197e12 < nbytes / 819e9


def test_kernel_and_prefill_needs_against_a_hand_count(real_args):
    """One ``ssm_step`` call moves a layer's ``H`` twice (83.9 MB each
    way) and 8 MB of inputs: 0.22 ms at the memory peak; one ``ssm_scan``
    call over a piece of 32 prompts 0.52 GB; the prefill 0.376 PFLOP,
    1.9 s at the matrix peak."""
    a = real_args
    flops, nbytes = rh.ssm_step_needs(a, 256)
    h_layer = 256 * 16 * 5120 * 4
    assert nbytes == pytest.approx(2 * h_layer + 4 * (
        3 * 256 * 5120 + 2 * 256 * 16 + 5120 * 16))
    assert round(1e3 * nbytes / 819e9, 2) == 0.22
    assert flops == 6 * 256 * 5120 * 16
    _, scan = rh.ssm_scan_needs(a, 32, 256)
    assert round(scan / 1e9, 2) == 0.52
    flops, _ = rh.prefill_needs(a, rows=256, prompt_len=256, weight_bytes=2,
                                kv_bytes=2)
    assert round(flops / 1e15, 3) == 0.376
    assert round(flops / 197e12, 1) == 1.9


def test_a_fatter_layout_is_refused_and_cannot_raise_a_share(real_args):
    """The need is the configuration's: what the program holds is only
    held against it.  The program's own layout (whole tiles: 1.00 of
    the need) passes, 1.11 of it is refused — of the state, of the
    windows, of the attention layers' rows — and a program with no
    gauges is not judged."""
    a = real_args
    h, conv = rh.needed_state_bytes(a, 256, 2)
    rows = 2 * rh.needed_cache_bytes(a, 256, 4368, 2)
    base = {"rows": 256, "weight_bytes": 2, "kv_bytes": 2, "max_len": 4352}
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
    # 16 states on the lanes would be padded to 128: 8x the need
    with pytest.raises(ValueError, match="8.000 times"):
        rh.check_held(dict(sound, ssm_state_bytes=8 * h + conv), a)


def test_weights_made_a_kind_of_node_at_a_time_are_the_initialisers_own():
    """The driver draws each node under the key ``graph.init`` would
    hand it, one program a kind of node: the leaves are ``graph.init``'s
    own, cast, and the head is the embedding's table."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.drivers import batch_decode_hybrid_ssm as drv
    from defer_tpu import models

    graph = models.jamba(**ARGS)
    seed = 2 ** 31 + 77
    got = drv.make_weights(graph, seed, jnp.bfloat16)
    want = graph.init(jax.random.key(seed % (2 ** 31 - 1)))
    assert got["lm_head"]["w"] is got["embeddings"]["wte"]
    want = dict(want, lm_head={"w": want["embeddings"]["wte"]})
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got), strict=True):
        name = "/".join(k.key for k in path)
        assert b.dtype == jnp.bfloat16 and isinstance(b, np.ndarray)
        np.testing.assert_array_equal(
            b, np.asarray(a.astype(jnp.bfloat16)), err_msg=name)
