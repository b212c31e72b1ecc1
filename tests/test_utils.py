"""Checkpoint save/restore + profiling breakdown + metrics/config units."""

import os

import jax
import numpy as np
import pytest

from defer_tpu import (DeferConfig, SpmdPipeline, StopwatchWindow,
                       load_params, partition, pipeline_mesh,
                       profile_pipeline, save_params)
from defer_tpu.models import resnet_tiny


def test_checkpoint_roundtrip(tmp_path):
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    path = os.path.join(tmp_path, "ckpt.npz")
    save_params(path, params)
    like = jax.eval_shape(lambda: g.init(jax.random.key(1)))
    restored = load_params(path, like)
    flat_a = jax.tree_util.tree_leaves(params)
    flat_b = jax.tree_util.tree_leaves(restored)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_restore_then_deploy(tmp_path):
    """The deployment story: restore a checkpoint, place onto a pipeline."""
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    path = os.path.join(tmp_path, "ckpt.npz")
    save_params(path, params)
    restored = load_params(path, params)
    pipe = SpmdPipeline(partition(g, num_stages=2), restored,
                        mesh=pipeline_mesh(2), chunk=2)
    x = np.zeros((2, 1, 32, 32, 3), np.float32)
    ref = np.asarray(jax.jit(g.apply)(params, x[0]))
    np.testing.assert_allclose(pipe.run(x)[0], ref, rtol=2e-4, atol=2e-4)


def test_checkpoint_mismatch_fails_loudly(tmp_path):
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    path = os.path.join(tmp_path, "ckpt.npz")
    save_params(path, params)
    other = dict(params)
    other.pop(next(iter(other)))
    with pytest.raises(ValueError, match="mismatch"):
        load_params(path, other)


def test_profile_pipeline_breakdown():
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    pipe = SpmdPipeline(partition(g, num_stages=4), params,
                        mesh=pipeline_mesh(4), chunk=2)
    prof = profile_pipeline(pipe, params, iters=2, warmup=1)
    assert prof["num_stages"] == 4
    assert len(prof["stage_latency_ms"]) == 4
    assert prof["stage_imbalance"] >= 1.0
    assert prof["pipeline_step_ms"] > 0
    assert prof["steady_state_throughput_per_s"] > 0


def test_stopwatch_window():
    w = StopwatchWindow(window_s=60)
    assert w.tick(5)
    assert w.count == 5
    assert w.rate > 0


def test_config_defaults():
    cfg = DeferConfig()
    assert cfg.mode == "spmd"
    assert cfg.microbatch == 1


def test_checkpoint_path_without_suffix(tmp_path):
    """save_params('x') writes x.npz; load_params('x') must find it."""
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    base = os.path.join(tmp_path, "ckpt")  # no .npz suffix
    save_params(base, params)
    restored = load_params(base, params)
    a = jax.tree_util.tree_leaves(params)[0]
    b = jax.tree_util.tree_leaves(restored)[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_metrics_clear_counters():
    """clear_counters zeroes the streaming counters but keeps geometry and
    stage latencies (harness warmup must not pollute a measured window)."""
    from defer_tpu.utils.metrics import PipelineMetrics

    m = PipelineMetrics(num_stages=4, microbatch=2, buffer_elems=64,
                        buffer_bytes_per_hop=256)
    m.inferences, m.steps, m.wall_s, m.chunk_calls = 10, 20, 1.5, 3
    m.stage_latency_s = [0.1, 0.2]
    m.clear_counters()
    assert (m.inferences, m.steps, m.wall_s, m.chunk_calls) == (0, 0, 0.0, 0)
    assert m.stage_latency_s == [0.1, 0.2]
    assert m.num_stages == 4 and m.buffer_elems == 64


def test_hop_utilization_property():
    """hop_utilization = per-stage out size / buf_elems, in stage order."""
    from defer_tpu import SpmdPipeline, partition, pipeline_mesh

    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    stages = partition(g, num_stages=4)
    pipe = SpmdPipeline(stages, params, mesh=pipeline_mesh(4),
                        microbatch=1, chunk=2)
    util = pipe.hop_utilization
    assert len(util) == 4
    assert util == [s.out_spec.size / pipe.buf_elems for s in stages]
    assert all(0 < u <= 1 for u in util)


def test_flush_artifact_atomic_merge(tmp_path):
    """Timeout-safe artifact writer: atomic write, row merge across a
    re-run with a row filter, value recomputed over MERGED rows (the
    DECODE_r05 clobber scenario)."""
    import json
    from defer_tpu.utils.artifact import flush_artifact

    p = str(tmp_path / "a.json")
    # run 1: 2 rows, then times out
    flush_artifact(p, {"metric": "m", "value": 5.0,
                       "rows": {"a": {"tokens_per_s": 5.0},
                                "b": {"tokens_per_s": 3.0}}},
                   merge_key="rows")
    # run 2 (filtered re-run, merge_prior) measures only row c
    got = flush_artifact(p, {"metric": "m", "value": 2.0,
                             "rows": {"c": {"tokens_per_s": 2.0}}},
                         merge_key="rows", merge_prior=True)
    on_disk = json.loads(open(p).read())
    assert set(on_disk["rows"]) == {"a", "b", "c"}
    assert on_disk["value"] == 5.0  # max over merged, not just run 2
    assert got == on_disk
    # a FULL re-run (no merge_prior) replaces stale rows instead of
    # letting an obsolete fast row own the headline
    full = flush_artifact(p, {"metric": "m", "value": 0.0,
                              "rows": {"a": {"tokens_per_s": 4.0}}},
                          merge_key="rows")
    assert set(full["rows"]) == {"a"} and full["value"] == 4.0
    # row_filter restricts the headline (bench_spec: exclude baseline)
    f = flush_artifact(None, {"value": 0.0,
                              "rows": {"base": {"tokens_per_s": 9.0},
                                       "spec_x": {"tokens_per_s": 2.0}}},
                       merge_key="rows",
                       row_filter=lambda k: k.startswith("spec_"))
    assert f["value"] == 2.0
    # a failed row that carries the key with None under it is skipped,
    # not fed to max() (xla_flag_sweep rows of a bench that died)
    n = flush_artifact(None, {"value": 0.0,
                              "rows": {"dead": {"tokens_per_s": None},
                                       "ok": {"tokens_per_s": 3.0}}},
                       merge_key="rows")
    assert n["value"] == 3.0
    # empty prior file must not crash the flush (the touch/stray-redirect
    # scenario)
    e = str(tmp_path / "empty.json")
    open(e, "w").close()
    flush_artifact(e, {"value": 0.0, "rows": {"a": {"tokens_per_s": 1.0}}},
                   merge_key="rows", merge_prior=True)
    assert json.loads(open(e).read())["value"] == 1.0
    # no .part file left behind
    assert not [f for f in tmp_path.iterdir() if f.suffix == ".part"]
    # no path -> no write, payload returned unchanged
    r = flush_artifact(None, {"x": 1})
    assert r == {"x": 1}


def test_ring_jit_kwargs_contract(monkeypatch):
    """Ring programs get the TPU defaults only on TPU meshes; env
    options merge over (and can disable) the defaults; no other
    platform's compile — CPU or anything else that merely is not the
    CPU — receives TPU-only flags implicitly."""
    import numpy as np
    import jax
    from defer_tpu.utils.xla_opts import (RING_DEFAULTS, compiler_options,
                                          jit_kwargs, ring_jit_kwargs)

    cpu_devices = np.array(jax.devices())  # conftest pins the cpu backend
    monkeypatch.delenv("DEFER_XLA_COMPILER_OPTS", raising=False)
    assert ring_jit_kwargs(cpu_devices) == {}
    assert jit_kwargs() == {}

    monkeypatch.setenv("DEFER_XLA_COMPILER_OPTS", "a=1, b=two")
    assert compiler_options() == {"a": "1", "b": "two"}
    assert ring_jit_kwargs(cpu_devices) == {
        "compiler_options": {"a": "1", "b": "two"}}

    class FakeTpu:
        platform = "tpu"

    class FakeGpu:
        platform = "gpu"

    assert ring_jit_kwargs([FakeGpu()]) == {
        "compiler_options": {"a": "1", "b": "two"}}
    tpu_devices = [FakeTpu()]
    opts = ring_jit_kwargs(tpu_devices)["compiler_options"]
    assert opts["a"] == "1"
    for k, v in RING_DEFAULTS.items():
        assert opts[k] == v
    # env overrides a default key-by-key
    key = next(iter(RING_DEFAULTS))
    monkeypatch.setenv("DEFER_XLA_COMPILER_OPTS", f"{key}=false")
    assert ring_jit_kwargs(tpu_devices)["compiler_options"][key] == "false"
