"""Stage artifact export/load: StableHLO + weights roundtrip.

Invariant: a stage reloaded from its serialized artifact (in a codebase
that needs no model definition) computes exactly what the live stage
computes — the reference's ship-JSON-then-set_weights contract
(src/dispatcher.py:44-65 / src/node.py:31-34) without Keras or sockets.
"""

import numpy as np
import pytest

import jax

from defer_tpu import partition
from defer_tpu.models import bert_tiny, resnet_tiny
from defer_tpu.utils.export import (export_pipeline, export_stage,
                                    export_stage_bytes, load_stage,
                                    load_stage_program,
                                    stage_weight_leaves, weights_blob)


def test_stage_roundtrip_exact(tmp_path):
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    stages = partition(g, num_stages=4)
    s = stages[1]
    path = str(tmp_path / "s1.zip")
    export_stage(s, params, path, batch=2)

    fn, manifest = load_stage(path)
    assert manifest["index"] == 1
    assert tuple(manifest["in_shape"]) == s.in_spec.shape
    x = np.random.default_rng(0).normal(
        size=(2,) + s.in_spec.shape).astype(np.float32)
    want = s.fn(s.select_params(params), x)
    got = fn(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_pipeline_export_chains_to_full_model(tmp_path):
    """Relaying an input through all reloaded stage artifacts reproduces
    the full model — the partition-equivalence invariant over the wire
    format itself."""
    g = bert_tiny()
    params = g.init(jax.random.key(1))
    stages = partition(g, num_stages=2)
    paths = export_pipeline(stages, params, str(tmp_path), batch=1)
    assert len(paths) == 2

    ids = (np.arange(16).reshape(1, 16) % 100).astype(np.int32)
    ref = np.asarray(g.apply(params, ids))
    x = ids
    for p in paths:
        fn, _ = load_stage(p)
        x = np.asarray(fn(x))
    np.testing.assert_allclose(x, ref, rtol=1e-5, atol=1e-5)


def test_load_rejects_non_artifact(tmp_path):
    import zipfile
    bad = str(tmp_path / "bad.zip")
    with zipfile.ZipFile(bad, "w") as z:
        z.writestr("manifest.json", "{}")
    with pytest.raises(ValueError, match="not a defer_tpu stage"):
        load_stage(bad)


def test_stage_weights_are_arguments_not_constants():
    """A loaded stage program takes its weights as jit ARGUMENTS: the
    lowered module stays kilobytes however big the weights are (closed
    over, they become dense HLO constants — 145 MB of text for the last
    ResNet50 stage), they sit on the program's device, and ``reweight``
    swaps them without a retrace or recompile."""
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    s = partition(g, num_stages=2)[1]
    prog = load_stage_program(export_stage_bytes(s, params, batch=2))
    x = np.random.default_rng(0).normal(
        size=(2,) + s.in_spec.shape).astype(np.float32)

    weight_bytes = sum(int(l.nbytes) for l in prog._leaves)
    text = prog._call.lower(prog._leaves, x).as_text()
    assert weight_bytes > 40_000
    assert len(text) < weight_bytes // 2, (len(text), weight_bytes)
    assert "dense<\"0x" not in text  # no embedded weight blobs

    y0 = np.asarray(prog(x))
    assert prog._call._cache_size() == 1
    doubled = jax.tree.map(lambda a: a * 2.0, params)
    prog.reweight(weights_blob(stage_weight_leaves(s, doubled)))
    y1 = np.asarray(prog(x))
    assert prog._call._cache_size() == 1, "reweight retraced the program"
    np.testing.assert_allclose(
        y1, np.asarray(s.fn(s.select_params(doubled), x)),
        rtol=1e-5, atol=1e-5)
    assert not np.allclose(y0, y1)


def test_stage_program_weights_live_on_its_device(host_devices):
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    s = partition(g, num_stages=2)[0]
    prog = load_stage_program(export_stage_bytes(s, params, batch=1))
    dev = host_devices[1]
    prog.place(dev)
    assert prog.weight_device_ids == [dev.id]
    x = np.zeros((1,) + s.in_spec.shape, np.float32)
    assert {d.id for d in prog(x).devices()} == {dev.id}
    # a re-push lands on the same device, not the default one
    prog.reweight(weights_blob(stage_weight_leaves(s, params)))
    assert prog.weight_device_ids == [dev.id]
