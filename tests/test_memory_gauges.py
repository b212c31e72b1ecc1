"""A memory format says its own gauges (``ops/layered.py::
LayeredState.gauges`` / ``.rows_read``); ``PipelinedDecoder`` adds up
what its layers' formats say and spells no kind.  Over every tiny
decoder family: the gauges a decoder sets are those sums, and no name
of another kind of memory is touched.
"""

import pytest

import jax

from defer_tpu import models
from defer_tpu.obs import REGISTRY
from defer_tpu.ops.kv_cache import KVCacheFormat
from defer_tpu.ops.layered import LayeredState, totals
from defer_tpu.runtime.decode import PipelinedDecoder

FAMILIES = ("gpt_tiny", "olmoe_tiny", "brumby_tiny", "cohere_moe_tiny",
            "jamba_tiny", "granite_hybrid_tiny", "kimi_k2_tiny",
            "mellum_tiny", "longcat_flash_tiny", "nemotron_h_tiny")
#: every name some format of some family answers with
NAMES = (
    "decode.cache.window_bytes", "decode.cache.full_bytes",
    "decode.cache.window_positions", "decode.cache.block_sequences",
    "decode.cache.block_positions", "decode.cache.latent_bytes",
    "decode.cache.latent_positions", "decode.cache.latent_sublayers",
    "decode.ssm.conv_bytes", "decode.ssm.bc_groups",
    "decode.cache.full_rows_read",
    "decode.cache.window_rows_read")
#: a layer's measures, no amounts: over layers the largest stands
LARGEST = {"decode.cache.window_positions", "decode.cache.block_sequences",
           "decode.cache.block_positions", "decode.ssm.bc_groups"}
KINDS = ("kv_cache", "retention", "ssm", "latent_cache")
MB, UNSET = 2, -1


@pytest.mark.parametrize("family", FAMILIES)
def test_a_decoders_gauges_are_what_its_formats_say(family):
    graph = getattr(models, family)()
    for name in NAMES + tuple(f"decode.{k}.state_bytes" for k in KINDS):
        REGISTRY.gauge(name).set(UNSET)
    n = 2
    dec = PipelinedDecoder(graph, graph.init(jax.random.key(0)),
                           num_stages=n, microbatch=MB,
                           max_len=graph.nodes["embeddings"].op.max_len)
    said = [fmt.gauges(MB, n) for fmt in dec.state_formats]
    assert set().union(*said) <= set(NAMES)
    want = {}
    for fmt, layer in zip(dec.state_formats, said):
        for name, value in layer.items():
            want[name] = max(want.get(name, 0), value) \
                if name in LARGEST else want.get(name, 0) + value
    assert want == totals(dec.state_formats, lambda fmt: fmt.gauges(MB, n))
    # a step's reads are posted only where the formats tell kinds apart
    reads = totals(dec._row_readers, lambda fmt: fmt.rows_read(MB * n, 7))
    windows = [fmt.window for fmt in dec.state_formats
               if isinstance(fmt, KVCacheFormat)]
    assert bool(reads) == any(w is not None for w in windows)
    dec._post_rows_read(MB * n, 7)
    want.update(reads)
    # (a layer that keeps no memory has no kind and no gauge)
    for kind in set(dec.memory) - {None}:
        want[f"decode.{kind}.state_bytes"] = sum(
            n * fmt.state_bytes(MB, 1)
            for k, fmt in zip(dec.memory, dec.state_formats) if k == kind)
    for name in NAMES + tuple(f"decode.{k}.state_bytes" for k in KINDS):
        assert REGISTRY.gauge(name).value == want.get(name, UNSET), name
    # the parts of a kind add up to the kind
    if "kv_cache" in dec.memory:
        assert want["decode.cache.window_bytes"] \
            + want["decode.cache.full_bytes"] \
            == want["decode.kv_cache.state_bytes"]
    if "latent_cache" in dec.memory:
        assert want["decode.cache.latent_bytes"] \
            == want["decode.latent_cache.state_bytes"]


def test_a_format_without_parts_says_nothing():
    assert LayeredState().gauges(4, 2) == {}
    assert LayeredState().rows_read(4, 9) == {}
    full = KVCacheFormat(2, 16, 32, "float32", groups=1)
    ring = KVCacheFormat(2, 16, 32, "float32", groups=1, window=8)
    assert full.rows_read(4, 9) == {"decode.cache.full_rows_read": 36}
    assert ring.rows_read(4, 9) == {"decode.cache.window_rows_read": 32}
    assert ring.rows_read(4, 5) == {"decode.cache.window_rows_read": 20}
    both = totals((full, ring, ring), lambda fmt: fmt.gauges(4, 2))
    assert both["decode.cache.window_positions"] == 8
    assert both["decode.cache.window_bytes"] \
        == 2 * 2 * ring.state_bytes(4, 1)
    assert both["decode.cache.full_bytes"] == 2 * full.state_bytes(4, 1)
