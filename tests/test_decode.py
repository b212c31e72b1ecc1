"""Pipelined KV-cache decoding: equivalence against reference decoders.

Ground truth #1 is an incremental single-device greedy loop built from the
same ``CausalTransformerBlock.decode`` ops — the pipelined engine must match
it token-for-token exactly (same math, same op order, just scheduled across
the stage ring).  Ground truth #2 is full-sequence recompute through
``graph.apply`` (a different reduction order, so ids must agree but logits
only approximately).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding, PartitionSpec as P

from defer_tpu.graph.ir import GraphBuilder
from defer_tpu.graph.ops import Dense, LayerNorm
from defer_tpu.models import gpt_stage_cuts, gpt_tiny
from defer_tpu.models.decoder import (balanced_cut, decoder_parts,
                                      split_blocks)
from defer_tpu.models.gpt import CausalTransformerBlock, GptEmbedding
from defer_tpu.ops.kv_cache import KVCacheFormat
from defer_tpu.runtime.decode import (PipelinedDecoder, off_default_layout,
                                      relaid)

VOCAB = 97
MAX_LEN = 24


def incremental_greedy(graph, params, prompt, t_tok, max_len):
    """Single-device KV-cache greedy decode via the same block ops."""
    nodes = graph.nodes
    blocks = [nm for nm in graph.topo_order if nm.startswith("block_")]
    b, plen = prompt.shape
    op0 = nodes[blocks[0]].op
    d = nodes[blocks[0]].out_spec.shape[-1]
    # one layer's buffers a block (kv < num_heads under GQA)
    fmt = KVCacheFormat(op0.kv_heads, d // op0.num_heads, max_len,
                        jnp.float32)
    cache = {nm: fmt.layer(fmt.zeros(b, 1), 0) for nm in blocks}
    out = np.zeros((b, t_tok), np.int64)
    out[:, :plen] = prompt
    for p in range(t_tok - 1):
        tok = jnp.asarray(out[:, p], jnp.int32)
        x = nodes["embeddings"].op.embed_at(params["embeddings"], tok, p)
        for nm in blocks:
            x, cache[nm] = nodes[nm].op.decode(
                params[nm], x, cache[nm], p, fmt)
        h = nodes["final_ln"].op.apply(params["final_ln"], x)
        logits = nodes["lm_head"].op.apply(params["lm_head"], h)
        nxt = np.asarray(jnp.argmax(logits.astype(jnp.float32), -1))
        if p + 1 >= plen:
            out[:, p + 1] = nxt
    return out


def full_recompute_greedy(graph, params, prompt, t_tok):
    """Greedy decode by re-running the whole causal graph every token."""
    cur = np.asarray(prompt, np.int64)
    while cur.shape[1] < t_tok:
        logits = graph.apply(params, jnp.asarray(cur, jnp.int32))
        nxt = np.asarray(jnp.argmax(logits[:, -1].astype(jnp.float32), -1))
        cur = np.concatenate([cur, nxt[:, None].astype(np.int64)], 1)
    return cur


def _family(name, **size):
    from defer_tpu import models
    from defer_tpu.models.cohere_moe import tie_head
    graph = getattr(models, name)(**size)
    params = graph.init(jax.random.key(3))
    return graph, tie_head(params) if name == "cohere_moe_tiny" else params


def _weight_gauges():
    """(0, bytes placed) of the newest decoder: the first gauge is kept
    for the benchmark's drivers, no leaf rides a flat row."""
    from defer_tpu.obs import REGISTRY
    return (REGISTRY.gauge("decode.weights.row_bytes").value,
            REGISTRY.gauge("decode.weights.own_bytes").value)


@pytest.fixture(scope="module")
def model():
    graph = gpt_tiny(seq_len=MAX_LEN, vocab=VOCAB)
    params = graph.init(jax.random.key(7))
    return graph, params


@pytest.fixture(scope="module")
def prompt():
    rng = np.random.default_rng(3)
    return rng.integers(0, VOCAB, size=(8, 5)).astype(np.int32)


# 3 stages: gpt_tiny's 4 layers split 1 / 2 / 1, so the stages with one
# block get the zeroed stand-in for their second (and 8 prompts take two
# pipeline fills of 6)
@pytest.mark.parametrize("num_stages,microbatch", [(4, 2), (2, 4), (1, 8),
                                                   (3, 2)])
def test_pipelined_matches_incremental(model, prompt, num_stages, microbatch):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=microbatch, max_len=MAX_LEN)
    got = dec.generate(prompt, max_new_tokens=9)
    want = incremental_greedy(graph, params, prompt, 5 + 9, MAX_LEN)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("num_stages", [4, 3])
def test_pipelined_matches_full_recompute(model, prompt, num_stages):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=2, max_len=MAX_LEN)
    got = dec.generate(prompt, max_new_tokens=8)
    want = full_recompute_greedy(graph, params, prompt, 5 + 8)
    np.testing.assert_array_equal(got, want)


def test_partial_group_occupancy(model, prompt):
    """B < num_stages*microbatch: unused slots are bubbles, results exact."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                           max_len=MAX_LEN)
    got = dec.generate(prompt[:4], max_new_tokens=6)
    want = incremental_greedy(graph, params, prompt[:4], 5 + 6, MAX_LEN)
    np.testing.assert_array_equal(got, want)


def test_prompt_only_roundtrip(model, prompt):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN)
    out = dec.generate(prompt, max_new_tokens=0)
    np.testing.assert_array_equal(out, prompt)


def test_chunked_dispatch_matches_single_dispatch(model, prompt):
    """token_chunk splits the scan into several dispatches with carried
    state; results must be identical, and one compiled program must serve
    different generation lengths."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                           max_len=MAX_LEN)
    whole = dec.generate(prompt, max_new_tokens=9)
    chunked = dec.generate(prompt, max_new_tokens=9, token_chunk=2)
    np.testing.assert_array_equal(whole, chunked)
    n_compiled = len(dec._decode_fns)
    shorter = dec.generate(prompt, max_new_tokens=4, token_chunk=2)
    assert len(dec._decode_fns) == n_compiled  # same program, shorter run
    np.testing.assert_array_equal(shorter, whole[:, : 5 + 4])


def test_sampling_deterministic_and_chunking_invariant(model, prompt):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN)
    a = dec.generate(prompt, max_new_tokens=8, temperature=1.0, seed=11)
    b = dec.generate(prompt, max_new_tokens=8, temperature=1.0, seed=11)
    np.testing.assert_array_equal(a, b)          # same seed -> same draw
    c = dec.generate(prompt, max_new_tokens=8, temperature=1.0, seed=11,
                     token_chunk=3)
    np.testing.assert_array_equal(a, c)          # chunking-invariant
    d = dec.generate(prompt, max_new_tokens=8, temperature=1.0, seed=12)
    assert not np.array_equal(a, d)              # different seed differs
    assert (a[:, 5:] < VOCAB).all() and (a[:, 5:] >= 0).all()
    e = dec.generate(prompt, max_new_tokens=8, temperature=1.0, seed=11,
                     top_k=5)
    assert e.shape == a.shape


def test_eos_early_stop(model, prompt):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN)
    ref = dec.generate(prompt, max_new_tokens=10)
    # pick the token the greedy run emits first as the "EOS" so it triggers
    eos = int(ref[0, 5])
    got = dec.generate(prompt, max_new_tokens=10, eos_id=eos, token_chunk=2)
    assert got.shape == ref.shape
    for r in range(got.shape[0]):
        gen = got[r, 5:]
        hits = np.where(gen == eos)[0]
        if hits.size:                      # everything after first EOS is EOS
            assert (gen[hits[0]:] == eos).all()
    # rows must agree with the unconstrained run up to their first EOS
    row0 = ref[0, 5:]
    stop = np.where(row0 == eos)[0][0]
    np.testing.assert_array_equal(got[0, 5: 5 + stop + 1],
                                  ref[0, 5: 5 + stop + 1])


@pytest.mark.parametrize("num_stages,microbatch", [(4, 2), (1, 8), (3, 2)])
def test_fused_prefill_matches_decode_rate(model, prompt, num_stages,
                                           microbatch):
    """prefill=True seeds the caches with the pipelined full-sequence pass;
    greedy tokens must match the decode-rate teacher-forced path."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=microbatch, max_len=MAX_LEN)
    slow = dec.generate(prompt, max_new_tokens=9)
    fast = dec.generate(prompt, max_new_tokens=9, prefill=True)
    np.testing.assert_array_equal(slow, fast)


def test_prefill_single_new_token(model, prompt):
    """max_new_tokens=1 with prefill needs zero decode steps."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN)
    ref = dec.generate(prompt, max_new_tokens=1)
    got = dec.generate(prompt, max_new_tokens=1, prefill=True)
    np.testing.assert_array_equal(ref, got)


def test_prefill_with_chunking_and_eos(model, prompt):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN)
    ref = dec.generate(prompt, max_new_tokens=8)
    got = dec.generate(prompt, max_new_tokens=8, prefill=True,
                       token_chunk=2)
    np.testing.assert_array_equal(ref, got)
    eos = int(ref[0, 6])
    stopped = dec.generate(prompt, max_new_tokens=8, prefill=True,
                           token_chunk=2, eos_id=eos)
    gen = stopped[0, 5:]
    hits = np.where(gen == eos)[0]
    assert hits.size and (gen[hits[0]:] == eos).all()


def test_gqa_decode_matches_references(prompt):
    """GQA (kv_heads < num_heads): pipelined == incremental == recompute,
    and the engine's cache uses the narrow KV head count."""
    graph = gpt_tiny(seq_len=MAX_LEN, vocab=VOCAB, kv_heads=1)
    params = graph.init(jax.random.key(9))
    dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                           max_len=MAX_LEN)
    assert decoder_parts(graph, 4).geometry == ((2, 1, 16),) * 4
    assert dec.state_format.kv_heads == 1       # cache halved vs MHA
    got = dec.generate(prompt, max_new_tokens=8)
    want = incremental_greedy(graph, params, prompt, 5 + 8, MAX_LEN)
    np.testing.assert_array_equal(got, want)
    full = full_recompute_greedy(graph, params, prompt, 5 + 8)
    np.testing.assert_array_equal(got, full)
    fast = dec.generate(prompt, max_new_tokens=8, prefill=True)
    np.testing.assert_array_equal(got, fast)


def test_batch_beyond_one_pipeline_fill(model, prompt):
    """B > num_stages*microbatch runs in rounds; results match per-row."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=MAX_LEN)
    got = dec.generate(prompt, max_new_tokens=6)       # B=8 > 4
    want = incremental_greedy(graph, params, prompt, 5 + 6, MAX_LEN)
    np.testing.assert_array_equal(got, want)


def test_multi_round_sampling_draws_independently(model):
    """Identical prompts in different rounds must not sample identical
    continuations (each round derives its own seed)."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=MAX_LEN)
    same = np.full((8, 5), 3, np.int32)  # two rounds of four equal prompts
    out = dec.generate(same, 8, temperature=1.0, seed=0)
    assert not np.array_equal(out[:4], out[4:])


def test_int8_kv_cache(model, prompt):
    """kv_cache='int8': int8 rows + per-row scales, ~1/254 relative error;
    composes with prefill and GQA-free decode alike."""
    graph, params = model
    ref_dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                               max_len=MAX_LEN)
    q_dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                             max_len=MAX_LEN, kv_cache="int8")
    assert q_dec._init_state()[1]["k"][0].dtype == jnp.int8
    ref = ref_dec.generate(prompt, max_new_tokens=8)
    got = q_dec.generate(prompt, max_new_tokens=8)
    # tokens may differ where logits are within quant error; demand strong
    # agreement on this tiny model and exact prompt echo
    assert (got[:, :5] == prompt).all()
    agree = (got == ref).mean()
    assert agree > 0.9, (agree, got, ref)
    # deterministic + prefill path works
    np.testing.assert_array_equal(got, q_dec.generate(prompt, 8))
    pre = q_dec.generate(prompt, max_new_tokens=8, prefill=True)
    assert (pre == got).mean() > 0.9


def test_defer_generate_convenience(model, prompt):
    """Defer.generate wires the decoder into the flagship API."""
    import defer_tpu as dt
    graph, params = model
    defer = dt.Defer(config=dt.DeferConfig(microbatch=2))
    got = defer.generate(graph, params, prompt, 6, num_stages=4)
    want = incremental_greedy(graph, params, prompt, 5 + 6, MAX_LEN)
    np.testing.assert_array_equal(got, want)


def test_defer_score(model, prompt):
    """Defer.score: pipeline log-likelihood == direct single-program."""
    import defer_tpu as dt
    graph, params = model
    rng = np.random.default_rng(5)
    ids = rng.integers(0, VOCAB, size=(4, 10)).astype(np.int32)
    defer = dt.Defer(config=dt.DeferConfig(microbatch=2, chunk=4))
    lp, ppl = defer.score(graph, params, ids, num_stages=4)
    logits = np.asarray(graph.apply(params, jnp.asarray(ids)))
    ref_logp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), -1)
    pick = jnp.take_along_axis(ref_logp[:, :-1],
                               jnp.asarray(ids[:, 1:, None]), -1)[..., 0]
    np.testing.assert_allclose(lp, np.asarray(pick.sum(-1)), rtol=1e-4)
    assert (ppl > 0).all() and np.allclose(ppl, np.exp(-lp / 9), rtol=1e-6)


def test_w8a16_weight_quant_decode(model, prompt):
    """int8 weight-only decoding (channel-wise scales, dequant fused in
    the stage branch): the leaves really are int8, generations agree
    strongly with the f32 engine, and reweight works under quant."""
    from defer_tpu.ops.quant import Int8Weight
    graph, params = model
    ref = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                           max_len=MAX_LEN)
    ref_placed = sum(_weight_gauges())
    assert ref_placed == sum(
        leaf.nbytes for leaf in jax.tree.leaves(params))
    q = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                         max_len=MAX_LEN, weight_dtype="int8")
    # the tree the f32 engine placed, each leaf an int8 array of the
    # leaf's shape beside one f32 scale a channel of its last axis
    held = jax.tree.leaves(q._w, is_leaf=lambda x: isinstance(x, Int8Weight))
    plain = jax.tree.leaves(ref._w)
    assert jax.tree.structure(q._w, is_leaf=lambda x: isinstance(
        x, Int8Weight)) == jax.tree.structure(ref._w)
    for h, leaf in zip(held, plain):
        assert h.q.dtype == jnp.int8 and h.q.shape == leaf.shape
        assert h.scale.dtype == jnp.float32 \
            and h.scale.shape == (4,) + leaf.shape[-1:]
    # the weight stream is 1 byte/elem vs 4 (scales only matter for the
    # tiny 1-D leaves; on real geometries they are ~1/last_dim overhead)
    assert _weight_gauges() == (0, ref_placed // 4 + 4 * sum(
        leaf.shape[-1] for leaf in jax.tree.leaves(params)))
    a = ref.generate(prompt, 8)
    b = q.generate(prompt, 8)
    assert (b[:, :5] == prompt).all()          # exact prompt echo
    agree = (a == b).mean()
    assert agree > 0.9, (agree, a, b)
    np.testing.assert_array_equal(b, q.generate(prompt, 8))  # deterministic
    # prefill path under quant weights
    pre = q.generate(prompt, 8, prefill=True)
    assert (pre == b).mean() > 0.9
    # reweight re-quantizes: scaled weights change the generation but the
    # engine stays compiled
    compiled = len(q._decode_fns) + len(q._prefill_fns)
    q.reweight(jax.tree.map(lambda x: x * 1.1, params))
    q.generate(prompt, 8)
    assert len(q._decode_fns) + len(q._prefill_fns) == compiled


@pytest.mark.parametrize("num_stages", [4, 3])
def test_decoder_reweight_no_recompile(model, prompt, num_stages):
    """Weights-only re-push on the decode engine: fresh params install
    into the live weight arguments, compiled decode programs are reused,
    and generations match the single-device oracle under the new
    weights."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=2, max_len=MAX_LEN)
    a = dec.generate(prompt, 6)
    compiled_before = len(dec._decode_fns) + len(dec._prefill_fns)

    params2 = jax.tree.map(lambda x: x * 1.1, params)
    dec.reweight(params2)
    b = dec.generate(prompt, 6)
    np.testing.assert_array_equal(
        b, incremental_greedy(graph, params2, prompt, 5 + 6, MAX_LEN))
    assert len(dec._decode_fns) + len(dec._prefill_fns) == compiled_before

    dec.reweight(params)  # originals restore the original generation
    np.testing.assert_array_equal(dec.generate(prompt, 6), a)

    bad = dict(params2)
    bad["lm_head"] = {"w": np.zeros((2, 2), np.float32),
                      "b": np.zeros((2,), np.float32)}
    with pytest.raises(ValueError, match="reweight: lm_head"):
        dec.reweight(bad)
    # dtype drift with matching shapes must also be refused: the buffer
    # would otherwise blind-cast the values
    drift = dict(params)
    drift["lm_head"] = jax.tree.map(
        lambda a: np.asarray(a).astype(np.int32), params["lm_head"])
    with pytest.raises(ValueError, match="reweight: lm_head"):
        dec.reweight(drift)
    # a refused reweight leaves the deployed weights where they were
    np.testing.assert_array_equal(dec.generate(prompt, 6), a)


@pytest.mark.parametrize("name,node,key", [
    ("gpt_tiny", "block_2", "qkv"), ("gpt_tiny", "block_0", "fc1"),
    ("gpt_tiny", "embeddings", "wte"), ("olmoe_tiny", "block_1", "q"),
    ("olmoe_tiny", "block_0", "router"), ("olmoe_tiny", "embeddings", "wte"),
    ("gpt_tiny", "final_ln", "scale"), ("gpt_tiny", "block_1", "ln2"),
    ("olmoe_tiny", "block_1", "q_norm"), ("olmoe_tiny", "final_ln", "scale")])
def test_reweight_changed_matrices_and_wrong_shapes(prompt, name, node, key):
    """``reweight`` with one leaf changed, a matrix or a norm's scale,
    gives what a fresh decoder on those weights gives, through the
    programs already compiled, and one of another shape or type is
    refused by its node's name: one error path for every leaf."""
    graph, params = _family(name, seq_len=MAX_LEN, vocab=VOCAB)
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN)
    a = dec.generate(prompt, 6, prefill=True)
    compiled = len(dec._decode_fns) + len(dec._prefill_fns)
    rng = np.random.default_rng(17)
    changed = dict(params)
    changed[node] = dict(params[node], **{key: jax.tree.map(
        lambda x: x + 0.5 * rng.standard_normal(x.shape).astype(x.dtype),
        params[node][key])})
    dec.reweight(changed)
    b = dec.generate(prompt, 6, prefill=True)
    assert len(dec._decode_fns) + len(dec._prefill_fns) == compiled
    fresh = PipelinedDecoder(graph, changed, num_stages=2, microbatch=4,
                             max_len=MAX_LEN)
    np.testing.assert_array_equal(b, fresh.generate(prompt, 6, prefill=True))
    assert not np.array_equal(a, b)

    wrong = dict(params)
    wrong[node] = dict(params[node], **{key: jax.tree.map(
        lambda x: np.zeros(x.shape + (2,), np.float32), params[node][key])})
    with pytest.raises(ValueError, match=f"reweight: {node}'s leaves"):
        dec.reweight(wrong)
    drift = dict(params)
    drift[node] = dict(params[node], **{key: jax.tree.map(
        lambda x: np.asarray(x).astype(np.float16), params[node][key])})
    with pytest.raises(ValueError, match=f"reweight: {node}'s leaves"):
        dec.reweight(drift)
    np.testing.assert_array_equal(dec.generate(prompt, 6, prefill=True), b)


@pytest.mark.parametrize("num_stages", [1, 3, 4])
def test_gpt_weights_are_arguments_of_their_own(model, num_stages):
    """Every leaf of GPT-2's nodes, ``final_ln``'s with the matrices, is
    a stage-sharded argument of its own in its own shape — the ring asks
    a node nothing about placement — and the gauges say so."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=2, max_len=MAX_LEN)
    assert set(dec._w) == {"blocks", "ends"}
    assert len(dec._w["blocks"]) == dec.l_max
    for tree in dec._w["blocks"]:
        assert jax.tree.structure(tree) \
            == jax.tree.structure(params["block_0"])
    last = num_stages - 1
    assert set(dec._w["ends"]) == {"embeddings", "final_ln", "lm_head"}
    for nm, at in (("embeddings", 0), ("final_ln", last), ("lm_head", last)):
        # each end whole on the stage that holds it, zeros elsewhere
        for got, want in zip(jax.tree.leaves(dec._w["ends"][nm]),
                             jax.tree.leaves(params[nm]), strict=True):
            assert got.shape == (num_stages,) + want.shape
            np.testing.assert_array_equal(np.asarray(got[at]), want)
            assert not np.delete(np.asarray(got), at, axis=0).any()
    # a stage with fewer blocks than the fullest holds zeros
    for s, blocks in enumerate(dec.stage_blocks):
        for l in range(dec.l_max):
            w = np.asarray(dec._w["blocks"][l]["qkv"]["w"][s])
            if l < len(blocks):
                np.testing.assert_array_equal(
                    w, np.asarray(params[blocks[l]]["qkv"]["w"]))
            else:
                assert not w.any()
    assert _weight_gauges() == (0, sum(
        leaf.nbytes for leaf in jax.tree.leaves(params)))


def _relaid_gauges():
    from defer_tpu.obs import REGISTRY
    return (REGISTRY.gauge("decode.weights.relaid_leaves").value,
            REGISTRY.gauge("decode.weights.relaid_bytes").value)


@pytest.mark.parametrize("weight_dtype", [None, "int8"])
@pytest.mark.parametrize("num_stages", [1, 3])
def test_every_weight_leaf_lies_row_major_behind_the_stage_axis(
        model, num_stages, weight_dtype):
    """Every leaf the decoder holds — a stage with fewer blocks' zeros,
    an ``Int8Weight``'s values and scales alike — reports the row-major
    layout and the stage sharding, which is what ``weight_formats``
    declares to a script that lowers from shapes."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=2, max_len=MAX_LEN,
                           weight_dtype=weight_dtype)
    leaves = jax.tree.leaves(dec._w)
    formats = jax.tree.leaves(dec.weight_formats())
    assert len(leaves) == len(formats) > 0
    for leaf, fmt in zip(leaves, formats):
        assert leaf.format.layout.major_to_minor == tuple(range(leaf.ndim))
        assert fmt.layout.major_to_minor == tuple(range(leaf.ndim))
        assert leaf.sharding == fmt.sharding == NamedSharding(
            dec.mesh, P("stage", *(None,) * (leaf.ndim - 1)))
        # on the CPU the device's own order is that one: nothing re-laid
        assert not off_default_layout(leaf)
    assert _relaid_gauges() == (0, 0)


@pytest.mark.parametrize("num_stages", [1, 3])
def test_reweight_places_leaves_as_deployed_and_compiles_nothing(
        model, prompt, num_stages):
    """``reweight`` puts the new leaves where and how the deployed ones
    lay, so the decode and prefill programs — compiled for the layouts
    their arguments had — are the same objects and take them without a
    second compilation."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=4, max_len=MAX_LEN)
    dec.generate(prompt, 6, prefill=True)

    def programs():
        return {(kind, key): fn for kind, held in (
            ("decode", dec._decode_fns), ("prefill", dec._prefill_fns))
            for key, fn in held.items()}

    fns = programs()
    assert {kind for kind, _ in fns} == {"decode", "prefill"}
    misses = {key: fn._cache_size() for key, fn in fns.items()}
    before = [leaf.format for leaf in jax.tree.leaves(dec._w)]

    params2 = jax.tree.map(lambda x: x * 1.1, params)
    dec.reweight(params2)
    assert [leaf.format for leaf in jax.tree.leaves(dec._w)] == before
    got = dec.generate(prompt, 6, prefill=True)
    np.testing.assert_array_equal(
        got, incremental_greedy(graph, params2, prompt, 5 + 6, MAX_LEN))
    after = programs()
    assert after.keys() == fns.keys()
    for key, fn in after.items():
        assert fn is fns[key]
        assert fn._cache_size() == misses[key], key


@pytest.mark.parametrize("num_stages", [1, 2])
def test_widths_that_are_no_multiple_of_128_decode_as_the_reference(
        num_stages):
    """GPT-2 XL's kind of width (``d_model`` 192, ``mlp`` 768: neither
    matrix has a whole number of lane tiles both ways, which is where a
    device's default order leaves row-major) generates the incremental
    reference's tokens, prompt prefilled and not."""
    from defer_tpu.models import gpt
    graph = gpt(2, 192, 3, MAX_LEN, vocab=VOCAB)
    params = graph.init(jax.random.key(11))
    assert params["block_0"]["fc2"]["w"].shape == (768, 192)
    prompt = np.random.default_rng(5).integers(
        0, VOCAB, size=(4, 5)).astype(np.int32)
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=4 // num_stages, max_len=MAX_LEN)
    want = incremental_greedy(graph, params, prompt, 5 + 7, MAX_LEN)
    np.testing.assert_array_equal(dec.generate(prompt, 7), want)
    np.testing.assert_array_equal(
        dec.generate(prompt, 7, prefill=True), want)


def test_off_default_layout_counts_a_leaf_put_in_another_order(model):
    """The function behind ``decode.weights.relaid_leaves`` holds a
    placed array's layout against its device's default for the shape: an
    array put by hand with its last two dimensions exchanged counts, one
    the decoder placed does not (the CPU's default is row-major)."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=MAX_LEN)
    placed = dec._w["blocks"][0]["fc2"]["w"]
    assert not off_default_layout(placed)
    by_hand = jax.device_put(np.asarray(placed), Format(
        Layout(major_to_minor=(0, 2, 1)), placed.sharding))
    assert by_hand.format.layout.major_to_minor == (0, 2, 1)
    np.testing.assert_array_equal(np.asarray(by_hand), np.asarray(placed))
    assert off_default_layout(by_hand)
    assert [off_default_layout(a) for a in jax.tree.leaves(dec._w)] \
        == [False] * len(jax.tree.leaves(dec._w))
    assert _relaid_gauges() == (0, 0)


def test_relaid_lays_a_leaf_out_anew_and_writes_no_cache_entry(model):
    """The one program that *produces* an array in a named layout (on
    the chip: a leaf whose default is not row-major; here a leaf asked
    for with its last two dimensions exchanged) gives the leaf's values
    in that layout, gives the old array up, and is never written to the
    persistent compilation cache, whatever the floor for writing is: a
    program read back from there tags its results with the default
    layout."""
    import glob
    import os
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=MAX_LEN)
    placed = dec._w["blocks"][0]["fc1"]["w"]
    values = np.asarray(placed)
    cache_dir = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        out = relaid(placed, Format(Layout((0, 2, 1)), placed.sharding))
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
    assert out.format.layout.major_to_minor == (0, 2, 1)
    assert out.sharding == placed.sharding and off_default_layout(out)
    np.testing.assert_array_equal(np.asarray(out), values)
    assert placed.is_deleted()
    if cache_dir:
        assert not glob.glob(os.path.join(cache_dir, "jit__as_is*"))
    # a program compiled for the leaf takes it as it lies
    twice = jax.jit(lambda w: w * 2)
    assert twice.lower(out).compile().input_formats[0][0].layout \
        .major_to_minor == (0, 2, 1)
    np.testing.assert_array_equal(np.asarray(twice(out)), values * 2)


def test_defer_score_bucketed_short_sequence(model):
    """Scoring T=6 under a 24-token graph routes through a power-of-two
    bucketed pipeline (8 positions, not 24) with identical results."""
    import defer_tpu as dt
    graph, params = model
    rng = np.random.default_rng(9)
    ids = rng.integers(0, VOCAB, size=(4, 6)).astype(np.int32)
    defer = dt.Defer(config=dt.DeferConfig(microbatch=2, chunk=4))
    lp, ppl = defer.score(graph, params, ids, num_stages=4)
    # the cached pipeline really is the short-bucket one
    (g_ref, p_ref, pipe), = [v for k, v in defer._score_cache.items()]
    assert g_ref is graph and p_ref is params
    assert pipe.in_spec.shape[0] == 8  # next pow2 >= 6
    # identical log-likelihoods vs the full-length direct computation
    logits = np.asarray(graph.apply(params, jnp.asarray(
        np.pad(ids, ((0, 0), (0, MAX_LEN - 6))))))[:, :6]
    ref_logp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), -1)
    pick = jnp.take_along_axis(ref_logp[:, :-1],
                               jnp.asarray(ids[:, 1:, None]), -1)[..., 0]
    np.testing.assert_allclose(lp, np.asarray(pick.sum(-1)), rtol=1e-4)
    # second call with the same (graph, params, T-bucket) reuses the pipe
    defer.score(graph, params, ids, num_stages=4)
    assert len(defer._score_cache) == 1


@pytest.mark.slow
def test_defer_score_bucket_speedup():
    """The bucketed path must actually be cheaper: steady-state scoring of
    short sequences beats the full-length pipeline by >=4x (VERDICT r4 #8
    'done' bar).  Needs a compute-dominated config (T=256, d=128) so the
    per-dispatch overhead doesn't mask the work ratio; timed on compiled,
    warmed pipelines, min over reps."""
    import time
    import defer_tpu as dt
    from defer_tpu.models.gpt import gpt
    graph = gpt(4, 128, 4, seq_len=256, vocab=VOCAB, name="gpt_score_perf")
    params = graph.init(jax.random.key(2))
    rng = np.random.default_rng(11)
    ids = rng.integers(0, VOCAB, size=(4, 10)).astype(np.int32)
    defer = dt.Defer(config=dt.DeferConfig(microbatch=2, chunk=4))

    def steady(fn):
        fn()  # compile/warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    short = steady(lambda: defer.score(graph, params, ids, num_stages=4))
    full_ids = np.zeros((4, 256), np.int32)
    full_ids[:, :10] = ids  # the old behavior: pad to the graph's length
    full = steady(lambda: defer.score(graph, params, full_ids,
                                      num_stages=4))
    assert full / short >= 4, (short, full)


def test_defer_generate_caches_decoder(model, prompt):
    """Repeated Defer.generate reuses one PipelinedDecoder (ADVICE r4):
    rebuilding repacked weights + re-jitted the decode program per call."""
    import defer_tpu as dt
    graph, params = model
    defer = dt.Defer(config=dt.DeferConfig(microbatch=2))
    a = defer.generate(graph, params, prompt, 4, num_stages=4)
    dec1 = next(iter(defer._decoder_cache.values()))[2]
    b = defer.generate(graph, params, prompt, 4, num_stages=4)
    dec2 = next(iter(defer._decoder_cache.values()))[2]
    assert dec1 is dec2 and len(defer._decoder_cache) == 1
    np.testing.assert_array_equal(a, b)
    # different kv_cache => different engine, cache grows
    defer.generate(graph, params, prompt, 4, num_stages=4, kv_cache="int8")
    assert len(defer._decoder_cache) == 2


def test_gqa_int8_prefill_sampling_compose(prompt):
    """All decoder features at once: GQA + int8 cache + fused prefill +
    top-k sampling + chunking + EOS, generating to the max_len boundary."""
    graph = gpt_tiny(seq_len=MAX_LEN, vocab=VOCAB, kv_heads=1)
    params = graph.init(jax.random.key(11))
    dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                           max_len=MAX_LEN, kv_cache="int8")
    new = MAX_LEN - 5  # generate right up to the positional-table edge
    a = dec.generate(prompt, new, prefill=True, temperature=0.7,
                     top_k=7, seed=3, token_chunk=4)
    b = dec.generate(prompt, new, prefill=True, temperature=0.7,
                     top_k=7, seed=3)  # single dispatch
    np.testing.assert_array_equal(a, b)  # chunking-invariant end to end
    assert a.shape == (8, MAX_LEN)
    assert (a[:, :5] == prompt).all()
    assert ((a >= 0) & (a < VOCAB)).all()
    eos = int(a[0, 7])
    c = dec.generate(prompt, new, prefill=True, temperature=0.7,
                     top_k=7, seed=3, token_chunk=4, eos_id=eos)
    gen = c[0, 5:]
    hits = np.where(gen == eos)[0]
    assert hits.size and (gen[hits[0]:] == eos).all()


def reference_beam(graph, params, prompt, max_new, beam, max_len):
    """Single-device beam search from the same decode ops + expansion math
    (flat top-k of beam*V cumulative log-probs, duplicate-masked first
    expansion, cache re-parenting before each append)."""
    nodes = graph.nodes
    blocks = [nm for nm in graph.topo_order if nm.startswith("block_")]
    op0 = nodes[blocks[0]].op
    d = nodes[blocks[0]].out_spec.shape[-1]
    vocab = nodes["lm_head"].out_spec.shape[-1]
    b, plen = prompt.shape
    t_tok = plen + max_new
    outs = []
    for s in range(b):
        seqs = np.tile(prompt[s], (beam, 1)).astype(np.int64)
        fmt = KVCacheFormat(op0.kv_heads, d // op0.num_heads, max_len,
                            jnp.float32)
        cache = {nm: fmt.layer(fmt.zeros(beam, 1), 0) for nm in blocks}
        cum = jnp.zeros(beam)
        for p in range(t_tok - 1):
            tok = jnp.asarray(seqs[:, p], jnp.int32)
            x = nodes["embeddings"].op.embed_at(params["embeddings"],
                                                tok, p)
            for nm in blocks:
                x, cache[nm] = nodes[nm].op.decode(
                    params[nm], x, cache[nm], p, fmt)
            if p < plen - 1:
                continue  # forced prompt token; no expansion
            h = nodes["final_ln"].op.apply(params["final_ln"], x)
            logits = nodes["lm_head"].op.apply(
                params["lm_head"], h).astype(jnp.float32)
            sc = cum[:, None] + jax.nn.log_softmax(logits, -1)
            if p == plen - 1:
                sc = sc.at[1:].set(-jnp.inf)
            best, idx = jax.lax.top_k(sc.reshape(1, beam * vocab), beam)
            parent = np.asarray(idx[0] // vocab)
            new_tok = np.asarray(idx[0] % vocab, np.int64)
            cum = best[0]
            seqs = np.concatenate([seqs[parent],
                                   new_tok[:, None]], axis=1)
            cache = {nm: {key: jnp.take(buf, jnp.asarray(parent), axis=0)
                          for key, buf in cache[nm].items()}
                     for nm in blocks}
        outs.append(seqs[int(np.argmax(np.asarray(cum)))])
    return np.stack(outs)


@pytest.mark.parametrize("num_stages,microbatch,beam", [(4, 4, 2), (2, 6, 3),
                                                        (1, 4, 4), (3, 4, 2)])
def test_pipelined_beam_matches_reference(model, prompt, num_stages,
                                          microbatch, beam):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=microbatch, max_len=MAX_LEN,
                           beam_width=beam)
    nspg = microbatch // beam
    b = min(8, num_stages * nspg)
    b -= b % nspg
    got = dec.generate(prompt[:b], max_new_tokens=8)
    want = reference_beam(graph, params, prompt[:b], 8, beam, MAX_LEN)
    np.testing.assert_array_equal(got, want)


def test_beam_with_chunked_dispatch(model, prompt):
    """Chunk-overshoot steps must be true bubbles: with token_chunk the
    final dispatch overruns num_steps, and an un-guarded extra expansion
    would corrupt the beam ledger before the host picks the best beam."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN, beam_width=2)
    whole = dec.generate(prompt[:4], max_new_tokens=7)
    chunked = dec.generate(prompt[:4], max_new_tokens=7, token_chunk=1)
    np.testing.assert_array_equal(whole, chunked)
    want = reference_beam(graph, params, prompt[:4], 7, 2, MAX_LEN)
    np.testing.assert_array_equal(whole, want)


def test_beam_one_equals_greedy(model, prompt):
    graph, params = model
    greedy = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                              max_len=MAX_LEN)
    beam1 = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                             max_len=MAX_LEN, beam_width=1)
    np.testing.assert_array_equal(greedy.generate(prompt, 6),
                                  beam1.generate(prompt, 6))


def test_token_streaming_callback(model, prompt):
    """on_tokens delivers contiguous, non-overlapping spans that concat to
    exactly the generated region — with chunking and with prefill."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                           max_len=MAX_LEN)
    for kw in (dict(token_chunk=2), dict(token_chunk=3, prefill=True)):
        spans = []
        out = dec.generate(prompt, 9, on_tokens=lambda lo, hi, t, rows:
                           spans.append((lo, hi, t, rows)), **kw)
        los = [s[0] for s in spans]
        his = [s[1] for s in spans]
        assert los[0] == 5 and his[-1] == 14
        assert all(h == l for h, l in zip(his[:-1], los[1:]))  # contiguous
        assert all(s[3] == (0, 8) for s in spans)
        streamed = np.concatenate([s[2] for s in spans], axis=1)
        np.testing.assert_array_equal(streamed, out[:, 5:])


def test_streaming_multi_round(model, prompt):
    """B beyond one pipeline fill: spans arrive per round with the round's
    row range, and together cover every sequence."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=MAX_LEN)  # capacity 4 < B=8
    spans = []
    out = dec.generate(prompt, 6, token_chunk=2,
                       on_tokens=lambda lo, hi, t, rows:
                       spans.append((lo, hi, t, rows)))
    row_ranges = {s[3] for s in spans}
    assert row_ranges == {(0, 4), (4, 8)}
    for r0, r1 in sorted(row_ranges):
        streamed = np.concatenate(
            [s[2] for s in spans if s[3] == (r0, r1)], axis=1)
        np.testing.assert_array_equal(streamed, out[r0:r1, 5:])


def test_streaming_with_eos(model, prompt):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN)
    ref = dec.generate(prompt, 10)
    eos = int(ref[0, 6])
    spans = []
    out = dec.generate(prompt, 10, eos_id=eos, token_chunk=2,
                       on_tokens=lambda lo, hi, t, rows:
                       spans.append((lo, hi)))
    assert spans and spans[0][0] == 5
    gen = out[0, 5:]
    hits = np.where(gen == eos)[0]
    assert hits.size and (gen[hits[0]:] == eos).all()


def _chunk_counts():
    """(chunks launched, chunks read, launched ahead, discarded) so far."""
    from defer_tpu.obs import REGISTRY
    return (REGISTRY.histogram("decode.dispatch_s").count,
            REGISTRY.histogram("decode.sync_s").count,
            REGISTRY.counter("decode.ahead.launched").n,
            REGISTRY.counter("decode.ahead.discarded").n)


@pytest.mark.parametrize("prefill", [False, True],
                         ids=["teacher_forced", "prefill"])
@pytest.mark.parametrize("num_stages", [1, 2, 3, 4])
def test_streaming_keeps_one_chunk_ahead_and_changes_no_token(
        model, prompt, num_stages, prefill):
    """The loop launches chunk n+1 before it reads chunk n: the tokens
    streamed and returned are the ones a generation with nothing
    streamed returns, and ``on_tokens`` never holds a chunk's tokens
    before the next chunk (where there is one) was launched.  Four new
    positions a chunk do not divide the eleven generated."""
    graph, params = model
    rows = num_stages * 2
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=2, max_len=MAX_LEN)
    want = dec.generate(prompt[:rows], 11, prefill=prefill)
    num_steps, chunk_steps = dec._schedule(16, 5 if prefill else 0, 4)
    chunks = -(-num_steps // chunk_steps)
    assert chunks >= 3 and num_steps % chunk_steps
    before = _chunk_counts()
    spans, seen = [], []

    def on_tokens(lo, hi, toks, rows):
        spans.append((lo, hi, toks))
        seen.append([now - was for now, was in
                     zip(_chunk_counts(), before)])

    got = dec.generate(prompt[:rows], 11, prefill=prefill, token_chunk=4,
                       on_tokens=on_tokens)
    np.testing.assert_array_equal(got, want)
    assert spans[0][0] == 5 and spans[-1][1] == 16
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
    np.testing.assert_array_equal(
        np.concatenate([t for _lo, _hi, t in spans], axis=1), want[:, 5:])
    for launched, read, _ahead, discarded in seen:
        assert not discarded
        if read:        # (a prefill's own first token comes before any)
            assert launched == min(read + 1, chunks)
    launched, read, ahead, discarded = [
        now - was for now, was in zip(_chunk_counts(), before)]
    assert (launched, read, ahead, discarded) \
        == (chunks, chunks, chunks - 1, 0)


@pytest.mark.parametrize("prefill", [False, True],
                         ids=["teacher_forced", "prefill"])
@pytest.mark.parametrize("num_stages", [1, 2, 4])
def test_an_eos_stop_discards_the_one_chunk_launched_past_it(
        model, prompt, num_stages, prefill):
    """Every sequence the same, so all reach the EOS together: the stop
    is seen when the chunk that holds it is read, one chunk is running
    by then, and its tokens reach neither the callback nor the result
    (which is what a whole generation gives, frozen at the EOS)."""
    graph, params = model
    same = np.repeat(prompt[:1], num_stages * 2, axis=0)
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=2, max_len=MAX_LEN)
    ref = dec.generate(same, 16, prefill=prefill)
    eos = int(ref[0, 5 + 3])
    at = 5 + int(np.where(ref[0, 5:] == eos)[0][0])    # first EOS position
    want = ref.copy()
    want[:, at + 1:] = eos
    num_steps, chunk_steps = dec._schedule(21, 5 if prefill else 0, 2)
    chunks = -(-num_steps // chunk_steps)
    before = _chunk_counts()
    his = []
    got = dec.generate(same, 16, prefill=prefill, token_chunk=2, eos_id=eos,
                       on_tokens=lambda lo, hi, t, rows: his.append(hi))
    np.testing.assert_array_equal(got, want)
    launched, read, _ahead, discarded = [
        now - was for now, was in zip(_chunk_counts(), before)]
    assert launched == read + 1 < chunks and discarded == 1
    # the chunk that showed the stop was the last one handed over: two
    # positions a chunk, so nothing at or past ``at + 2`` was
    assert at < his[-1] <= at + 2
    assert dec.state is not None        # at or one chunk past the stop
    # and the decoder goes on: the next generation waits for that chunk
    np.testing.assert_array_equal(dec.generate(same, 16, prefill=prefill),
                                  ref)


@pytest.mark.parametrize("then", ["generate", "reweight"])
@pytest.mark.parametrize("num_stages", [1, 2, 4])
def test_a_callback_that_raises_leaves_one_chunk_running_and_the_decoder_whole(
        model, prompt, num_stages, then):
    graph, params = model
    rows = num_stages * 2
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=2, max_len=MAX_LEN)
    want = dec.generate(prompt[:rows], 12, prefill=True, token_chunk=3)

    class Stop(Exception):
        pass

    def on_tokens(lo, hi, toks, rows):
        np.testing.assert_array_equal(toks, want[:, lo:hi])
        if hi >= 5 + 4:
            raise Stop

    before = _chunk_counts()
    with pytest.raises(Stop):
        dec.generate(prompt[:rows], 12, prefill=True, token_chunk=3,
                     on_tokens=on_tokens)
    launched, read, ahead, discarded = [
        now - was for now, was in zip(_chunk_counts(), before)]
    assert read >= 1 and (launched, ahead, discarded) == (read + 1, read, 1)
    # the chunk launched ahead is what the next allocation waits for
    assert dec._tail is not None and dec.state is not None
    if then == "reweight":
        dec.reweight(params)
        assert dec._tail is None
    got = dec.generate(prompt[:rows], 12, prefill=True, token_chunk=3)
    np.testing.assert_array_equal(got, want)


def test_a_chunks_ids_are_read_from_stage_zeros_own_buffer(model, prompt):
    """``ids[0]`` of the stage-sharded output is a program of its own,
    queued behind whatever was launched last; the loop reads the shard
    that holds the same numbers, a transfer of the chunk's own buffer."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=2,
                           max_len=MAX_LEN)
    dec.generate(prompt, 4, token_chunk=2)
    fn = dec._get_decode_fn(8, False, None)
    a, caches = dec._init_state()
    z = jnp.int32(0)
    _a, _c, ids = fn(dec._w, jnp.zeros((4, 2, 5), jnp.int32), jnp.int32(5),
                     z, jnp.int32(8), jnp.uint32(0), jnp.float32(0),
                     jnp.zeros((4, 2), jnp.int32), jnp.int32(-1), z, a,
                     caches)
    own = ids.addressable_data(0)
    assert own.shape == (1,) + ids.shape[1:]
    assert own.devices() == {dec.mesh.devices.flat[0]}
    np.testing.assert_array_equal(np.asarray(own)[0], np.asarray(ids[0]))


def test_beam_with_int8_cache(model, prompt):
    """Beam re-parenting gathers the int8 cache AND its scale entries."""
    graph, params = model
    exact = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                             max_len=MAX_LEN, beam_width=2)
    quant = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                             max_len=MAX_LEN, beam_width=2,
                             kv_cache="int8")
    a = exact.generate(prompt[:4], 7)
    b = quant.generate(prompt[:4], 7)
    assert a.shape == b.shape and (b[:, :5] == prompt[:4]).all()
    assert (a == b).mean() > 0.85, (a, b)
    np.testing.assert_array_equal(b, quant.generate(prompt[:4], 7))


def test_beam_validation(model, prompt):
    graph, params = model
    with pytest.raises(ValueError, match="divide"):
        PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                         max_len=MAX_LEN, beam_width=3)
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN, beam_width=2)
    with pytest.raises(ValueError, match="beam search"):
        dec.generate(prompt[:4], 4, prefill=True)
    with pytest.raises(ValueError, match="beam search"):
        dec.generate(prompt[:4], 4, temperature=0.5)


def test_gqa_param_shapes():
    from defer_tpu.models.gpt import CausalTransformerBlock
    from defer_tpu.graph.ir import ShapeSpec
    blk = CausalTransformerBlock(4, num_kv_heads=2)
    p = blk.init(jax.random.key(0), (ShapeSpec((6, 32)),))
    assert p["qkv"]["w"].shape == (32, 32 + 2 * 2 * 8)  # d + 2*kv*hd
    # GQA tensor parallelism (added r5): each rank holds whole query
    # groups — nh/tp query cols + kv/tp KV cols each for K and V
    shard = blk.tp_shard(p, 2, 0)
    assert shard["qkv"]["w"].shape == (32, 16 + 2 * 8)


def test_repeat_generate_reuses_compiled_program(model, prompt):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN)
    a = dec.generate(prompt, max_new_tokens=4)
    b = dec.generate(prompt, max_new_tokens=4)
    np.testing.assert_array_equal(a, b)
    assert len(dec._decode_fns) == 1


def test_validation_errors(model, prompt):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=4,
                           max_len=MAX_LEN)
    with pytest.raises(ValueError, match="multiple of microbatch"):
        dec.generate(prompt[:3], max_new_tokens=2)
    with pytest.raises(ValueError, match="exceeds"):
        dec.generate(prompt, max_new_tokens=MAX_LEN)
    with pytest.raises(ValueError, match="at least one token"):
        dec.generate(np.zeros((8, 0), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="max_len"):
        PipelinedDecoder(graph, params, num_stages=2, microbatch=1,
                         max_len=MAX_LEN + 1)
    with pytest.raises(ValueError, match="attn_impl"):
        blk = CausalTransformerBlock(2, attn_impl="Flash")
        blk._attend(jnp.zeros((1, 2, 4, 16)), jnp.zeros((1, 2, 4, 16)),
                    jnp.zeros((1, 2, 4, 16)))


def test_split_blocks():
    assert split_blocks(4, 4) == [[0], [1], [2], [3]]
    assert split_blocks(12, 4) == [[0, 1, 2], [3, 4, 5], [6, 7, 8],
                                   [9, 10, 11]]
    assert split_blocks(5, 2) == [[0, 1], [2, 3, 4]]
    with pytest.raises(ValueError):
        split_blocks(2, 4)


#: one GPT-2 XL layer's bf16 leaves, and its final norm + head's
_XL_LAYER, _XL_HEAD = 61_493_600, 160_828_800


@pytest.mark.parametrize("costs,stages,kwargs,want", [
    # the ends are counted: a head worth two blocks takes blocks off the
    # last stage (2 | 3 | 1 + 2 where the even rule's 2 | 2 | 2 + 2 is 4)
    pytest.param([1] * 6, 3, {"last": 2}, [2, 3, 1], id="ends_counted"),
    # a small head: split_blocks' cut, exactly, rounding and all
    pytest.param([100] * 6, 3, {"last": 2}, [2, 2, 2], id="small_head"),
    # the even rule's rounding stands where it ties (3 | 2 costs the
    # same), and falls to the head where it put the odd block beside it
    pytest.param([100] * 5, 2, {}, [2, 3], id="a_tie_is_the_even_rounding"),
    pytest.param([100] * 5, 2, {"first": 1, "last": 3}, [3, 2],
                 id="the_odd_block_leaves_the_heads_stage"),
    pytest.param([100] * 7, 3, {}, [2, 3, 2], id="no_ends_is_the_even_rule"),
    pytest.param([5, 1, 7], 1, {"first": 3, "last": 9}, [3], id="one_stage"),
    # a pattern of 4: only whole periods (and a last stage that is a
    # prefix of one) repeat the longest stage's kinds; the head goes
    # with the short stage
    pytest.param([10] * 28, 4, {"kinds": list("ssas") * 7, "last": 15},
                 [8, 8, 8, 4], id="pattern_of_4_over_28"),
    # blocks of unlike cost: the costliest stage decides, not the count
    pytest.param([4, 1, 1, 1, 1], 2, {}, [1, 4], id="unlike_costs"),
    # no cut of these kinds repeats a longest stage: the even one, for
    # the holder to refuse
    pytest.param([3, 1, 1, 1, 3, 3], 3, {"kinds": list("abcdab")},
                 [2, 2, 2], id="no_cut_passes_is_the_even_one"),
    # GPT-2 XL on four chips: 48 layers, the head 2.6 of them, the
    # first stage's gathered rows next to nothing — but not nothing
    pytest.param([_XL_LAYER] * 48, 4, {"first": 12_800, "last": _XL_HEAD},
                 [12, 13, 13, 10], id="gpt2xl_on_four"),
    pytest.param([_XL_LAYER] * 48, 8, {"first": 12_800, "last": _XL_HEAD},
                 [6, 6, 6, 6, 6, 7, 7, 4], id="gpt2xl_on_eight"),
])
def test_balanced_cut(costs, stages, kwargs, want):
    """The contiguous cut whose costliest stage costs least, ends
    counted; ``split_blocks``' own wherever that is among the best."""
    got = balanced_cut(costs, stages, **kwargs)
    assert got == want and sum(got) == len(costs)


@pytest.mark.parametrize("layers,stages,head", [
    (28, 4, 15), (28, 4, 0), (16, 3, 25), (8, 4, 15), (24, 5, 40)])
def test_balanced_cut_never_cuts_inside_a_period(layers, stages, head):
    """Over a pattern of 4 every stage opens where the pattern does and
    repeats the longest stage's kinds as far as it goes."""
    kinds = (list("ssas") * layers)[:layers]
    got = balanced_cut([10] * layers, stages, kinds, first=1, last=head)
    assert sum(got) == layers and min(got) >= 1
    bounds = np.cumsum([0] + got)
    longest = "".join(kinds[bounds[got.index(max(got))]:][:max(got)])
    for b, count in zip(bounds, got):
        assert longest.startswith("".join(kinds[b:b + count]))


def test_balanced_cut_ties_fall_to_the_shortest_longest_stage():
    """Where several cuts have the same costliest stage and the even
    one is not among them: the fewest blocks on the longest stage (the
    fewest zero leaves), then the bounds nearest the even cut's."""
    # 6 blocks on 3 stages, the costliest stage 6 either way
    costs = [6, 1, 1, 1, 1, 2]
    assert balanced_cut(costs, 3) == [1, 3, 2]      # not [1, 4, 1]
    assert balanced_cut(costs, 3, last=1) == [1, 3, 2]


def _patterned(layers: int, period: int):
    """A GPT graph whose every ``period``-th block has one KV head."""
    from defer_tpu import models
    graph = models.gpt(layers, 32, 2, 16, vocab=VOCAB)
    nodes = dict(graph.nodes)
    for i in range(period - 1, layers, period):
        nm = f"block_{i}"
        nodes[nm] = dataclasses.replace(nodes[nm], op=dataclasses.replace(
            nodes[nm].op, num_kv_heads=1))
    other = graph.__class__.__new__(graph.__class__)
    other.__dict__.update(graph.__dict__)
    other.nodes = nodes
    return other


@pytest.mark.parametrize("cut,words", [
    ([7, 7, 7, 7], r"stage 1's layer 0 \(block_7\) keeps KVCacheFormat.*"
     r"block_0 at the same place.*cut the graph at a whole period"),
    ([8, 8, 8, 8], "does not lay 28 blocks on 4 stages"),
    ([8, 8, 12, 0], "one or more a stage"),
    ([12, 8, 8], "names 3 stages, the ring has 4"),
])
def test_a_handed_cut_the_ring_cannot_run_is_refused(cut, words):
    """``cut=`` is checked by the rule the chooser keeps to, in the
    ring's words: a cut inside a period, a sum that is not the blocks',
    an empty stage."""
    graph = _patterned(28, 4)
    with pytest.raises(ValueError, match=words):
        decoder_parts(graph, 4, cut=cut)
    assert [len(b) for b in decoder_parts(
        graph, 4, cut=[8, 8, 8, 4]).stage_blocks] == [8, 8, 8, 4]


def test_decoder_parts_cuts_by_what_a_step_reads():
    """``step_bytes`` (what the holder's step reads of each node) picks
    the cut: whole periods of a pattern of 4 where the even rule's 7 a
    stage is refused, the head with the short stage; without it the
    even rule, unchecked, as the serving engine takes it; one stage is
    every block whatever is handed in."""
    graph = _patterned(28, 4)
    reads = dict({f"block_{i}": 100 for i in range(28)},
                 embeddings=1, final_ln=1, lm_head=150)
    counts = [len(b) for b in decoder_parts(
        graph, 4, step_bytes=reads).stage_blocks]
    assert counts == [8, 8, 8, 4]
    assert [len(b) for b in decoder_parts(graph, 4).stage_blocks] == [7] * 4
    assert decoder_parts(graph, 1, step_bytes=reads).stage_blocks == [
        [f"block_{i}" for i in range(28)]]
    # one period on four stages: the last stage's one layer is of
    # another kind than the others', whichever way it is cut
    with pytest.raises(ValueError, match=r"stage 3's layer 0 \(block_3\).*"
                       "cut the graph at a whole period"):
        decoder_parts(_patterned(4, 4), 4, step_bytes=reads)


def _cut_gauges(stages):
    from defer_tpu.obs import REGISTRY
    return ([REGISTRY.gauge(f"decode.cut.blocks.{s}").value
             for s in range(stages)],
            REGISTRY.gauge("decode.cut.stage_bytes_max").value,
            REGISTRY.gauge("decode.cut.stage_bytes_mean").value)


@pytest.mark.parametrize("layers,stages,cut,want", [
    (4, 2, None, [3, 1]), (6, 3, None, [2, 3, 1]), (4, 2, [1, 3], [1, 3]),
])
def test_an_uneven_cut_hands_out_the_one_stage_decoders_tokens(
        prompt, layers, stages, cut, want):
    """A head worth two and a half blocks: the bytes' cut takes blocks
    off the last stage (a handed-in cut stands as it is), the gauges
    say so, and the tokens are the one-stage decoder's, bit for bit —
    stepwise and through the fused prefill."""
    from defer_tpu import models
    graph = models.gpt(layers, 32, 2, MAX_LEN, vocab=1000)
    params = graph.init(jax.random.key(11))
    one = PipelinedDecoder(graph, params, num_stages=1, microbatch=8,
                           max_len=MAX_LEN)
    blocks, worst, mean = _cut_gauges(1)
    assert blocks == [layers] and worst == mean > 0
    ring = PipelinedDecoder(graph, params, num_stages=stages, microbatch=2,
                            max_len=MAX_LEN, cut=cut)
    assert [len(b) for b in ring.stage_blocks] == want
    blocks, worst, mean = _cut_gauges(stages)
    assert blocks == want and worst > mean > 0
    if cut is None:     # the even cut's costliest stage reads more
        PipelinedDecoder(graph, params, num_stages=stages, microbatch=2,
                         max_len=MAX_LEN, cut=[layers // stages] * stages)
        assert _cut_gauges(stages)[1] > worst
    for prefill in (False, True):
        np.testing.assert_array_equal(
            ring.generate(prompt, max_new_tokens=7, prefill=prefill),
            one.generate(prompt, max_new_tokens=7, prefill=prefill))


def test_a_stage_touches_the_layers_it_lacks_and_passes_none_through(model):
    """A shorter stage hands on the buffers of the local layers it
    lacks with one element rewritten in place (``LayeredState.idle``):
    a buffer a branch only passes through is copied whole on the chip,
    every step.  1 | 2 | 1: stages 0 and 2 touch layer 1's two buffers,
    stage 1 none; the stages of an even cut touch nothing."""
    from defer_tpu.ops.layered import LayeredState
    layer = {"k": jnp.ones((2, 3)), "h": jnp.ones((4,), jnp.int8)}
    idle = LayeredState.idle(layer)
    assert {key: (buf.shape, buf.dtype) for key, buf in idle.items()} == {
        key: (buf.shape, buf.dtype) for key, buf in layer.items()}
    assert int(idle["k"].sum()) == 5 and int(idle["h"].sum()) == 3
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=3, microbatch=2,
                           max_len=MAX_LEN)
    assert [len(b) for b in dec.stage_blocks] == [1, 2, 1]
    _, caches = dec._init_state()
    local = jax.tree.map(lambda a: a[0], caches)

    def touched(s):
        jaxpr = jax.make_jaxpr(lambda c: dec._idle_layers(s, c))(local)
        return sum(eqn.primitive.name == "dynamic_update_slice"
                   for eqn in jaxpr.eqns)

    assert [touched(s) for s in range(3)] == [2, 0, 2]
    even = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                            max_len=MAX_LEN)
    assert even._idle_layers(0, local) is local


def test_causal_block_full_vs_decode(model):
    """Full-sequence causal apply == stepwise decode on the same tokens."""
    graph, params = model
    blk_name = "block_0"
    op: CausalTransformerBlock = graph.nodes[blk_name].op
    p = params[blk_name]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 6, 32)), jnp.float32)
    full = np.asarray(op.apply(p, x))
    fmt = KVCacheFormat(op.num_heads, x.shape[-1] // op.num_heads, 8,
                        jnp.float32)
    cache = fmt.layer(fmt.zeros(2, 1), 0)
    for t in range(6):
        y, cache = op.decode(p, x[:, t], cache, t, fmt)
        np.testing.assert_allclose(np.asarray(y), full[:, t],
                                   rtol=2e-5, atol=2e-5)


def test_gpt_full_sequence_pipeline(model):
    """The causal graph rides the ordinary inference pipeline (scoring)."""
    from defer_tpu import SpmdPipeline, partition, pipeline_mesh
    graph, params = model
    cuts = gpt_stage_cuts(4, 4)
    stages = partition(graph, cuts)
    pipe = SpmdPipeline(stages, params, mesh=pipeline_mesh(4),
                        microbatch=2, chunk=4)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, VOCAB, size=(3, 2, MAX_LEN)).astype(np.float32)
    got = pipe.run(ids)
    want = np.stack([
        np.asarray(graph.apply(params, jnp.asarray(m, jnp.int32)))
        for m in ids])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- the ring's cache state: a row written in place, no stacked array ---------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of the programs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _decode_scan_body(dec, chunk_steps):
    n, mb = dec.num_stages, dec.microbatch
    a, caches = dec._init_state()
    jaxpr = jax.make_jaxpr(dec._get_decode_fn(chunk_steps, False, None))(
        dec._w, jnp.zeros((n, mb, 5), jnp.int32), jnp.int32(5),
        jnp.int32(0), jnp.int32(chunk_steps), jnp.uint32(0),
        jnp.float32(0.0), jnp.zeros((n, mb), jnp.int32), jnp.int32(-1),
        jnp.int32(0), a, caches)
    scans = [e for e in _walk(jaxpr.jaxpr) if e.primitive.name == "scan"]
    assert len(scans) == 1
    return scans[0].params["jaxpr"].jaxpr


@pytest.mark.parametrize("kv_cache,num_stages,beam", [
    ("buffer", 1, 1), ("buffer", 4, 1), ("int8", 1, 1), ("int8", 4, 1),
    ("buffer", 2, 2)])
def test_decode_step_writes_rows_into_per_block_buffers(model, kv_cache,
                                                        num_stages, beam):
    """Structural guard of the decode program's scan body: every write
    into a K/V (or scale) buffer is one position wide — a
    ``dynamic_update_slice``, or, where the positions lie on the lanes
    (float rows, ``head_dim`` under 128: ``ops/kv_cache.py``), the
    attention's own kernel, which writes the step's key and value rows
    into the block it reads (``kv_step``; the row-writer kernel until
    PR 54) — beam search may also re-parent one whole
    group, and no value has the shape of the stack of all local blocks'
    caches — the shape whose whole-stack copies were 92% of a step on
    the chip (docs/DECODE_CLIFF.md)."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=8 // num_stages, max_len=MAX_LEN,
                           kv_cache=kv_cache, beam_width=beam)
    body = _decode_scan_body(dec, 2 * num_stages)
    buffers = {buf.shape for buf in
               dec.state_format.buffers(dec.microbatch).values()}
    assert len(buffers) == (2 if kv_cache == "int8" else 1)
    stacked = {(dec.l_max,) + sh for sh in buffers}
    rows = groups = 0
    for eqn in _walk(body):
        for v in list(eqn.invars) + list(eqn.outvars):
            assert getattr(v.aval, "shape", None) not in stacked, eqn
        if eqn.primitive.name == "pallas_call":
            assert eqn.params["name"] != "kv_write_rows"
            if eqn.params["name"] == "kv_step":
                assert kv_cache == "buffer"
                rows += 2               # a layer's key and value rows
        if eqn.primitive.name != "dynamic_update_slice":
            continue
        buf, upd = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
        if buf not in buffers:
            continue
        if upd[3] == 1:
            assert upd[0] == 1      # one group's rows, one position
            rows += 1
        else:
            assert beam > 1 and upd == (1,) + buf[1:], (buf, upd)
            groups += 1
    blocks = sum(len(b) for b in dec.stage_blocks)
    assert rows == blocks * len(buffers) * 2        # k and v (and scales)
    assert groups == (dec.l_max * len(buffers) * 2 * num_stages
                      if beam > 1 else 0)


@dataclasses.dataclass(frozen=True, repr=False)
class _GainBlock(CausalTransformerBlock):
    """GPT-2's block with one more matrix, of which the class says
    nothing to the ring."""

    def init(self, key, in_specs):
        d = in_specs[0].shape[-1]
        return dict(super().init(key, in_specs),
                    gain={"w": jnp.eye(d, dtype=jnp.float32)})

    def decode_finish(self, params, x, y, sow=None):
        return super().decode_finish(params, x, y, sow) @ params["gain"]["w"]


def _gpt_of(blocks):
    """GPT-2 at ``gpt_tiny``'s widths with the given block ops."""
    b = GraphBuilder("gpt_of")
    x = b.input((MAX_LEN,), jnp.int32)
    x = b.add(GptEmbedding(VOCAB, 32, MAX_LEN), x, name="embeddings")
    for i, op in enumerate(blocks):
        x = b.add(op, x, name=f"block_{i}")
    x = b.add(LayerNorm(), x, name="final_ln")
    b.add(Dense(VOCAB), x, name="lm_head")
    return b.build()


@pytest.mark.parametrize("name,num_stages,beam,weight_dtype", [
    ("gpt_tiny", 1, 1, None), ("gpt_tiny", 4, 1, None),
    ("gpt_tiny", 3, 1, None), ("gpt_tiny", 2, 2, None),
    ("olmoe_tiny", 1, 1, None), ("olmoe_tiny", 2, 1, None),
    ("gain", 2, 1, None), ("gpt_tiny", 2, 1, "int8"),
    ("olmoe_tiny", 2, 1, "int8")])
def test_decode_step_takes_every_weight_in_its_own_shape(
        name, num_stages, beam, weight_dtype):
    """Structural guard of the decode program's scan body: no ``slice``
    or ``reshape`` produces a weight, flat or in its shape.  A leaf cut
    out of a flat row is laid out anew inside the loop, every step (12
    of 21 ms a step at GPT-2 XL's widths on the chip, 7 of 21 at
    OLMoE's with the experts alone out of the row); every leaf arrives
    as an argument in its own shape, whatever its block's class and
    whether it is held int8."""
    if name == "gain":
        graph = _gpt_of([_GainBlock(2)] * 4)
        params = graph.init(jax.random.key(3))
        assert params["block_0"]["gain"]["w"].shape == (32, 32)
    else:
        graph, params = _family(name, seq_len=MAX_LEN, vocab=VOCAB)
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=4, max_len=MAX_LEN, beam_width=beam,
                           weight_dtype=weight_dtype)
    # a row's cut: a 1-D slice of the leaf's size, reshaped to the
    # leaf's shape
    shapes = {shape for leaf in jax.tree.leaves(params) if leaf.ndim > 1
              for shape in ((leaf.size,), leaf.shape)}
    cut = [eqn for eqn in _walk(_decode_scan_body(dec, 2 * num_stages))
           if eqn.primitive.name in ("slice", "reshape")
           and eqn.outvars[0].aval.shape in shapes]
    assert not cut, cut


@pytest.mark.parametrize("other,leaf", [
    (_GainBlock(2), "gain"),
    (CausalTransformerBlock(2, mlp_ratio=2), "fc1"),
], ids=["another_tree", "another_shape"])
def test_layers_whose_parameter_trees_differ_have_a_tree_each(other, leaf):
    """Local layer ``l``'s leaves are stacked over the stages: two stages
    whose ``l``-th blocks have different parameter trees, or one tree in
    different shapes (a leading dense layer at the place of another
    stage's routed one), are stacked a tree a kind, zeros on the stage
    whose block is of the other, and each stage reads its own (PR 44
    refused them; one stage, which stacks nothing, always took them)."""
    graph = _gpt_of([CausalTransformerBlock(2), other])
    params = graph.init(jax.random.key(3))
    prompt = np.arange(10).reshape(2, 5) % VOCAB
    want = incremental_greedy(graph, params, prompt, 9, MAX_LEN)
    two = PipelinedDecoder(graph, params, num_stages=2, microbatch=1,
                           max_len=MAX_LEN)
    assert two._variant == [[0, 1]]
    first, second = two._w["blocks"][0]
    if leaf in first:       # one tree in another shape
        np.testing.assert_array_equal(first[leaf]["w"][0],
                                      params["block_0"][leaf]["w"])
    np.testing.assert_array_equal(second[leaf]["w"][1],
                                  params["block_1"][leaf]["w"])
    assert not np.asarray(second[leaf]["w"][0]).any()
    assert not np.asarray(first["qkv"]["w"][1]).any()
    np.testing.assert_array_equal(two.generate(prompt, 4), want)
    one = PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                           max_len=MAX_LEN)
    assert one._variant == [None, None]
    np.testing.assert_array_equal(one.generate(prompt, 4), want)


@pytest.mark.parametrize("stages", [1, 2])
@pytest.mark.parametrize("name", [
    "gpt_tiny", "olmoe_tiny", "brumby_tiny", "cohere_moe_tiny", "jamba_tiny",
    "granite_hybrid_tiny"])
def test_the_weights_have_one_shape_whatever_the_family(name, stages):
    """``_w`` is the local blocks' trees and the ends' trees for every
    family, at one stage and at more, plain and W8A16: under W8A16 every
    leaf is an int8 argument in its own shape beside its last axis's
    f32 scales, and the bytes placed are a quarter of f32's plus the
    scales."""
    from defer_tpu.ops.quant import Int8Weight
    graph, params = _family(name)

    def held(x):
        return isinstance(x, Int8Weight)

    plain = PipelinedDecoder(graph, params, num_stages=stages, microbatch=2,
                             max_len=16)
    f32_bytes = _weight_gauges()[1]
    assert f32_bytes == sum(l.nbytes for l in jax.tree.leaves(params))
    q = PipelinedDecoder(graph, params, num_stages=stages, microbatch=2,
                         max_len=16, weight_dtype="int8")
    for dec in (plain, q):
        assert set(dec._w) == {"blocks", "ends"}
        assert isinstance(dec._w["blocks"], tuple) \
            and len(dec._w["blocks"]) == dec.l_max
        assert set(dec._w["ends"]) == {"embeddings", "final_ln", "lm_head"}
    assert jax.tree.structure(q._w, is_leaf=held) \
        == jax.tree.structure(plain._w)
    for h, leaf in zip(jax.tree.leaves(q._w, is_leaf=held),
                       jax.tree.leaves(plain._w), strict=True):
        assert h.q.dtype == jnp.int8 and h.q.shape == leaf.shape
        assert h.scale.dtype == jnp.float32 \
            and h.scale.shape == (stages,) + leaf.shape[-1:]
    assert _weight_gauges() == (0, f32_bytes // 4 + 4 * sum(
        l.shape[-1] for l in jax.tree.leaves(params)))
    # int8 of the leaf itself, scaled by its last axis's largest values
    wte = np.asarray(params["embeddings"]["wte"])
    got = q._w["ends"]["embeddings"]["wte"]
    scale = np.abs(wte).max(axis=0) / 127
    np.testing.assert_allclose(np.asarray(got.scale[0]), scale, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(got.q[0]), np.clip(np.rint(wte / np.asarray(
            got.scale[0])), -127, 127))


@pytest.mark.parametrize("quant", [False, True], ids=["buffer", "int8"])
def test_block_decode_is_its_two_halves(model, quant):
    """``decode()`` == ``decode_qkv`` + the format's write and attention
    + ``decode_finish`` (what both engines run against their own
    buffers), and the halves name no cache: key and value columns come
    out, the attention's output goes in.  The format's half of the step
    is held in tests/test_kv_cache.py."""
    graph, params = model
    op: CausalTransformerBlock = graph.nodes["block_1"].op
    p = params["block_1"]
    rng = np.random.default_rng(5)
    b, d, cache_len, pos = 3, 32, 9, 4
    hd = d // op.num_heads
    x = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
    fmt = KVCacheFormat(op.kv_heads, hd, cache_len, jnp.float32,
                        quantized=quant)
    cache = {key: jnp.asarray(
        rng.integers(-127, 128, s.shape) if s.dtype == jnp.int8
        else rng.uniform(0.01, 0.1, s.shape), s.dtype)
        for key, s in fmt.buffers(b).items()}
    want, want_cache = op.decode(p, x, cache, pos, fmt)

    q, k_new, v_new = op.decode_qkv(p, x)
    assert q.shape == (b, d)
    assert k_new.shape == v_new.shape == (b, op.kv_heads * hd)
    got_cache = fmt.write_position(cache, fmt.rows(k_new, v_new), pos)
    got = op.decode_finish(p, x, fmt.attend(q, got_cache, pos))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert set(want_cache) == set(got_cache) == set(cache)
    for key, buf in got_cache.items():
        np.testing.assert_array_equal(np.asarray(want_cache[key]),
                                      np.asarray(buf))
        assert (np.asarray(buf) != np.asarray(cache[key])).any()
