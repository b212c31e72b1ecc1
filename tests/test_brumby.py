"""Brumby on the normal path, against the plain reference
(``chipbench/reference/brumby.py``) at a tiny size: seeded random
weights, 2 layers, d 64, 4 query heads and 2 KV heads of 16, MLP width
96, vocabulary 211.

Tolerances.  In float32 both sides multiply in float32 in different
orders (the program blocks the attention form and, decoding, reads a
recurrent state; the reference holds one dense weight matrix a head), so
logits agree to about 1e-5 of their largest: the first positions of a
sequence divide two nearly cancelling sums (``tests/test_retention.py``).
``RTOL`` 2e-4 leaves room and stays 50x under what any change of the
mathematics costs: a dropped QK-norm, a dropped RoPE or a power of 4
each move the logits by more than 1e-2 (asserted below by mutating the
reference), and so does a bfloat16 product.  In bfloat16 the program is
held by the benchmark's own measure, ``logit_gaps``: no generated token
may sit a tenth of the position's logit spread under the reference's
best, where a wrong state lands a whole spread down.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.agreement import logit_gaps, rel_err
from chipbench.reference import brumby as ref
from defer_tpu.models import brumby, brumby_tiny, gpt_tiny, olmoe_tiny
from defer_tpu.models.decoder import (DecoderBlock, RetentionBlock,
                                      decoder_parts)
from defer_tpu.obs import REGISTRY
from defer_tpu.ops import retention
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

VOCAB, SEQ, PLEN, NEW = 211, 24, 8, 10
REF = dict(n_layer=2, n_head=4, n_kv=2, eps=1e-6, theta=1e6)
RTOL = 2e-4


@pytest.fixture(scope="module")
def model():
    graph = brumby_tiny(seq_len=SEQ, vocab=VOCAB)
    return graph, graph.init(jax.random.key(3))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(
        0, VOCAB, (4, SEQ)).astype(np.int32)


# -- the full-sequence graph, and the reference against itself -------------------

def test_full_sequence_logits_match_the_reference(model, ids):
    graph, params = model
    got = jax.jit(graph.apply)(params, jnp.asarray(ids))
    want = ref.logits(params, ids, **REF)
    assert got.shape == (4, SEQ, VOCAB)
    assert rel_err(got, want) < RTOL


def test_the_references_two_forms_agree(model, ids):
    """Inside the reference itself: the attention form and the
    recurrence over the plain symmetric power give the same logits."""
    _, params = model
    want = ref.logits(params, ids, **REF)
    assert rel_err(ref.logits(params, ids, form="recurrent", **REF),
                   want) < RTOL


@pytest.mark.parametrize("state_dtype, least, most", [
    (None, 0.0, 1e-5), (jnp.bfloat16, 1e-3, 0.1)])
def test_the_recurrent_forms_state_is_the_explicit_sum(model, ids,
                                                       state_dtype, least,
                                                       most):
    """The reference's recurrent form ends on what its explicit sum
    says; with the state rounded to bfloat16 after every position (the
    control the chip's limits are set against) it departs by what that
    mantissa gives, in every layer."""
    _, params = model
    want = ref.states(params, ids[:2, :12], **REF)
    got = ref.states(params, ids[:2, :12], form="recurrent",
                     state_dtype=state_dtype, **REF)
    errs = [max(rel_err(s, ws), rel_err(z, wz))
            for (s, z), (ws, wz) in zip(got, want)]
    assert least <= min(errs) and max(errs) < most


@pytest.mark.parametrize("mutation", ["no_qk_norm", "no_rope", "power_4",
                                      "bfloat16_products"])
def test_the_tolerance_tells_a_changed_model_apart(model, ids, mutation):
    """Each departure from the equations moves the reference's logits by
    more than 1e-2 of their largest: 50x the tolerance of the tests
    above, which would therefore fail on any of them."""
    _, params = model
    want = ref.logits(params, ids, **REF)
    if mutation == "bfloat16_products":
        half = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
        got = ref.logits(half, ids, **REF)
    else:
        got = ref.logits(params, ids, **REF, **{
            "no_qk_norm": {"qk_norm": False}, "no_rope": {"use_rope": False},
            "power_4": {"power": 4}}[mutation])
    assert rel_err(got, want) > (2e-3 if mutation == "bfloat16_products"
                                 else 1e-2)


# -- one token against the state ----------------------------------------------------

def _step_logits(graph, params, seqs):
    """Prefill-free decode of ``seqs`` [b, t] through the block's own
    step against the state's format (``RetentionBlock.decode``), a
    position a step: logits [b, t, vocab] and the final states."""
    nodes = graph.nodes
    blocks = [nm for nm in graph.topo_order if nm.startswith("block_")]
    op0 = nodes[blocks[0]].op
    b, t = seqs.shape
    d = nodes[blocks[0]].out_spec.shape[-1]
    fmt = op0.memory_format(d, t, jnp.float32)
    states = {nm: fmt.layer(fmt.zeros(b, 1), 0) for nm in blocks}
    out = []
    for p in range(t):
        x = nodes["embeddings"].op.embed_at(
            params["embeddings"], jnp.asarray(seqs[:, p]), p)
        for nm in blocks:
            x, states[nm] = nodes[nm].op.decode(
                params[nm], x, states[nm], jnp.int32(p), fmt)
        h = nodes["final_ln"].op.apply(params["final_ln"], x)
        out.append(nodes["lm_head"].op.apply(params["lm_head"], h))
    return jnp.stack(out, axis=1), states


def test_decode_steps_match_the_references_full_forward(model, ids):
    """Logits, not tokens: every position decoded through the state
    against the reference's full forward, and the state the steps leave
    against the reference's explicit sum."""
    graph, params = model
    got, states = _step_logits(graph, params, ids[:2, :12])
    assert rel_err(got, ref.logits(params, ids[:2, :12], **REF)) < RTOL
    want = ref.states(params, ids[:2, :12], **REF)
    for i, (s, z) in enumerate(want):
        assert rel_err(retention.dense(states[f"block_{i}"]["S"]), s) < 1e-5
        assert rel_err(retention.dense(states[f"block_{i}"]["z"], -1),
                       z) < 1e-5


@pytest.mark.parametrize("token_chunk", [1, 3])
def test_prefill_then_decode_through_the_ring(model, ids, token_chunk):
    """Prefill, then decode through the ring's state, on 1 stage and on
    2 (where warm-up, the prefill's fill and drain and every chunk's
    overshoot are bubbles): every generated token is the argmax of the
    reference's full forward over the program's own sequence
    (teacher-forced), the logits by the same steps agree, and both
    rings are left with the state of the tokens they took: a bubble
    changed nothing."""
    graph, params = model
    outs, decs = {}, {}
    for n in (1, 2):
        decs[n] = dec = PipelinedDecoder(graph, params, num_stages=n,
                                         microbatch=4 // n, max_len=SEQ)
        outs[n] = out = dec.generate(ids[:, :PLEN], NEW, prefill=True,
                                     token_chunk=token_chunk)
        assert out.shape == (4, PLEN + NEW)
        np.testing.assert_array_equal(out[:, :PLEN], ids[:, :PLEN])
        lg = np.asarray(ref.logits(params, out[:, :-1], lo=PLEN - 1, **REF))
        np.testing.assert_array_equal(out[:, PLEN:], lg.argmax(-1))
        if n == 1:
            got, _ = _step_logits(graph, params, out[:, :-1])
            assert rel_err(got[:, PLEN - 1:], lg) < RTOL
    np.testing.assert_array_equal(outs[1], outs[2])
    # the state each ring is left with is the reference's explicit sum
    # over the tokens its steps took: a bubble (warm-up, a chunk's
    # overshoot, the prefill's fill and drain) added nothing.  Stage s
    # of the 2-stage ring holds block s; its schedule's last step hands
    # stage 0 the last token of group 0, which no later stage reads
    short = ref.states(params, outs[1][:, :-1], **REF)
    whole = ref.states(params, outs[1], **REF)
    for key, axis, at in (("S", -2, 0), ("z", -1, 1)):
        for l in (0, 1):
            one = np.asarray(decs[1].state[key][l])[0, 0]       # [4, ...]
            assert rel_err(retention.dense(one, axis), short[l][at]) < 1e-4
            two = np.asarray(decs[2].state[key][0])[l]  # [group, mb, ...]
            for g in (0, 1):
                want = whole if (l, g) == (0, 0) else short
                assert rel_err(retention.dense(two[g], axis),
                               want[l][at][2 * g:2 * g + 2]) < 1e-4
    # teacher forcing inside the scan instead of the fused prefill
    slow = decs[2].generate(ids[:, :PLEN], NEW, token_chunk=token_chunk)
    np.testing.assert_array_equal(slow, outs[2])


def test_bfloat16_weights_stay_within_the_references_near_ties(model, ids):
    """The type the chip serves: bfloat16 weights and activations, the
    state float32; judged as the benchmark judges its cell."""
    graph, params = model
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    dec = PipelinedDecoder(graph, half, num_stages=2, microbatch=2,
                           max_len=SEQ, compute_dtype=jnp.bfloat16)
    out = dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=2)
    assert dec.state["S"][0].dtype == jnp.float32
    gaps = logit_gaps(half, out, PLEN, {
        "module": "chipbench.reference.brumby", "args": REF})
    assert (gaps <= 0).mean() > 0.8 and gaps.max() < 0.1


def test_updates_are_counted_and_a_bubble_counts_none(model, ids):
    """``decode.retention.updates``: sequences x layers of every *valid*
    decode step; warm-up, overshoot and the prefill sow nothing."""
    graph, params = model
    counter = REGISTRY.counter("decode.retention.updates")
    for n in (1, 2):
        dec = PipelinedDecoder(graph, params, num_stages=n,
                               microbatch=4 // n, max_len=SEQ)
        before = counter.n
        dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
        # positions PLEN .. PLEN+NEW-2 are decoded (the last token needs
        # no step of its own; the first comes from the prefill); on two
        # stages the schedule's last step hands stage 0 the last token
        # of group 0: 2 sequences, 1 layer
        assert counter.n - before == 4 * 2 * (NEW - 1) + (2 if n == 2 else 0)
    assert REGISTRY.gauge("decode.retention.state_bytes").value == \
        dec.num_stages * dec.state_format.state_bytes(dec.microbatch,
                                                      dec.l_max)


# -- the contract ----------------------------------------------------------------------

def test_the_block_declares_its_memory_and_the_ring_asks_for_it(model):
    graph, params = model
    op = graph.nodes["block_0"].op
    assert isinstance(op, RetentionBlock) and isinstance(op, DecoderBlock)
    assert op.memory == "retention"
    assert gpt_tiny().nodes["block_0"].op.memory == "kv_cache"
    parts = decoder_parts(graph, 2, max_len=16)
    assert parts.memory == ("retention",) * 2
    assert parts.geometry == ((4, 2, 16),) * 2
    assert parts.decode_stats == ("retention.updates",)
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    assert isinstance(dec.state_format, retention.RetentionFormat)
    _, state = dec._init_state()
    # what the blocks sow leaves each chunk as an output of its own
    assert set(state) == {"S", "z"}
    assert state["S"][0].shape == (2, 2, 2, 2, 192, 16)  # stage, groups: no scratch
    with pytest.raises(ValueError, match="quantizes cached key and value"):
        PipelinedDecoder(graph, params, num_stages=1, kv_cache="int8")


def test_a_kv_cache_graphs_ring_state_is_what_it_was():
    """A graph whose blocks keep a KV cache holds exactly the keys it
    held before: no retention entry, the scratch group and row there."""
    for graph in (gpt_tiny(), olmoe_tiny()):     # blocks that sow, too
        dec = PipelinedDecoder(graph, graph.init(jax.random.key(0)),
                               num_stages=2, microbatch=2, max_len=12)
        _, state = dec._init_state()
        assert set(state) == {"k", "v"}
        fmt = dec.state_format
        assert state["k"][0].shape == (2, 2 + 1, 2, fmt.kv_heads, 12 + 1,
                                       fmt.head_dim)
        assert set(dec.memory) == {"kv_cache"}


def test_a_published_head_dim_sizes_the_state_and_the_matrices():
    """The family publishes ``head_dim`` beside ``hidden_size``: where
    it is not ``hidden / heads`` the block's matrices, the contract's
    geometry and the ring's state follow the block's own number, and
    the ring still decodes what the plain reference computes."""
    graph = brumby(2, 64, 4, 2, 96, SEQ, vocab=VOCAB, head_dim=8)
    params = graph.init(jax.random.key(3))
    assert params["block_0"]["q"]["w"].shape == (64, 32)
    assert params["block_0"]["proj"]["w"].shape == (32, 64)
    assert params["block_0"]["k"]["w"].shape == (64, 16)
    assert decoder_parts(graph, 1).geometry == ((4, 2, 8),) * 2
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                           max_len=SEQ)
    assert dec.state_format.head_dim == 8
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, 6)).astype(np.int32)
    out = dec.generate(ids, 5, prefill=True)
    gaps = logit_gaps(params, out, 6, {
        "module": "chipbench.reference.brumby",
        "args": dict(REF, head_dim=8)})
    assert gaps.max() <= 0


def test_beam_search_is_refused_for_a_state(model):
    graph, params = model
    with pytest.raises(ValueError, match="beam search re-parents.*retention"):
        PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                         beam_width=2)


def test_two_memory_kinds_are_the_layers_own_but_the_ledger_is_shared(model):
    """A retention layer beside a KV layer is no fault of the contract
    (kinds are reported by layer); what it still refuses is two sets of
    sown statistics, which these two blocks have."""
    import dataclasses
    graph, _ = model
    nodes = dict(graph.nodes)
    nodes["block_1"] = dataclasses.replace(
        nodes["block_1"], op=olmoe_tiny().nodes["block_0"].op)
    mixed = graph.__class__.__new__(graph.__class__)
    mixed.__dict__.update(graph.__dict__)
    mixed.nodes = nodes
    with pytest.raises(ValueError, match="block_1 sows.*one ledger"):
        decoder_parts(mixed, 1)


def test_the_serving_engine_refuses_the_block_by_name(model):
    graph, params = model
    with pytest.raises(TypeError, match=r"block_0 \(BrumbyBlock\)"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


def test_every_leaf_is_an_argument_of_its_own(model):
    """The blocks' leaves — matrices, norms and the decay map alike —
    the embedding, the last norm and the head are arguments of their
    own, each on the stage that holds it; ``reweight`` swaps and checks
    them all."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    assert set(dec._w) == {"blocks", "ends"}
    assert jax.tree.structure(dec._w["blocks"][0]) \
        == jax.tree.structure(params["block_0"])
    assert set(dec._w["ends"]) == {"embeddings", "final_ln", "lm_head"}
    wte = np.asarray(dec._w["ends"]["embeddings"]["wte"])
    assert wte.shape == (2, VOCAB, 64)
    np.testing.assert_array_equal(wte[0], params["embeddings"]["wte"])
    assert not wte[1].any()                     # stage 1 has no embedding
    head = np.asarray(dec._w["ends"]["lm_head"]["w"])
    assert not head[0].any() and head[1].any()
    before = dec.generate(np.zeros((4, 4), np.int32), 4, prefill=True)
    dec.reweight(jax.tree.map(lambda a: a * 1.5, params))
    assert not np.array_equal(
        dec.generate(np.zeros((4, 4), np.int32), 4, prefill=True), before)
    wrong = dict(params, lm_head={"w": params["lm_head"]["w"][:, :-1]})
    with pytest.raises(ValueError, match="reweight: lm_head's leaves"):
        dec.reweight(wrong)
    # the same ends, whatever the family
    for other in (gpt_tiny(), olmoe_tiny()):
        d2 = PipelinedDecoder(other, other.init(jax.random.key(0)),
                              num_stages=1, max_len=8)
        assert set(d2._w["ends"]) == set(dec._w["ends"])


def test_the_published_model_builds_at_its_widths():
    """``Brumby-14B-Base`` as published: shapes only, nothing allocated."""
    graph = brumby(40, 5120, 40, 8, 17408, 32768)
    shapes = jax.eval_shape(graph.init, jax.random.key(0))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 14.7e9 < count < 14.9e9
    block = shapes["block_0"]
    assert block["q"]["w"].shape == (5120, 5120)
    assert block["k"]["w"].shape == (5120, 1024)
    assert block["decay"]["w"].shape == (5120, 8)
    assert block["q_norm"]["scale"].shape == (128,)
    assert block["mlp_down"]["w"].shape == (17408, 5120)
    assert shapes["embeddings"]["wte"].shape == (151936, 5120)
    assert shapes["lm_head"]["w"].shape == (5120, 151936)
    op = graph.nodes["block_0"].op
    spec = graph.nodes["block_0"].out_spec
    # the planner's count: the matrices and 12 D d a KV head a token
    per_token = op.flops((spec,), spec) / spec.shape[0]
    assert per_token == pytest.approx(
        2 * 330.3e6 + 12 * 8704 * 128 * 8, rel=1e-3)
