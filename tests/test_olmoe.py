"""OLMoE on the normal path, against the plain reference
(``chipbench/reference/olmoe.py``) at a tiny size: seeded random float32
weights, 2 layers, d 64, 4 heads of 16, 8 experts of width 32, 2 a token,
vocabulary 211.

Tolerances.  Both sides multiply in float32, in different orders (the
program groups rows by expert, the reference masks and sums over all
experts), so logits agree to about 1e-6 of their largest; ``RTOL`` 1e-4
leaves room and stays 100x under what any change of the mathematics
costs: a dropped QK-norm, RoPE along the wrong axis or renormalised
top-k weights each move the logits by more than 1e-2 (asserted below by
mutating the reference), and so does a bfloat16 product.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import defer_tpu as dt
from chipbench.agreement import rel_err
from chipbench.reference import olmoe as ref
from defer_tpu.graph.ops import MoE
from defer_tpu.models import gpt_tiny, olmoe, olmoe_tiny
from defer_tpu.models.decoder import DecoderBlock, decoder_parts
from defer_tpu.models.olmoe import OlmoeBlock
from defer_tpu.ops.routed import expert_dispatch, route_top_k
from defer_tpu.obs import REGISTRY
from defer_tpu.ops.kv_cache import KVCacheFormat
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

VOCAB, SEQ, PLEN, NEW = 211, 24, 8, 10
REF = dict(n_layer=2, n_head=4, top_k=2, eps=1e-5, theta=10000.0)
RTOL = 1e-4
COUNTERS = ("decode.moe.assignments", "decode.moe.experts_hit",
            "decode.moe.load_max")


@pytest.fixture(scope="module")
def model():
    graph = olmoe_tiny(seq_len=SEQ, vocab=VOCAB)
    return graph, graph.init(jax.random.key(3))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(
        0, VOCAB, (4, SEQ)).astype(np.int32)


# -- the full-sequence graph ----------------------------------------------------

def test_full_sequence_logits_match_the_reference(model, ids):
    graph, params = model
    got = jax.jit(graph.apply)(params, jnp.asarray(ids))
    want = ref.logits(params, ids, **REF)
    assert got.shape == (4, SEQ, VOCAB)
    assert rel_err(got, want) < RTOL


_ROPE = ref._rope


def _rope_over_the_heads(x, theta):
    """RoPE with the position read off the head axis: the wrong axis."""
    return _ROPE(x.transpose(0, 2, 1, 3), theta).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("mutation", ["no_qk_norm", "renormalised_top_k",
                                      "rope_on_the_wrong_axis",
                                      "bfloat16_products"])
def test_the_tolerance_tells_a_changed_model_apart(model, ids, mutation,
                                                   monkeypatch):
    """Each departure from the equations moves the reference's logits by
    more than 1e-2 of their largest: 100x the tolerance of the test
    above, which would therefore fail on any of them."""
    graph, params = model
    want = ref.logits(params, ids, **REF)
    if mutation == "no_qk_norm":
        got = ref.logits(params, ids, **REF, qk_norm=False)
    elif mutation == "renormalised_top_k":
        got = ref.logits(params, ids, **REF, norm_topk_prob=True)
    elif mutation == "rope_on_the_wrong_axis":
        monkeypatch.setattr(ref, "_rope", _rope_over_the_heads)
        with jax.disable_jit():
            got = ref.logits(params, ids, **REF)
    else:
        bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        got = jax.jit(graph.apply)(bf16, jnp.asarray(ids))
    assert rel_err(got, want) > 1e-2


def test_the_graph_rides_the_stage_pipeline(model, ids):
    """``Defer.run`` cuts the full-sequence graph into two stage programs
    (``SpmdPipeline``) and gives the single program's logits."""
    graph, params = model
    defer = dt.Defer(config=dt.DeferConfig(microbatch=1, chunk=2))
    x = ids[:, None, :]                               # [4, mb=1, t]
    out = defer.run(graph, params, x, num_stages=2)
    want = np.asarray(ref.logits(params, ids, **REF))
    assert rel_err(out[:, 0], want) < RTOL


# -- the expert dispatch -----------------------------------------------------------

def _mask_and_sum(x, eid, gate, w1, w2):
    """Every expert on every row, masked: the form ``ops.MoE`` used."""
    out = jnp.zeros((x.shape[0], w2.shape[-1]), x.dtype)
    for e in range(w1.shape[0]):
        weight = jnp.sum(jnp.where(eid == e, gate, 0.0), axis=-1)
        out = out + weight[:, None] * (jnp.tanh(x @ w1[e]) @ w2[e])
    return out


@pytest.mark.parametrize("routing", ["one_expert_gets_every_row",
                                     "some_experts_get_none", "random"])
def test_dispatch_equals_mask_and_sum(routing):
    rng = np.random.default_rng(11)
    t, d, h, e, k = 12, 16, 8, 6, 2
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((e, d, h)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((e, h, d)), jnp.float32)
    gate = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    if routing == "one_expert_gets_every_row":
        eid = np.stack([np.full(t, 4), np.arange(t) % 3], axis=1)
    elif routing == "some_experts_get_none":
        eid = np.stack([np.full(t, 1), np.full(t, 5)], axis=1)
    else:
        eid = np.stack([rng.permutation(e)[:k] for _ in range(t)])
    eid = jnp.asarray(eid, jnp.int32)

    def experts(xs, sizes, es):
        assert xs.shape == (t * k, d) and es.shape == (t * k,)
        hid = jnp.tanh(jax.lax.ragged_dot(xs, w1, sizes))
        return jax.lax.ragged_dot(hid, w2, sizes)

    got, sizes = jax.jit(
        lambda x, eid, gate: expert_dispatch(x, eid, gate, e, experts)
    )(x, eid, gate)
    np.testing.assert_array_equal(
        np.asarray(sizes), np.bincount(np.asarray(eid).ravel(), minlength=e))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_mask_and_sum(x, eid, gate, w1, w2)),
                               rtol=2e-5, atol=2e-5)


def test_routing_is_a_float32_softmax_and_keeps_the_probabilities():
    logits = jnp.asarray([[2.0, 0.0, 1.0, 3.0]], jnp.bfloat16)
    eid, p = route_top_k(logits, 2)
    soft = np.asarray(jax.nn.softmax(logits.astype(jnp.float32)))[0]
    assert p.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(eid), [[3, 0]])
    np.testing.assert_allclose(np.asarray(p)[0], soft[[3, 0]], rtol=1e-6)
    assert float(p.sum()) < 1.0                      # not renormalised


def test_switch_moe_is_the_top_1_case_of_the_dispatch():
    """``ops.MoE`` (top-1, GELU, biases) through the shared dispatch
    equals evaluating every expert and masking."""
    op = MoE(num_experts=4, hidden=16)
    spec = jax.ShapeDtypeStruct((6, 8), jnp.float32)
    params = op.init(jax.random.key(0), (spec,))
    params["fc1"]["b"] = params["fc1"]["b"] + 0.1
    params["fc2"]["b"] = params["fc2"]["b"] - 0.2
    x = jax.random.normal(jax.random.key(1), (3, 6, 8))
    eid, pe = op.route(params, x)
    every = jnp.stack([op.expert_fn(params, x, jnp.int32(e))
                       for e in range(4)], axis=2)          # [b, t, E, d]
    sel = jax.nn.one_hot(eid, 4)
    want = x + (every * sel[..., None]).sum(2) * pe[..., None]
    np.testing.assert_allclose(np.asarray(op.apply(params, x)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


# -- one token against the cache -----------------------------------------------

def _step_logits(graph, params, seqs):
    """Prefill-free decode of ``seqs`` [b, t] through the block's own two
    halves around the cache's format (``DecoderBlock.decode``), a
    position a step: logits [b, t, vocab]."""
    nodes = graph.nodes
    blocks = [nm for nm in graph.topo_order if nm.startswith("block_")]
    op0 = nodes[blocks[0]].op
    b, t = seqs.shape
    d = nodes[blocks[0]].out_spec.shape[-1]
    fmt = KVCacheFormat(op0.kv_heads, d // op0.num_heads, t, jnp.float32)
    caches = {nm: fmt.layer(fmt.zeros(b, 1), 0) for nm in blocks}
    out = []
    for p in range(t):
        x = nodes["embeddings"].op.embed_at(
            params["embeddings"], jnp.asarray(seqs[:, p]), p)
        for nm in blocks:
            x, caches[nm] = nodes[nm].op.decode(
                params[nm], x, caches[nm], jnp.int32(p), fmt)
        h = nodes["final_ln"].op.apply(params["final_ln"], x)
        out.append(nodes["lm_head"].op.apply(params["lm_head"], h))
    return jnp.stack(out, axis=1)


def test_decode_steps_match_the_references_full_forward(model, ids):
    """Logits, not tokens: every position decoded through the cache
    (rotated keys cached, ``pos`` through ``decode_qkv``) against the
    reference's full forward."""
    graph, params = model
    got = _step_logits(graph, params, ids[:2, :12])
    want = ref.logits(params, ids[:2, :12], **REF)
    assert rel_err(got, want) < RTOL


@pytest.mark.parametrize("num_stages", [1, 2])
@pytest.mark.parametrize("token_chunk", [1, 3])
def test_prefill_then_decode_through_the_ring(model, ids, num_stages,
                                              token_chunk):
    """Prefill, then decode through the ring's caches: every generated
    token is the argmax of the reference's full forward over the
    program's own sequence (teacher-forced), with the reference's margin
    over its runner-up far above what float32 reordering moves."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=4 // num_stages, max_len=SEQ)
    out = dec.generate(ids[:, :PLEN], NEW, prefill=True,
                       token_chunk=token_chunk)
    assert out.shape == (4, PLEN + NEW)
    np.testing.assert_array_equal(out[:, :PLEN], ids[:, :PLEN])
    lg = np.asarray(ref.logits(params, out[:, :-1], lo=PLEN - 1, **REF))
    np.testing.assert_array_equal(out[:, PLEN:], lg.argmax(-1))
    # and the logits themselves, by the same steps the ring runs
    assert rel_err(_step_logits(graph, params, out[:, :-1])[:, PLEN - 1:],
                   lg) < RTOL
    # teacher forcing inside the scan instead of the fused prefill
    slow = dec.generate(ids[:, :PLEN], NEW, token_chunk=token_chunk)
    np.testing.assert_array_equal(slow, out)


def test_the_int8_cache_serves_the_same_blocks(model, ids):
    """Every cache type the ring has: with int8 rows the tokens stay
    within the reference's near-ties (a share of the logit spread)."""
    from chipbench.agreement import logit_gaps
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ, kv_cache="int8")
    out = dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=2)
    gaps = logit_gaps(params, out, PLEN, {
        "module": "chipbench.reference.olmoe", "args": REF})
    # int8 rows at d 64 are coarse: most tokens are the reference's
    # argmax and none is a whole spread down, where a wrong row lands
    assert (gaps <= 0).mean() > 0.8 and gaps.max() < 0.5


def test_the_ring_holds_expert_leaves_as_arguments_of_their_own(model):
    """The experts are arguments of their own, a leaf a local block,
    stage-sharded — as every other leaf is, the norms' scales with the
    matrices; ``reweight`` swaps all of them and checks all of them."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    assert set(dec._w) == {"blocks", "ends"}
    assert len(dec._w["blocks"]) == 1
    own = dec._w["blocks"][0]["experts"]
    assert {k: v.shape for k, v in own.items()} == {
        "gate": (2, 8, 64, 32), "up": (2, 8, 64, 32),
        "down": (2, 8, 32, 64)}
    np.testing.assert_array_equal(np.asarray(own["up"][1]),
                                  np.asarray(params["block_1"]["experts"]["up"]))
    # a block's four scales, each a stage's own
    for key in ("ln1", "q_norm", "k_norm", "ln2"):
        (scale,) = jax.tree.leaves(dec._w["blocks"][0][key])
        assert scale.shape == (2,) + jax.tree.leaves(
            params["block_0"][key])[0].shape
    before = dec.generate(np.zeros((4, 4), np.int32), 4)
    other = graph.init(jax.random.key(99))
    dec.reweight(other)
    assert not np.array_equal(dec.generate(np.zeros((4, 4), np.int32), 4),
                              before)
    dec.reweight(params)
    np.testing.assert_array_equal(
        dec.generate(np.zeros((4, 4), np.int32), 4), before)
    bad = jax.tree.map(lambda a: a, params)
    bad["block_0"] = dict(bad["block_0"], experts=jax.tree.map(
        lambda a: a[:4], bad["block_0"]["experts"]))
    with pytest.raises(ValueError, match="reweight"):
        dec.reweight(bad)


def _nbytes(tree):
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


@pytest.mark.parametrize("layers,num_stages", [(2, 1), (2, 2), (3, 2)],
                         ids=["stages1", "stages2", "uneven"])
def test_olmoe_weights_are_arguments_of_their_own(layers, num_stages):
    """Every leaf of OLMoE's nodes is a stage-sharded argument of its
    own in its own shape, the norms' scales with the matrices, each end
    on the stage that holds it, and the gauges say so."""
    graph = olmoe(layers, 64, 4, SEQ, vocab=VOCAB, num_experts=8,
                  experts_per_tok=2, expert_hidden=32)
    params = graph.init(jax.random.key(4))
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=2, max_len=SEQ)
    n, last = num_stages, num_stages - 1
    assert set(dec._w) == {"blocks", "ends"}
    assert len(dec._w["blocks"]) == dec.l_max == -(-layers // n)
    for tree in dec._w["blocks"]:
        assert jax.tree.structure(tree) \
            == jax.tree.structure(params["block_0"])
    assert set(dec._w["ends"]) == {"embeddings", "final_ln", "lm_head"}
    for leaf in jax.tree.leaves(dec._w):
        assert leaf.shape[0] == n
    # each end on the stage that holds it, zeros elsewhere
    wte = np.asarray(dec._w["ends"]["embeddings"]["wte"])
    head = np.asarray(dec._w["ends"]["lm_head"]["w"])
    (norm,) = map(np.asarray, jax.tree.leaves(dec._w["ends"]["final_ln"]))
    np.testing.assert_array_equal(wte[0], params["embeddings"]["wte"])
    np.testing.assert_array_equal(head[last], params["lm_head"]["w"])
    np.testing.assert_array_equal(
        norm[last], jax.tree.leaves(params["final_ln"])[0])
    assert not wte[1:].any() and not head[:last].any() \
        and not norm[:last].any()
    # a stage with fewer blocks than the fullest holds zeros
    for s, blocks in enumerate(dec.stage_blocks):
        for l in range(dec.l_max):
            for key in ("q", "router", "ln1"):
                (w,) = jax.tree.leaves(dec._w["blocks"][l][key])
                w = np.asarray(w[s])
                if l < len(blocks):
                    np.testing.assert_array_equal(w, np.asarray(
                        jax.tree.leaves(params[blocks[l]][key])[0]))
                else:
                    assert not w.any()
    assert REGISTRY.gauge("decode.weights.row_bytes").value == 0
    assert REGISTRY.gauge("decode.weights.own_bytes").value \
        == _nbytes(params)


def test_an_uneven_split_pads_the_leaves_of_the_shorter_stage(ids):
    """Three layers over two stages (2 + 1): the second local block's
    leaves are [2, ...] with zeros on the stage that has none, and the
    tokens are the one-stage decoder's."""
    graph = olmoe(3, 64, 4, SEQ, vocab=VOCAB, num_experts=8,
                  experts_per_tok=2, expert_hidden=32)
    params = graph.init(jax.random.key(4))
    two = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    assert [len(b) for b in two.stage_blocks] == [2, 1]
    second = two._w["blocks"][1]["experts"]["down"]
    assert second.shape == (2, 8, 32, 64) and not np.asarray(second[1]).any()
    (scale,) = jax.tree.leaves(two._w["blocks"][1]["ln1"])
    assert scale.shape == (2, 64) and not np.asarray(scale[1]).any()
    one = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    np.testing.assert_array_equal(
        two.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=2),
        one.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=2))


#: recorded on the parent commit (169e9f5, where every matrix but the
#: experts' rode the flat row): ``olmoe_tiny(seq_len=24, vocab=211)``, key
#: 3, the four seeded prompts' first 8 tokens, 16 greedy tokens, prefill,
#: token_chunk 2; the same on 1 and 2 stages.
PARENT_OLMOE_TOKENS_SHA = {"float32": "95572485a40a23a2",
                           "bfloat16": "e2ea0d15ef1dbda0"}


@pytest.mark.parametrize("num_stages", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_olmoe_tiny_decodes_as_on_the_parent(model, ids, dtype, num_stages):
    """A leaf handed over as an argument of its own is cast as the row's
    leaves are and multiplied as before: the tokens are the parent
    commit's, bit for bit, in both compute types."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=4 // num_stages, max_len=SEQ,
                           compute_dtype=jnp.dtype(dtype))
    toks = dec.generate(ids[:, :PLEN], SEQ - PLEN, prefill=True,
                        token_chunk=2)
    assert hashlib.sha256(str(toks.tolist()).encode()).hexdigest()[:16] \
        == PARENT_OLMOE_TOKENS_SHA[dtype]


# -- the counters ----------------------------------------------------------------

def _ring_steps(n, start, t_tok, max_len):
    """(stage, group, position) of every live step the ring runs to
    decode positions ``start+1 .. t_tok-1`` (``PipelinedDecoder``'s
    schedule: stage ``s`` serves group ``(t-s) % n`` at ``start +
    (t-s)//n``; the last fill's leading stages run one position that
    never reaches the head)."""
    num_steps = (n - 1) + n * (t_tok - 2 - start) + (n - 1) + 1
    for t in range(num_steps):
        for s in range(n):
            rel = t - s
            if rel >= 0 and start + rel // n < max_len:
                yield s, rel % n, start + rel // n


@pytest.mark.parametrize("num_stages", [1, 2])
def test_moe_counters_equal_the_references_chosen_experts(model, ids,
                                                          num_stages):
    graph, params = model
    mb = 4 // num_stages
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=mb, max_len=SEQ)
    before = [REGISTRY.counter(c).n for c in COUNTERS]
    hist = REGISTRY.histogram("decode.moe_stats_s")
    fetches = hist.count
    out = dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=3)
    got = [REGISTRY.counter(c).n - b for c, b in zip(COUNTERS, before)]
    assert hist.count == fetches + 1          # one fetch a generation
    _, chosen = ref.logits(params, out, **REF, experts=True)
    chosen = np.asarray(chosen)                       # [layer, b, t, k]
    want = [0, 0, 0]
    for s, g, pos in _ring_steps(num_stages, PLEN, PLEN + NEW, SEQ):
        for nm in dec.stage_blocks[s]:
            layer = int(nm.split("_")[1])
            picks = chosen[layer, g * mb:(g + 1) * mb, pos].ravel()
            sizes = np.bincount(picks, minlength=8)
            want[0] += picks.size
            want[1] += int((sizes > 0).sum())
            want[2] += int(sizes.max())
    assert got == want
    assert got[0] == 2 * mb * sum(
        len(dec.stage_blocks[s])
        for s, _g, _p in _ring_steps(num_stages, PLEN, PLEN + NEW, SEQ))


@pytest.mark.parametrize("num_stages", [1, 2])
def test_counters_are_fetched_when_the_caller_stops_a_generation(model, ids,
                                                                 num_stages):
    """The counters hold what was sown up to the last chunk handed over,
    and nothing of the chunk that ran ahead of the stop: a chunk's sums
    come to the host with its ids, so the stop waits for nothing."""
    graph, params = model
    mb = 4 // num_stages
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=mb, max_len=SEQ)
    want = dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=3)
    before = REGISTRY.counter(COUNTERS[0]).n
    discarded = REGISTRY.counter("decode.ahead.discarded").n
    syncs = REGISTRY.histogram("decode.sync_s").count

    class Stop(Exception):
        pass

    def on_tokens(lo, hi, toks, rows):
        if hi >= PLEN + 4:
            raise Stop

    posted, post = [], dec._post_stats
    dec._post_stats = lambda sums: (posted.append(sums), post(sums))
    with pytest.raises(Stop):
        dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=3,
                     on_tokens=on_tokens)
    del dec._post_stats
    # summed on the host as the chunks were read: no device array is
    # left for the generation's end to wait for
    assert len(posted) == 1 and type(posted[0]) is np.ndarray
    assert REGISTRY.counter("decode.ahead.discarded").n == discarded + 1
    read = REGISTRY.histogram("decode.sync_s").count - syncs
    # the live (stage, step)s of the chunks that were read, of 3 x
    # num_stages steps each: x the stage's layers x mb rows x 2 choices
    steps = read * 3 * num_stages
    live = sum(len(dec.stage_blocks[s])
               for t in range(steps) for s in range(num_stages) if t >= s)
    assert read == (1 if num_stages == 1 else 2)
    assert REGISTRY.counter(COUNTERS[0]).n - before == live * mb * 2
    # the decoder is whole: the next generation waits for the chunk that
    # was left running, and gives the tokens it gave before
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=3), want)


# -- the block interface, and who refuses it -----------------------------------------

def test_blocks_of_both_families_meet_the_rings_interface(model):
    """The interface lives in ``models/decoder.py`` (neither family's
    file), the cache's half of a step in ``ops/kv_cache.py``: a block
    has the first and nothing of the second."""
    import inspect
    import defer_tpu.models.olmoe as olmoe_module
    graph, _ = model
    for op in (graph.nodes["block_0"].op, gpt_tiny().nodes["block_0"].op):
        assert isinstance(op, DecoderBlock)
        for name in ("apply_with_kv", "decode_qkv", "decode_finish",
                     "decode"):
            assert callable(getattr(op, name))
        for name in ("write_row", "quantize_row", "cache_rows",
                     "cache_attention", "decode_attend"):
            assert not hasattr(op, name)
    for name in ("rows", "write_position", "write_slots", "write_prefix",
                 "reparent", "attend"):
        assert callable(getattr(KVCacheFormat, name))
    # what a block declares to its holder: its statistics and its
    # memory, nothing about where its weights go
    assert {a for a, v in vars(DecoderBlock).items()
            if not a.startswith("_") and not callable(v)
            and not isinstance(v, property)} \
        == {"decode_stats", "memory", "window"}
    assert gpt_tiny().nodes["block_0"].op.decode_stats == ()
    assert DecoderBlock.__module__ == "defer_tpu.models.decoder"
    assert ".gpt" not in inspect.getsource(olmoe_module)    # no sibling


def _graph_with(graph, **ops):
    """``graph`` with the ops of the named nodes replaced (None: the
    node removed)."""
    import dataclasses
    nodes = dict(graph.nodes)
    for nm, op in ops.items():
        if op is None:
            del nodes[nm]
        else:
            nodes[nm] = dataclasses.replace(nodes[nm], op=op)
    broken = graph.__class__.__new__(graph.__class__)
    broken.__dict__.update(graph.__dict__)
    broken.nodes = nodes
    return broken


def _broken_graphs():
    import dataclasses
    from defer_tpu.models import bert_tiny
    graph = gpt_tiny()
    op = graph.nodes["block_2"].op
    return {
        "missing": (_graph_with(graph, final_ln=None), ValueError,
                    "missing 'final_ln'"),
        "foreign": (_graph_with(graph,
                                block_1=bert_tiny().nodes["block_0"].op),
                    TypeError, "block_1.*not a DecoderBlock"),
        # head geometry is a layer's own; the sown statistics are not
        "mixed": (_graph_with(graph, block_2=type(
            "Sowing", (type(op),), {"decode_stats": ("odd",)})(**{
                f.name: getattr(op, f.name)
                for f in dataclasses.fields(op)})), ValueError,
            "block_2 sows.*one ledger"),
    }


@pytest.mark.parametrize("fault", ["missing", "foreign", "mixed"])
@pytest.mark.parametrize("build", [
    lambda g, p: PipelinedDecoder(g, p, num_stages=2),
    lambda g, p: ContinuousBatchEngine(g, p, num_stages=2, width=2),
    lambda g, p: decoder_parts(g, 2),
], ids=["ring", "engine", "contract"])
def test_both_engines_refuse_a_graph_outside_the_contract(build, fault):
    """One function checks a graph for both constructors: a missing
    node, a foreign block and mixed sown statistics are refused by each
    in the same words, before anything is placed on a device."""
    broken, error, words = _broken_graphs()[fault]
    params = gpt_tiny().init(jax.random.key(0))
    with pytest.raises(error, match=words):
        build(broken, params)


def test_the_contract_hands_back_a_graphs_parts(model):
    graph, _ = model
    parts = decoder_parts(graph, 2, max_len=16)
    assert parts.block_names == ("block_0", "block_1")
    assert parts.stage_blocks == [["block_0"], ["block_1"]]
    assert (parts.d_model, parts.vocab, parts.max_len) == (64, VOCAB, 16)
    assert parts.geometry == ((4, 4, 16),) * 2
    assert parts.memory == ("kv_cache",) * 2
    assert parts.decode_stats == OlmoeBlock.decode_stats
    assert parts.embed_op is graph.nodes["embeddings"].op
    assert decoder_parts(graph, 1).max_len == SEQ
    with pytest.raises(ValueError, match="max_len"):
        decoder_parts(graph, 1, max_len=SEQ + 1)
    with pytest.raises(ValueError, match="cannot fill"):
        decoder_parts(graph, 3)


def test_the_serving_engine_refuses_the_block_by_name(model):
    """The engine adds learned positions and decodes with no position:
    it must refuse this family at construction, never answer wrongly."""
    graph, params = model
    with pytest.raises(TypeError, match=r"block_0 \(OlmoeBlock\)"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


# -- the GPT family through the changed interface ---------------------------------

#: recorded on the parent commit (d5480a9): gpt_tiny(seq_len=32), key 0,
#: 8 prompts ``arange(40).reshape(8, 5) % 97``, 8 greedy tokens,
#: prefill, token_chunk 2 — and the sha256 of the ring's lowered decode
#: program for the same decoder.  A PR that changes the ring's program
#: on purpose records them anew and says so.  PR 29 did: the attention
#: became the kernel ``kv_attend`` and the ring's row write, under a
#: lane row, the kernel ``kv_write_rows`` (``e8dcb192e737955e`` /
#: ``aadd22d3adc8d6a3`` before it).  PR 32 did: the family's nodes name
#: every leaf a stage-sharded argument of its own,
#: so the program takes a tree where it cut leaves out of the flat row
#: (``843764f96d5a7620`` / ``081b76872aa207eb`` before it); the tokens
#: are PR 26's still.  PR 44 did: every leaf of every node is such an
#: argument, ``final_ln``'s with the rest, and the ring holds no flat
#: row (``4c8596d40d6c1428`` / ``7ddd12dc4515bd7c`` before it); the
#: tokens are PR 26's still.  PR 54 did: under a lane row a layer's row
#: write rides the attention's block, one kernel ``kv_step`` where
#: ``kv_write_rows`` twice and ``kv_attend`` stood (``1c5e166cd6dcff82``
#: / ``dcf5442ff4e733bd`` before it); the tokens are PR 26's still.
PARENT_TOKENS_SHA = "0fef1cc65e752cd8"
PARENT_DECODE_SHA = {1: "415f9fbfbdf516ad", 2: "f6dd70ad3a5dd88d"}


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("num_stages", [1, 2])
def test_gpt_tiny_decodes_as_on_the_parent(num_stages):
    """``decode_qkv`` takes a position and ``decode_finish`` a ``sow``,
    and the cache's half of a step lives in ``ops/kv_cache.py``: the
    GPT family ignores the first two, and its ring still lowers to the
    text recorded (PR 54's) and gives d5480a9's tokens, bit for bit."""
    graph = gpt_tiny(seq_len=32)
    params = graph.init(jax.random.key(0))
    n, mb = num_stages, 8 // num_stages
    dec = PipelinedDecoder(graph, params, num_stages=n, microbatch=mb,
                           max_len=32)
    a, caches = dec._init_state()
    num_steps, chunk = dec._schedule(5 + 8, 5, 2)
    lowered = dec._get_decode_fn(chunk, False, None).lower(
        dec._w, jnp.zeros((n, mb, 5), jnp.int32), jnp.int32(5),
        jnp.int32(0), jnp.int32(num_steps), jnp.uint32(0), jnp.float32(0.),
        jnp.zeros((n, mb), jnp.int32), jnp.int32(5), jnp.int32(5), a, caches)
    assert _sha(lowered.as_text()) == PARENT_DECODE_SHA[n]
    toks = dec.generate(np.arange(40).reshape(8, 5) % 97, 8, prefill=True,
                        token_chunk=2)
    assert _sha(str(toks.tolist())) == PARENT_TOKENS_SHA


def test_the_published_model_builds_at_its_widths():
    graph = olmoe(16, 2048, 16, 4096)
    spec = graph.nodes["block_0"].param_spec
    assert spec["experts"]["gate"].shape == (64, 2048, 1024)
    assert spec["experts"]["down"].shape == (64, 1024, 2048)
    assert spec["router"]["w"].shape == (2048, 64)
    assert "bias" not in spec["ln1"] and set(spec["q"]) == {"w"}
    assert set(graph.nodes["embeddings"].param_spec) == {"wte"}
    assert set(graph.nodes["lm_head"].param_spec) == {"w"}
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        [node.param_spec for node in graph.nodes.values()]))
    assert round(n / 1e9, 2) == 6.92
