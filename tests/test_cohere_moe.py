"""The Cohere routed-expert family (models/cohere_moe.py): window layers
on ring buffers beside full layers, grouped queries, sigmoid routing
over all experts with a share of them held, shared experts averaged —
held to the plain reference (chipbench/reference/cohere2_moe.py), on
the ring and off it."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from defer_tpu.models import cohere_moe, cohere_moe_tiny, gpt_tiny
from defer_tpu.models.cohere_moe import (
    FULL_LAYER, WINDOW_LAYER, CohereMoeBlock, rope_interleaved, tie_head)
from defer_tpu.obs import REGISTRY
from defer_tpu.ops.routed import expert_dispatch_held, route_top_k
from defer_tpu.runtime.decode import PipelinedDecoder

ref = importlib.import_module("chipbench.reference.cohere2_moe")

VOCAB, WINDOW = 211, 8
PATTERN = (WINDOW_LAYER,) * 3 + (FULL_LAYER,)
REF_ARGS = dict(n_head=8, n_kv=2, head_dim=8, top_k=4, n_shared=2,
                layer_types=PATTERN, window=WINDOW, eps=1e-5,
                theta=50000.0)


def make(seq_len=32, held=(0, 2), seed=0):
    graph = cohere_moe_tiny(seq_len, VOCAB, experts_held=held)
    params = tie_head(graph.init(jax.random.key(seed)))
    # a sharper router and wider embedding rows: tokens differ at the
    # router, as the benchmark's init_gain makes them
    params = dict(params, embeddings={"wte": params["embeddings"]["wte"] * 50})
    params = tie_head(params)
    for i in range(8):
        blk = dict(params[f"block_{i}"])
        blk["router"] = {"w": blk["router"]["w"] * 4}
        params[f"block_{i}"] = blk
    return graph, params


def ref_logits(params, ids, held=(0, 2), **kw):
    with jax.default_matmul_precision("highest"):
        return ref.logits(params, ids, n_layer=8, held=held,
                          **dict(REF_ARGS, **kw))


@pytest.fixture(scope="module")
def tiny():
    return make()


@pytest.fixture(scope="module")
def prompts():
    return np.random.default_rng(3).integers(0, VOCAB, (4, 12)).astype(
        np.int32)


def gaps(params, seqs, plen):
    """How far the reference's logit of each generated token of ``seqs``
    sits under the reference's best, over the position's spread."""
    lg = np.asarray(ref_logits(params, seqs[:, :-1], lo=plen - 1))
    picked = np.take_along_axis(lg, seqs[:, plen:, None], -1)[..., 0]
    best = lg.max(-1)
    return (best - picked) / (best - lg.mean(-1))


@pytest.mark.parametrize("stages,chunk,prefill", [
    (1, None, True), (1, 3, True), (2, None, True), (2, 2, True),
    (1, 4, False), (2, None, False)])
def test_ring_tokens_are_the_references_argmax(tiny, prompts, stages, chunk,
                                               prefill):
    """Prefill (12 positions: past the window of 8), then 18 decode
    steps: two more wraps of the ring buffers.  Every token the ring
    hands out is the reference's own argmax, teacher-forced on the
    ring's tokens (float32 both)."""
    graph, params = tiny
    dec = PipelinedDecoder(graph, params, num_stages=stages,
                           microbatch=4 // stages, max_len=32)
    out = dec.generate(prompts, 18, prefill=prefill, token_chunk=chunk)
    assert out.shape == (4, 30)
    np.testing.assert_array_equal(out[:, :12], prompts)
    assert float(gaps(params, out, 12).max()) < 1e-4


def test_full_forward_logits_match_the_reference(tiny):
    graph, params = tiny
    ids = np.random.default_rng(5).integers(0, VOCAB, (32,)).astype(np.int32)
    got = np.asarray(jax.jit(graph.apply)(params, ids))
    want = np.asarray(ref_logits(params, ids[None]))[0]
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_a_wrong_window_shows_against_the_reference(tiny):
    """The reference at window 7 or 9 differs from the program at 8 by
    far more than the program differs from the reference at 8."""
    graph, params = tiny
    ids = np.random.default_rng(6).integers(0, VOCAB, (32,)).astype(np.int32)
    got = np.asarray(jax.jit(graph.apply)(params, ids))
    right = np.abs(got - np.asarray(ref_logits(params, ids[None]))[0]).max()
    for w in (WINDOW - 1, WINDOW + 1):
        wrong = np.abs(got - np.asarray(
            ref_logits(params, ids[None], window=w))[0]).max()
        assert wrong > 100 * right


def test_the_eight_shares_and_the_shared_term_once_are_the_uncut_layer():
    """A layer that holds experts [2j, 2j+2) computes the routed sum's
    part that fell to them; the 8 parts and the shared term, once, are
    the layer that holds all 16 — in the program and in the reference."""
    x = jax.random.normal(jax.random.key(1), (2, 12, 64), jnp.float32)

    def block(held):
        return CohereMoeBlock(8, 2, 8, 16, 4, 32, 2, window=WINDOW,
                              experts_held=held)

    whole = block(None)
    spec = jax.ShapeDtypeStruct((12, 64), jnp.float32)
    p = whole.init(jax.random.key(2), (spec,))
    p["router"]["w"] = p["router"]["w"] * 4

    def share_of(p, lo, hi):
        return dict(p, experts={k: v[lo:hi] for k, v in p["experts"].items()})

    def no_experts(p):
        # what every share adds besides its routed part: the stream,
        # the attention and the shared term
        return dict(p, experts={k: jnp.zeros_like(v)
                                for k, v in p["experts"].items()})

    y_whole = whole.apply(p, x)
    common = whole.apply(no_experts(p), x)
    for fwd in (
            lambda held, ps: block(held).apply(ps, x),
            lambda held, ps: ref.block(
                ps, x, n_head=8, n_kv=2, head_dim=8, top_k=4, n_shared=2,
                window=WINDOW, held=held, eps=1e-5, theta=50000.0)[0]):
        parts = sum(fwd((lo, lo + 2), share_of(p, lo, lo + 2)) - common
                    for lo in range(0, 16, 2))
        np.testing.assert_allclose(np.asarray(common + parts),
                                   np.asarray(y_whole), atol=2e-5)
    # and a share is a strict part: some rows fell elsewhere
    assert float(jnp.abs(block((0, 2)).apply(share_of(p, 0, 2), x)
                         - y_whole).max()) > 1e-3


def test_sigmoid_routing_renormalises_over_the_chosen():
    logits = jax.random.normal(jax.random.key(0), (64, 16)) * 3
    eid, w = route_top_k(logits, 4, scoring="sigmoid")
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    s = np.asarray(jax.nn.sigmoid(logits))
    order = np.argsort(-s, axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(eid), -1),
                                  np.sort(order, -1))
    picked = np.take_along_axis(s, np.asarray(eid), -1)
    np.testing.assert_allclose(np.asarray(w),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    # softmax stays what it was: probabilities as they are
    _, p = route_top_k(logits, 4)
    assert float(p.sum(-1).max()) < 1.0
    with pytest.raises(ValueError, match="scoring"):
        route_top_k(logits, 4, scoring="tanh")


def test_the_third_rule_is_a_softmax_over_the_chosen_logits():
    """``softmax_of_chosen`` (Granite 4.0-H): the ``k`` largest logits,
    then a softmax over those alone — the other two rules' experts under
    other weights, and the plain reference's own rule."""
    granite_ref = importlib.import_module(
        "chipbench.reference.granite_hybrid")
    logits = jax.random.normal(jax.random.key(0), (64, 16)) * 3
    eid, w = route_top_k(logits, 4, scoring="softmax_of_chosen")
    order = np.argsort(-np.asarray(logits), axis=-1)[:, :4]
    np.testing.assert_array_equal(np.asarray(eid), order)
    picked = np.take_along_axis(np.asarray(logits), order, -1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(jax.nn.softmax(picked, -1)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    # the other two choose the same experts and weigh them otherwise
    for scoring in ("softmax", "sigmoid"):
        other_eid, other_w = route_top_k(logits, 4, scoring=scoring)
        np.testing.assert_array_equal(np.asarray(other_eid), order)
        assert float(jnp.abs(other_w - w).max()) > 0.05
    want_ids, want_w = granite_ref.route(logits, 4)
    np.testing.assert_array_equal(np.asarray(want_ids), order)
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), rtol=1e-6)


def test_the_programs_blocks_make_the_references_choices(tiny):
    graph, params = tiny
    ids = np.random.default_rng(8).integers(0, VOCAB, (2, 24)).astype(
        np.int32)
    _, want = ref_logits(params, ids, experts=True)
    x = graph.nodes["embeddings"].op.apply(params["embeddings"], ids)
    for i in range(8):
        sown: dict = {}
        x, _k, _v = graph.nodes[f"block_{i}"].op.apply_with_kv(
            params[f"block_{i}"], x, sow=sown)
        got = np.sort(np.asarray(sown["moe.chosen"]).reshape(2, 24, 4), -1)
        assert (got == np.sort(np.asarray(want[i]), -1)).mean() > 0.99
        assert int(sown["moe.assignments"]) == 2 * 24 * 4
        assert 0 < int(sown["moe.held_assignments"]) < 2 * 24 * 4


@pytest.mark.parametrize("pairs_run", [4096, 16])
def test_held_dispatch_computes_held_pairs_only(monkeypatch, pairs_run):
    """Rows that fell to experts the layer does not hold never reach the
    product — in one run, and in runs of 16 pairs (a loop whose trip
    count is the held pairs')."""
    import defer_tpu.ops.routed as routed
    monkeypatch.setattr(routed, "_HELD_RUN", pairs_run)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(24, 8)), jnp.float32)
    eid = jnp.asarray(np.stack([rng.permutation(16)[:4] for _ in range(24)]))
    gate = jnp.asarray(rng.uniform(size=(24, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 8, 8)), jnp.float32)
    seen = []

    def fn(xs, sizes):
        seen.append(xs.shape[0])
        return jax.lax.ragged_dot(xs, w, sizes)

    got, sizes = jax.jit(lambda *a: expert_dispatch_held(
        *a, (5, 8), fn))(x, eid, gate)
    want = np.zeros((24, 8), np.float32)
    for t in range(24):
        for j in range(4):
            e = int(eid[t, j])
            if 5 <= e < 8:
                want[t] += float(gate[t, j]) * np.asarray(x[t] @ w[e - 5])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    assert int(sizes.sum()) == int(((eid >= 5) & (eid < 8)).sum())
    assert max(seen) == min(96, pairs_run)


def test_a_window_block_keeps_window_rows_and_a_full_block_every_position(
        tiny):
    graph, params = tiny
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=32)
    assert len(dec.state_formats) == 4
    lengths = [fmt.buffers(2)["k"].shape[3] for fmt in dec.state_formats]
    assert lengths == [WINDOW + 1] * 3 + [32 + 1]   # and the scratch row
    assert [fmt.window for fmt in dec.state_formats] == [WINDOW] * 3 + [None]
    assert dec.state_format is dec.state_formats[0]
    # a window as long as the positions never wraps: a row a position
    short = PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                             max_len=8)
    assert {fmt.window for fmt in short.state_formats} == {None}
    # gauges: the buffers' own bytes, by kind, scratch included
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=32)
    _a, caches = dec._init_state()
    by_len: dict = {}
    for key in ("k", "v"):
        for buf in caches[key]:
            by_len[buf.shape[4]] = by_len.get(buf.shape[4], 0) + buf.nbytes
    assert REGISTRY.gauge("decode.cache.window_bytes").value \
        == by_len[WINDOW + 1]
    assert REGISTRY.gauge("decode.cache.full_bytes").value == by_len[33]
    assert REGISTRY.gauge("decode.cache.window_positions").value == WINDOW
    assert REGISTRY.gauge("decode.kv_cache.state_bytes").value \
        == sum(by_len.values())
    # every leaf is an argument of its own, the nine norms' scales with
    # the matrices: nothing rides a flat row
    assert REGISTRY.gauge("decode.weights.row_bytes").value == 0
    assert REGISTRY.gauge("decode.weights.own_bytes").value == sum(
        leaf.nbytes for leaf in jax.tree.leaves(params))


def test_a_stage_whose_kinds_do_not_repeat_is_refused(tiny):
    graph, params = tiny
    with pytest.raises(ValueError, match="same kinds of memory in the same "
                                         "order"):
        PipelinedDecoder(graph, params, num_stages=4, microbatch=1,
                         max_len=32, cut=[2, 2, 2, 2])
    # left to the bytes, every stage opens where the pattern does: the
    # full layer closes a stage of three, a window layer is one alone
    dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=1,
                           max_len=32)
    assert [len(b) for b in dec.stage_blocks] == [1, 3, 1, 3]
    # a graph of one kind splits anywhere
    g = gpt_tiny()
    PipelinedDecoder(g, g.init(jax.random.key(0)), num_stages=2,
                     microbatch=1, max_len=16)


def test_the_serving_engine_refuses_the_block(tiny):
    from defer_tpu.serve.engine import ContinuousBatchEngine
    graph, params = tiny
    with pytest.raises((TypeError, ValueError), match="CausalTransformerBlock"
                       "|GPT|gpt"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


def test_counters_add_up_over_a_generation(tiny, prompts):
    graph, params = tiny
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=32)
    names = ("assignments", "held_assignments", "experts_hit", "load_max")
    before = {n: REGISTRY.counter(f"decode.moe.{n}").n for n in names}
    dec.generate(prompts, 9, prefill=True, token_chunk=4)
    got = {n: REGISTRY.counter(f"decode.moe.{n}").n - before[n]
           for n in names}
    # 8 decode steps x 8 layers x 4 rows x 4 experts a token
    assert got["assignments"] == 8 * 8 * 4 * 4
    assert 0 < got["held_assignments"] < got["assignments"] / 2
    assert got["experts_hit"] <= 8 * 8 * 2          # 2 held experts a layer
    assert got["load_max"] <= got["held_assignments"]


@pytest.mark.parametrize("kv_cache,beam", [("int8", 1), ("buffer", 2)])
def test_int8_rows_and_beams_work_over_a_ring_buffer(tiny, prompts, kv_cache,
                                                     beam):
    """Neither is silently wrong over a ring buffer: int8 rows stay
    within quantisation of the float ring's tokens' logits, and a beam
    of 2 scores at least as well as greedy under the reference."""
    graph, params = tiny
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=32, kv_cache=kv_cache, beam_width=beam)
    rows = prompts[:4 // beam]
    out = dec.generate(rows, 14)
    np.testing.assert_array_equal(out[:, :12], rows)
    if kv_cache == "int8":
        assert float(gaps(params, out, 12).max()) < 0.05
        return
    greedy = PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                              max_len=32).generate(prompts[:2], 14)

    def score(seqs):
        lp = jax.nn.log_softmax(ref_logits(params, seqs[:, :-1], lo=11), -1)
        return np.take_along_axis(np.asarray(lp), seqs[:, 12:, None],
                                  -1)[..., 0].sum(-1)

    assert np.all(score(out) >= score(greedy) - 1e-3)


def test_interleaved_rope_turns_pairs():
    x = jax.random.normal(jax.random.key(0), (5, 3, 8))
    got = np.asarray(rope_interleaved(x, jnp.arange(5), 50000.0))
    want = np.asarray(ref._rope_pairs(x.transpose(1, 0, 2)[None], 50000.0))[
        0].transpose(1, 0, 2)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # position 0 turns nothing; norms of pairs are kept
    np.testing.assert_allclose(got[0], np.asarray(x[0]), atol=1e-7)


def test_the_graph_builder_checks_its_layer_types():
    with pytest.raises(ValueError, match="layer type"):
        cohere_moe(4, 64, 8, 2, 8, 16, VOCAB, 16, 4, 32, 2,
                   ("chunked_attention",), 8)
    g = cohere_moe(4, 64, 8, 2, 8, 16, VOCAB, 16, 4, 32, 2, PATTERN, 8)
    assert [g.nodes[f"block_{i}"].op.window for i in range(4)] \
        == [8, 8, 8, None]
    assert g.nodes["block_0"].op.held == (0, 16)
