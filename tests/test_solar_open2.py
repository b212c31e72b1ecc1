"""Solar-Open2 on the normal path, against the plain reference
(``chipbench/reference/solar_open2.py``) at a tiny size: seeded random
weights, two periods of ``gqa kda kda kda``, d 64, 4 query heads on 2 KV
heads of 16, 4 KDA heads of 16 under chunks of 8, 4 taps, 2 of 16
experts of 32 a token of which 4 are held, one shared, vocabulary 211 —
a graph whose layers keep two kinds of memory, one of them a state that
its own write reads.

Tolerances.  In float32 both sides multiply in float32 in different
orders (the program's prefill is the chunked WY form and its step a
kernel over a folded layout, the reference the recurrence token by
token; the program sorts a routed layer's rows by expert, the reference
loops over the experts with a mask), so logits agree to about 1e-5 of
their largest.  ``RTOL`` 2e-4 leaves room and stays 50x under what a
change of the mathematics costs (the reference's switches: asserted
below by mutating the reference).  Tokens are held by the benchmark's
own measure, ``logit_gaps``: in float32 no generated token may sit
under the reference's best at all.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.agreement import logit_gaps, rel_err
from chipbench.reference import solar_open2 as ref
from defer_tpu.models import solar_open2, solar_open2_tiny
from defer_tpu.models.decoder import (DecoderBlock, DeltaRuleBlock,
                                      StateSpaceBlock, decoder_parts)
from defer_tpu.models.solar_open2 import SolarAttentionBlock, SolarKdaBlock
from defer_tpu.obs import REGISTRY
from defer_tpu.ops import conv_window, delta_rule, routed
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

VOCAB, SEQ, PLEN, NEW = 211, 32, 11, 9
REF = dict(gqa_layers=(0, 4), n_head=4, n_kv=2, head_dim=16, kda_heads=4,
           kda_head_dim=16, top_k=2, routed_scale=1.0, held=(0, 4),
           eps=1e-5)
REF_CFG = {"module": "chipbench.reference.solar_open2", "args": REF}
RTOL = 2e-4
KINDS = ("kv_cache", "delta_rule", "delta_rule", "delta_rule") * 2
KDA = (1, 2, 3, 5, 6, 7)
STATS = ("moe.assignments", "moe.held_assignments", "moe.experts_hit",
         "moe.load_max", "delta.updates")


def _rounded(params, dtype):
    """``params`` with every floating leaf rounded to ``dtype`` and held
    in float32: what a bfloat16 checkpoint gives both sides."""
    return jax.tree.map(lambda a: a.astype(dtype).astype(jnp.float32)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a,
                        params)


def _turning_bias(params, columns=16):
    """A seeded bias of the initialiser's spread turns too few choices
    for a test to see: this one turns about a choice in five."""
    for i in range(8):
        params[f"block_{i}"]["router"]["bias"] = 0.05 * jax.random.normal(
            jax.random.key(100 + i), (columns,), jnp.float32)
    return params


@pytest.fixture(scope="module")
def model():
    graph = solar_open2_tiny(seq_len=SEQ, vocab=VOCAB)
    return graph, _turning_bias(graph.init(jax.random.key(3)))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(
        0, VOCAB, (4, SEQ)).astype(np.int32)


@pytest.fixture(scope="module")
def generated(model, ids):
    """One stage, fused prefill, one chunk: the tokens every other way
    of running the ring must give, and the decoder that made them."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    return dec.generate(ids[:, :PLEN], NEW, prefill=True), dec


# -- the full-sequence graph, and the reference against itself -------------------

@pytest.mark.parametrize("weights", ["float32", "bfloat16"])
def test_full_sequence_logits_match_the_reference(model, ids, weights):
    """Seeded float32 weights, and the same rounded to bfloat16 (both
    sides then compute in float32 on what a bfloat16 checkpoint
    holds)."""
    graph, params = model
    if weights == "bfloat16":
        params = _rounded(params, jnp.bfloat16)
    got = jax.jit(graph.apply)(params, jnp.asarray(ids))
    want = ref.logits(params, ids, **REF)
    assert got.shape == (4, SEQ, VOCAB)
    assert rel_err(got, want) < RTOL


@pytest.mark.parametrize("control", [
    {"decay_a_head": True}, {"beta_scale": 1.0}, {"delta_reads": False},
    {"conv_silu": False}, {"qk_l2norm": False}, {"out_gate": False},
    {"gqa_theta": 10000.0}, {"gqa_gate": False}, {"bias_weighs": True},
    {"state_dtype": jnp.bfloat16}, {"router_dtype": jnp.bfloat16},
    {"window_shift": 1}],
    ids=lambda c: next(iter(c)))
def test_the_tolerance_tells_each_control_apart(model, ids, generated,
                                                control):
    """Every switch of the reference moves what the program is held to
    by far more than the tolerance: the window by its own comparison
    (the logits do not read it), the others by the logits."""
    _, params = model
    if "window_shift" in control:
        out, dec = generated
        off = ref.states(params, out[:, :-1], **REF, **control)
        got = conv_window.dense_window(dec.state["conv"][1][0, 0])
        assert rel_err(got, off[1][1]) > 0.1
        return
    moved = rel_err(ref.logits(params, ids, **{**REF, **control}),
                    ref.logits(params, ids, **REF))
    # rounding the float32-stated sums to bfloat16 costs least
    assert moved > (5 * RTOL if "dtype" in next(iter(control)) else 1e-2)


def test_the_references_state_is_the_recurrences(model):
    """``delta_rule`` of a sequence cut and continued is the whole
    sequence's, and its write reads the state: the same unit key twice
    at ``beta`` 1 under no decay leaves the second value there."""
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 9, 4, 16)), jnp.float32)
               for _ in range(3))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.asarray(rng.uniform(0.01, 1.0, (2, 9, 4, 16)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 1.9, (2, 9, 4)), jnp.float32)
    o, s = ref.delta_rule(q, k, v, g, beta)
    want_o, want_s = delta_rule.prefill_reference(q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=1e-5)
    key = jnp.zeros((1, 2, 1, 8)).at[..., 0].set(1.0)
    vals = jnp.stack([jnp.full((1, 1, 8), 3.0), jnp.full((1, 1, 8), 5.0)], 1)
    zero, one = jnp.zeros((1, 2, 1, 8)), jnp.ones((1, 2, 1))
    assert float(ref.delta_rule(key, key, vals, zero, one)[1][0, 0, 0, 0]) \
        == 5.0
    assert float(ref.delta_rule(key, key, vals, zero, one,
                                delta_reads=False)[1][0, 0, 0, 0]) == 8.0


# -- the router and the shares ---------------------------------------------------------

def test_route_top_k_noaux_tc_is_the_references_router():
    """The program's rule against the reference's own router: the same
    experts (the bias chooses) and the same weights (it never weighs,
    and the divisor has no term), to float32's last digits."""
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(33, 16)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(16,)), jnp.float32)
    eid, w = routed.route_top_k(logits, 4, "noaux_tc", bias=bias)
    p = {"w": jnp.eye(16, dtype=jnp.float32), "bias": bias}
    with jax.default_matmul_precision("highest"):
        want_id, want_w = ref.router(p, logits, top_k=4)
    np.testing.assert_array_equal(eid, want_id)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(model):
    """Four chips, each holding 4 of the 16 experts: the routed parts of
    ``experts_held`` [0, 4) ... [12, 16) summed, with the shared expert
    counted once, are the uncut reference's layer."""
    graph, _ = model
    whole = solar_open2(8, 64, 4, 2, 16, SEQ, VOCAB, (0, 4), 16, 2, 32,
                        gate_rank=8, chunk=8)
    p = _turning_bias(whole.init(jax.random.key(9)))["block_1"]
    assert p["experts"]["gate"].shape[0] == 16
    a = jnp.asarray(np.random.default_rng(2).normal(size=(2, 7, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, chosen, _ = ref.moe(ref._f32(p), a, top_k=2)
        routed_only, _, _ = ref.moe(ref._f32(p), a, top_k=2, shared=False)
    rows = a.reshape(-1, 64)
    total, shared_out, pairs = 0.0, None, 0
    for lo in range(0, 16, 4):
        op = solar_open2(8, 64, 4, 2, 16, SEQ, VOCAB, (0, 4), 16, 2, 32,
                         gate_rank=8, chunk=8,
                         experts_held=(lo, lo + 4)).nodes["block_1"].op
        assert op.held == (lo, lo + 4)
        share = jax.tree.map(lambda e: e[lo:lo + 4], p["experts"])
        sown: dict = {}
        part, shared_out = routed.routed_experts(
            rows, p["router"], share, k=2, scoring="noaux_tc",
            num_experts=16, held=op.held,
            shared=(p["shared_gate"]["w"], p["shared_up"]["w"],
                    p["shared_down"]["w"]), sow=sown)
        # every share routes alike, over all 16 columns
        np.testing.assert_array_equal(
            sown["moe.chosen"].reshape(chosen.shape), chosen)
        pairs += int(sown["moe.held_assignments"])
        total = total + part
        # the reference over the same share leaves the rest out too
        with jax.default_matmul_precision("highest"):
            mine, _, _ = ref.moe(ref._f32(dict(p, experts=share)), a,
                                 top_k=2, held=(lo, lo + 4), shared=False)
        assert rel_err(part.reshape(a.shape), mine) < RTOL
    assert pairs == 2 * 7 * 2           # every pair fell to one share
    assert rel_err(total.reshape(a.shape), routed_only) < RTOL
    assert rel_err((total + shared_out).reshape(a.shape), want) < RTOL


# -- the ring through both kinds of memory --------------------------------------------

def test_prefill_then_decode_is_the_references_full_forward(model, ids,
                                                            generated):
    """Every generated token is the reference's own argmax at its
    position, the reference teacher-forced with the program's tokens and
    seeing no cache (float32: no token sits under the best at all)."""
    _, params = model
    out, _ = generated
    assert out.shape == (4, PLEN + NEW)
    np.testing.assert_array_equal(out[:, :PLEN], ids[:, :PLEN])
    assert logit_gaps(params, out, PLEN, REF_CFG).max() <= 0


def test_prefill_then_decode_logits_are_the_references(model, ids):
    """The logits themselves: a block's prefill, then its steps one
    token at a time through each layer's own format, against the
    reference's full forward of the same tokens."""
    graph, params = model
    nodes = graph.nodes
    names = [nm for nm in graph.topo_order if nm.startswith("block_")]
    fmts = [nodes[nm].op.memory_format(64, SEQ, jnp.float32, groups=1)
            for nm in names]
    seqs = jnp.asarray(ids[:2])

    def head(x):
        h = nodes["final_ln"].op.apply(params["final_ln"], x)
        return nodes["lm_head"].op.apply(params["lm_head"], h)

    x = nodes["embeddings"].op.apply(params["embeddings"], seqs[:, :PLEN])
    layers = []
    for nm, fmt in zip(names, fmts):
        x, layer = nodes[nm].op.prefill(
            params[nm], x, fmt.layer(fmt.zeros(2, 1), 0), fmt,
            fmt.prefill_slot(True, 0))
        layers.append(layer)
    got = [head(x)]
    for pos in range(PLEN, SEQ):
        x = nodes["embeddings"].op.embed_at(params["embeddings"],
                                            seqs[:, pos], pos)
        for i, (nm, fmt) in enumerate(zip(names, fmts)):
            x, layers[i] = nodes[nm].op.decode(
                params[nm], x, layers[i], jnp.int32(pos), fmt,
                fmt.decode_slot(True, jnp.int32(pos)), 0)
        got.append(head(x)[:, None])
    want = ref.logits(params, ids[:2], **REF)
    assert rel_err(jnp.concatenate(got, axis=1), want) < RTOL


def test_the_ring_leaves_the_states_the_reference_holds(model, generated):
    """After the prefill and ``NEW - 1`` decode steps the ring's buffers,
    layer by layer: a KDA layer's state and window are the reference's
    after the same tokens (the last token handed out was never an
    input); an attention layer keeps a key row a position and neither."""
    _, params = model
    out, dec = generated
    want = ref.states(params, out[:, :-1], **REF)
    assert dec.memory == KINDS
    assert set(dec.state) >= {"conv", "S", "k", "v"}
    for l, kind in enumerate(KINDS):
        if kind == "kv_cache":
            assert want[l] is None and dec.state["S"][l] is None \
                and dec.state["conv"][l] is None
            assert dec.state["k"][l].shape[-2:] == (SEQ + 1, 16)
            continue
        assert dec.state["k"][l] is None
        assert dec.state["S"][l].shape == (1, 1, 4, 16, 16, 4)
        assert dec.state["conv"][l].shape == (1, 1, 3, 4, 192)
        got = delta_rule.dense(dec.state["S"][l][0, 0], 4)
        assert rel_err(got, want[l][0]) < RTOL
        window = conv_window.dense_window(dec.state["conv"][l][0, 0])
        assert rel_err(window, want[l][1]) < RTOL


def test_teacher_forcing_at_decode_rate_is_the_fused_prefill(model, ids,
                                                             generated):
    out, dec = generated
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=False), out)


@pytest.mark.parametrize("chunk", [1, 3])
def test_the_tokens_do_not_depend_on_the_chunking(model, ids, generated,
                                                  chunk):
    out, dec = generated
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=chunk),
        out)


@pytest.mark.parametrize("prefill", [True, False])
def test_two_stages_of_a_period_each_are_one_stage(model, ids, generated,
                                                   prefill):
    """4 + 4: both stages repeat ``g k k k``."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    assert dec.memory == KINDS[:4] and dec.l_max == 4
    assert [len(b) for b in dec.stage_blocks] == [4, 4]
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=prefill, token_chunk=2),
        generated[0])


def test_a_prefill_in_pieces_is_the_prefill(model, ids, generated,
                                            monkeypatch):
    """A group that crosses the stage two sequences at a time: each
    layer's state and window are written a piece at a time, from the
    piece's row on."""
    from defer_tpu.runtime import decode
    graph, params = model
    # the widest activation is the convolutions' 3 x 64 columns
    monkeypatch.setattr(decode, "_PREFILL_PIECE_BYTES", 2 * PLEN * 192 * 4)
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    assert dec._prefill_rows(PLEN) == 2
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=True), generated[0])


def test_a_cut_inside_a_period_is_refused(model):
    """Stage 1 would open with ``k k k`` where stage 0 opens ``g k k``."""
    graph, params = model
    with pytest.raises(ValueError, match="stage 1's layer 0 .block_5. keeps "
                       "DeltaFormat.*block_0 at the same place of its "
                       "stage KVCacheFormat.*cut the graph at a whole "
                       "period"):
        PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                         max_len=SEQ, cut=[5, 3])


@pytest.mark.parametrize("kwargs, words", [
    ({"beam_width": 2}, "beam search re-parents.*keep a delta_rule "
     ".DeltaFormat."),
    ({"kv_cache": "int8"}, "quantizes cached key and value rows.*"
     "delta-rule state"),
], ids=["beam", "int8"])
def test_what_a_state_cannot_do_is_refused_by_message(model, kwargs, words):
    graph, params = model
    with pytest.raises(ValueError, match=words):
        PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                         max_len=SEQ, **kwargs)


def test_the_serving_engine_refuses_the_block_by_name(model):
    graph, params = model
    with pytest.raises(TypeError, match=r"block_0 \(SolarAttentionBlock\) "
                       "is not a CausalTransformerBlock"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


def test_the_counters_and_gauges_by_kind(model, ids):
    graph, params = model
    updates = REGISTRY.counter("decode.delta.updates")
    pairs = REGISTRY.counter("decode.moe.assignments")
    held = REGISTRY.counter("decode.moe.held_assignments")
    for n in (1, 2):
        dec = PipelinedDecoder(graph, params, num_stages=n,
                               microbatch=4 // n, max_len=SEQ)
        before = updates.n, pairs.n, held.n
        dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
        # positions PLEN .. PLEN+NEW-2 are decoded by 6 KDA layers (an
        # attention layer sows 0) and routed by all 8, 2 choices a
        # token; on two stages the schedule's last step hands stage 0
        # the last token of group 0: 2 sequences, its 3 KDA layers and
        # 4 routed ones
        assert updates.n - before[0] == 4 * 6 * (NEW - 1) \
            + (6 if n == 2 else 0)
        assert pairs.n - before[1] == 4 * 8 * 2 * (NEW - 1) \
            + (16 if n == 2 else 0)
        assert 0 < held.n - before[2] < pairs.n - before[1]
    state = 4 * 6 * 4 * 16 * 16 * 4
    window = 4 * 6 * 3 * 192 * 4
    assert REGISTRY.gauge("decode.delta.state_bytes").value == state
    assert REGISTRY.gauge("decode.delta.window_bytes").value == window
    assert REGISTRY.gauge("decode.delta_rule.state_bytes").value \
        == state + window
    # an attention layer a stage: two groups and the scratch group of 2
    # sequences, SEQ rows and the scratch row of two heads of 16, keys
    # and values
    full = 2 * (2 + 1) * 2 * 2 * (SEQ + 1) * 16 * 4 * 2
    assert REGISTRY.gauge("decode.kv_cache.state_bytes").value == full
    assert REGISTRY.gauge("decode.cache.full_bytes").value == full


# -- the contract ----------------------------------------------------------------------

def test_the_blocks_declare_their_memory(model):
    graph, _ = model
    attn, kda = (graph.nodes[f"block_{i}"].op for i in (0, 1))
    assert isinstance(attn, SolarAttentionBlock) \
        and isinstance(kda, SolarKdaBlock)
    assert isinstance(kda, DeltaRuleBlock) and isinstance(kda, DecoderBlock) \
        and not isinstance(kda, StateSpaceBlock)
    assert kda.memory == "delta_rule" and kda.geometry(64) is None
    assert kda.mixer_width == 192
    assert kda.memory_format(64, SEQ, jnp.bfloat16, groups=2) == \
        delta_rule.DeltaFormat(4, 16, 4, 8, jnp.bfloat16, groups=2)
    # neither the positions nor the stream's width reach the format
    assert kda.memory_format(999, 5, jnp.bfloat16, groups=2) == \
        kda.memory_format(64, SEQ, jnp.bfloat16, groups=2)
    assert not isinstance(attn, DeltaRuleBlock)
    assert attn.memory == "kv_cache" and attn.geometry(64) == (4, 2, 16)
    # the widest activation: the convolutions' [q, k, v], or a token's
    # rows sorted by expert
    assert (kda.widest(64), attn.widest(64)) == (192, 128)
    assert kda.decode_stats == attn.decode_stats == STATS
    assert kda.scoring == attn.scoring == "noaux_tc"
    assert kda.held == attn.held == (0, 4)
    params = graph.init(jax.random.key(0))
    half = {"ln2", "router", "experts", "shared_gate", "shared_up",
            "shared_down"}
    assert set(params["block_1"]) == half | {
        "ln1", "in_proj", "conv", "f_down", "f_up", "decay", "beta",
        "g_down", "g_up", "o_norm", "out_proj"}
    assert set(params["block_0"]) == half | {
        "ln1", "q", "k", "v", "gate", "proj"}
    assert set(params["block_1"]["conv"]) == {"w"}          # no bias
    assert params["block_1"]["conv"]["w"].shape == (4, 192)
    assert params["block_1"]["decay"]["A_log"].shape == (4,)
    assert params["block_1"]["decay"]["dt_bias"].shape == (64,)
    assert params["block_1"]["o_norm"]["scale"].shape == (16,)
    assert params["block_1"]["router"]["w"].shape == (64, 16)
    assert params["block_1"]["experts"]["gate"].shape == (4, 64, 32)
    assert params["block_0"]["gate"]["w"].shape == (64, 64)
    # the decays as the layer's own initialisation draws them: a rate
    # in [1, 16] a head, a step in [1e-3, 1e-1] a channel
    rate = np.exp(params["block_1"]["decay"]["A_log"])
    step = np.log1p(np.exp(params["block_1"]["decay"]["dt_bias"]))
    assert (1 <= rate).all() and (rate <= 16).all()
    assert (9e-4 < step).all() and (step < 0.11).all()
    # the mixer's pieces: beta in (0, 2), a log-decay below 0, unit keys
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 64)),
                    jnp.float32)
    u, rest = kda.mixer_inputs(params["block_1"], x)
    assert u.shape == (2, 192) and set(rest) == {"f", "beta", "gate"}
    q, k, v, g, beta = kda.mixer_selection(
        params["block_1"], kda.mixer_conv(params["block_1"], [u] * 4), rest)
    assert ((0 < beta) & (beta < 2)).all() and (g < 0).all()
    np.testing.assert_allclose(
        jnp.linalg.norm(k.reshape(2, 4, 16), axis=-1), 1.0, rtol=1e-4)
    np.testing.assert_allclose(
        jnp.linalg.norm(q.reshape(2, 4, 16), axis=-1), 0.25, rtol=1e-4)
    # an attention block sows no update, a KDA block its rows
    sown: dict = {}
    attn.apply(params["block_0"], x[None], sow=sown)
    assert int(sown["moe.assignments"]) == 4 \
        and int(sown["delta.updates"]) == 0
    sown = {}
    kda.apply(params["block_1"], x[None], sow=sown)
    assert int(sown["delta.updates"]) == 2
    # the embedding reads no position
    embed = graph.nodes["embeddings"].op
    np.testing.assert_array_equal(
        embed.embed_at(params["embeddings"], jnp.array([3, 5]), 0),
        embed.embed_at(params["embeddings"], jnp.array([3, 5]), 17))


def test_the_attention_layer_reads_no_position(model):
    """Without rotation and without a learned position a row's keys do
    not know where they stand: the same token gives the same key and
    value columns at every position."""
    graph, _ = model
    attn = graph.nodes["block_0"].op
    params = graph.init(jax.random.key(0))["block_0"]
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 64)),
                    jnp.float32)
    a = attn.decode_qkv(params, x, jnp.int32(0))
    b = attn.decode_qkv(params, x, jnp.int32(23))
    for one, other in zip(a, b):
        np.testing.assert_array_equal(one, other)


def test_the_contract_reports_kinds_and_geometries_by_layer(model):
    graph, _ = model
    parts = decoder_parts(graph, 2)
    assert parts.memory == KINDS and parts.decode_stats == STATS
    assert parts.geometry == ((4, 2, 16), None, None, None) * 2
    assert [len(b) for b in parts.stage_blocks] == [4, 4]


def test_the_builder_refuses_what_the_family_has_not():
    with pytest.raises(ValueError, match=r"gqa_layers \[0, 9\] name layers"):
        solar_open2(4, 64, 4, 2, 16, SEQ, VOCAB, (0, 9), 16, 2, 32)
    with pytest.raises(ValueError, match="experts_held .12, 20. is no range"):
        solar_open2(4, 64, 4, 2, 16, SEQ, VOCAB, (0,), 16, 2, 32,
                    experts_held=(12, 20)).nodes["block_0"].op.held


def test_importing_the_family_does_no_work_and_no_ops_module():
    """ROADMAP A6 / C18: every cell imports the package, so the module
    makes no array at import; and the delta rule's ops module comes in
    where a block's ``memory_format`` asks, not with the package."""
    import importlib
    import subprocess
    import sys
    mod = sys.modules["defer_tpu.models.solar_open2"]
    before = len(jax.live_arrays())
    importlib.reload(mod)
    assert len(jax.live_arrays()) == before
    code = ("import sys, defer_tpu, defer_tpu.models, defer_tpu.ops; "
            "assert 'defer_tpu.models.solar_open2' in sys.modules; "
            "assert 'defer_tpu.ops.delta_rule' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
