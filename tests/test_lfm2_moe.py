"""LFM2-MoE on the normal path, against the plain reference
(``chipbench/reference/lfm2_moe.py``) at a tiny size: seeded random
weights, two periods of ``conv conv attention conv`` with the first two
layers dense, d 64, 4 query heads on 2 KV heads of 16, 3 taps, dense
width 96, 2 of 8 experts of 32 a token, vocabulary 211 — a graph whose
layers keep two kinds of memory, one of them a window and nothing else.

Tolerances.  In float32 both sides multiply in float32 in different
orders (the program sorts a routed layer's rows by expert and runs them
through a kernel; the reference loops over the experts with a mask), so
logits agree to about 1e-5 of their largest.  ``RTOL`` 2e-4 leaves room
and stays 50x under what a change of the mathematics costs (the three
controls — a window one position off, the ``B`` gate dropped, a ``silu``
left in — and the rest of the reference's switches: asserted below by
mutating the reference).  Tokens are held by the benchmark's own
measure, ``logit_gaps``: in float32 no generated token may sit under
the reference's best at all.
"""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.agreement import logit_gaps, rel_err
from chipbench.reference import lfm2_moe as ref
from defer_tpu.models import kimi_k2_tiny, lfm2_moe, lfm2_moe_tiny
from defer_tpu.models.cohere_moe import tie_head
from defer_tpu.models.decoder import (ConvWindowBlock, DecoderBlock,
                                      StateSpaceBlock, decoder_parts)
from defer_tpu.models.lfm2_moe import (ROUTE_EPS, Lfm2DenseConvBlock,
                                       Lfm2MoeAttentionBlock,
                                       Lfm2MoeConvBlock)
from defer_tpu.obs import REGISTRY
from defer_tpu.ops import conv_window, kv_cache, layered, routed, ssm
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

VOCAB, SEQ, PLEN, NEW = 211, 32, 7, 9
TYPES = ("conv", "conv", "full_attention", "conv")
REF = dict(layer_types=TYPES, dense_layers=2, n_head=4, n_kv=2, head_dim=16,
           top_k=2, routed_scale=1.0, theta=1000000.0, eps=1e-5)
REF_CFG = {"module": "chipbench.reference.lfm2_moe", "args": REF}
RTOL = 2e-4
KINDS = ("conv_window", "conv_window", "kv_cache", "conv_window") * 2
STATS = ("moe.assignments", "moe.experts_hit", "moe.load_max",
         "conv.updates")


def _rounded(params, dtype):
    """``params`` with every floating leaf rounded to ``dtype`` and held
    in float32: what a bfloat16 checkpoint gives both sides."""
    return jax.tree.map(lambda a: a.astype(dtype).astype(jnp.float32)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a,
                        params)


@pytest.fixture(scope="module")
def model():
    graph = lfm2_moe_tiny(seq_len=SEQ, vocab=VOCAB)
    params = tie_head(graph.init(jax.random.key(3)))
    # a seeded bias of the initialiser's spread turns too few choices
    # for a test to see: this one turns about a choice in five
    for i in range(2, 8):
        params[f"block_{i}"]["router"]["bias"] = 0.05 * jax.random.normal(
            jax.random.key(100 + i), (8,), jnp.float32)
    return graph, params


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
    {"window_shift": 1}, {"b_gate": False}, {"conv_silu": True},
    {"qk_norm": False}, {"bias_weighs": True}, {"route_eps": 0.1},
    {"theta": 10000.0}, {"conv_dtype": jnp.bfloat16},
    {"router_dtype": jnp.bfloat16}],
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
        got = conv_window.dense_window(dec.state["conv"][0][0, 0])
        assert rel_err(got, off[0]) > 0.1
        return
    moved = rel_err(ref.logits(params, ids, **{**REF, **control}),
                    ref.logits(params, ids, **REF))
    # rounding the float32-stated sums to bfloat16 costs least
    assert moved > (5 * RTOL if "dtype" in next(iter(control)) else 1e-2)


def test_the_references_window_is_the_last_inputs_of_its_convolution(model,
                                                                     ids):
    """``states`` hands back ``z``'s last two rows, oldest first, and
    the mixer's convolution of a sequence cut there and continued from
    that window is the whole sequence's."""
    _, params = model
    p = ref._f32(params["block_0"])
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 9, 64)),
                    jnp.float32)
    whole, window = ref.conv_mixer(p, u)
    head, mid = ref.conv_mixer(p, u[:, :6])
    b_in, _, x_in = jnp.split(u @ p["in_proj"]["w"], 3, axis=-1)
    z = b_in * x_in
    np.testing.assert_allclose(window, z[:, -2:], rtol=1e-6)
    np.testing.assert_allclose(mid, z[:, 4:6], rtol=1e-6)
    np.testing.assert_allclose(whole[:, :6], head, rtol=1e-5, atol=1e-6)
    assert ref.conv_mixer(p, u, window_shift=1)[1].shape == (2, 2, 64)


# -- the router -------------------------------------------------------------------

@pytest.mark.parametrize("eps, scale", [(ROUTE_EPS, 1.0), (1e-20, 2.5)],
                         ids=["lfm2", "kimi"])
def test_route_top_k_noaux_tc_is_the_references_router(eps, scale):
    """The program's rule under the caller's term against the
    reference's own router: the same experts (the bias chooses) and the
    same weights (it never weighs), to float32's last digits."""
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(33, 16)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(16,)), jnp.float32)
    eid, w = routed.route_top_k(logits, 4, "noaux_tc", bias=bias,
                                scale=scale, eps=eps)
    # the reference's router takes the stream and its matrix: an
    # identity makes the logits the stream
    p = {"w": jnp.eye(16, dtype=jnp.float32), "bias": bias}
    with jax.default_matmul_precision("highest"):
        want_id, want_w = ref.router(p, logits, top_k=4, routed_scale=scale,
                                     route_eps=eps)
    np.testing.assert_array_equal(eid, want_id)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    # the bias turned choices and is in no weight
    plain, _ = routed.route_top_k(logits, 4, "noaux_tc",
                                  bias=jnp.zeros(16), eps=eps)
    assert (np.sort(eid, -1) != np.sort(plain, -1)).any()
    s = jax.nn.sigmoid(logits)
    chosen = jnp.take_along_axis(s, eid, -1)
    np.testing.assert_allclose(
        w, scale * chosen / (chosen.sum(-1, keepdims=True) + eps), rtol=1e-6)


def test_the_default_term_is_kimis_and_its_text_does_not_move():
    """``eps`` defaults to the ``1e-20`` that stood in the rule before
    it became the caller's: a caller that names none traces the same
    constant."""
    logits = jnp.ones((3, 8), jnp.float32)
    bias = jnp.zeros((8,), jnp.float32)

    def rule(**kw):
        return jax.jit(lambda x, b: routed.route_top_k(
            x, 2, "noaux_tc", bias=b, **kw)).lower(logits, bias).as_text()

    assert rule() == rule(eps=1e-20) != rule(eps=ROUTE_EPS)
    graph = kimi_k2_tiny()
    assert graph.nodes["block_1"].op.scoring == "noaux_tc"


# -- the window's format -----------------------------------------------------------

def test_the_window_formats_buffers_gauge_and_bytes():
    fmt = conv_window.ConvWindowFormat(64, 3, jnp.bfloat16, groups=2)
    assert fmt.keys == ("conv",)
    bufs = fmt.buffers(5)
    assert list(bufs) == ["conv"]
    assert bufs["conv"].shape == (2, 2, 5, 64)      # groups, taps, batch, W
    assert bufs["conv"].dtype == jnp.bfloat16
    assert fmt.state_bytes(5, 3) == 3 * 2 * 2 * 5 * 64 * 2
    assert fmt.gauges(5, 2) == {
        "decode.conv.window_bytes": 2 * 2 * 2 * 5 * 64 * 2}
    assert fmt.rows_read(5, 9) == {}
    one = conv_window.ConvWindowFormat(64, 3, jnp.float32)
    assert one.buffers(5)["conv"].shape == (2, 5, 64)
    state = one.zeros(5, 2)
    assert set(state) == {"conv"} and len(state["conv"]) == 2
    # a scratch-free memory: a slot is "is the step real" and no address
    assert fmt.decode_slot(True, 7) is True
    assert fmt.prefill_slot(False, 1) == (1, False)
    assert fmt.prefill_slot(True, 1, 4) == (1, True, 4)
    # the state-space formats are the ones that add ``h`` to it
    for other in (ssm.SsmFormat(64, 8, 4, jnp.float32, groups=2),
                  ssm.SsdFormat(4, 16, 8, 4, 8, jnp.float32, groups=2)):
        assert isinstance(other, conv_window.Window)
        assert other.keys == ("conv", "h")
        assert other.buffers(5)["conv"].shape == (2, 3, 5, other.conv_width)
        # (since PR 67 the state of heads also says its B/C groups)
        assert set(other.gauges(5, 2)) - {"decode.ssm.bc_groups"} \
            == {"decode.ssm.conv_bytes"}


@pytest.mark.parametrize("plen", [1, 2, 9])
def test_the_format_prefills_then_shifts_like_one_long_prefill(plen):
    """A prompt's taps, then a step's at a time, are the taps of the
    whole text; the window left behind is its last two inputs."""
    rng = np.random.default_rng(plen)
    b, t, e = 3, 12, 64
    u = jnp.asarray(rng.normal(size=(b, t, e)), jnp.float32)
    fmt = conv_window.ConvWindowFormat(e, 3, jnp.float32, groups=2)
    whole, _ = fmt.prefill_shift(u, fmt.layer(fmt.zeros(b, 1), 0),
                                 fmt.prefill_slot(True, 1))
    taps, layer = fmt.prefill_shift(u[:, :plen], fmt.layer(fmt.zeros(b, 1), 0),
                                    fmt.prefill_slot(True, 1))
    for j in range(3):
        np.testing.assert_array_equal(taps[j], whole[j][:, :plen])
    for pos in range(plen, t):
        taps, layer = fmt.shift(u[:, pos], layer, group=1, valid=True)
        for j in range(3):
            np.testing.assert_array_equal(taps[j], whole[j][:, pos])
    np.testing.assert_array_equal(
        conv_window.dense_window(layer["conv"][1]), u[:, -2:])
    assert not np.asarray(layer["conv"][0]).any()       # the other group


def test_a_bubble_leaves_the_window_bit_for_bit():
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(2, 4, 64)), jnp.float32)
    fmt = conv_window.ConvWindowFormat(64, 3, jnp.float32, groups=1)
    _, layer = fmt.prefill_shift(u, fmt.layer(fmt.zeros(2, 1), 0),
                                 fmt.prefill_slot(True, 0))
    assert np.asarray(layer["conv"]).any()
    _, after = fmt.shift(u[:, 0], layer, group=0,
                         valid=fmt.decode_slot(False, 0))
    _, after = fmt.prefill_shift(u, after, fmt.prefill_slot(False, 0))
    assert np.asarray(after["conv"]).tobytes() == \
        np.asarray(layer["conv"]).tobytes()


@pytest.mark.parametrize("bias, activation", [
    (True, "silu"), (False, "silu"), (True, None), (False, None)])
def test_causal_conv_with_and_without_bias_and_activation(bias, activation):
    """Today's behaviour is the default (a bias and a ``silu``, the
    state-space mixers'); LFM2's has neither; float32 accumulation
    either way."""
    rng = np.random.default_rng(0)
    taps = [jnp.asarray(rng.normal(size=(2, 8)), jnp.bfloat16)
            for _ in range(3)]
    w = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    kw = {} if activation else {"activation": None}
    got = ssm.causal_conv(taps, w, b if bias else None, **kw)
    acc = sum(np.asarray(w[j], np.float64) * np.asarray(taps[j], np.float64)
              for j in range(3)) + (np.asarray(b, np.float64) if bias else 0)
    want = acc / (1 + np.exp(-acc)) if activation else acc
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=1e-2)
    if bias and activation:         # positional, as the callers spell it
        np.testing.assert_array_equal(ssm.causal_conv(taps, w, b), got)


def test_a_state_of_unlike_layers_lies_side_by_side():
    """A window-only layer beside a cache and a state-space layer: the
    key ``conv`` is shared by the two formats that keep a window, and
    each layer's is its own width."""
    fmts = (conv_window.ConvWindowFormat(64, 3, jnp.float32, groups=2),
            kv_cache.KVCacheFormat(2, 16, 12, jnp.float32, groups=2),
            ssm.SsmFormat(128, 8, 4, jnp.float32, groups=2))
    shapes = layered.shapes_by_layer(fmts, 3)
    assert list(shapes) == ["conv", "k", "v", "h"]
    assert [s is None for s in shapes["conv"]] == [False, True, False]
    assert shapes["conv"][0].shape == (2, 2, 3, 64)
    assert shapes["conv"][2].shape == (2, 3, 3, 128)
    assert shapes["h"][0] is None
    assert layered.totals(fmts, lambda f: f.gauges(3, 2)).keys() >= {
        "decode.conv.window_bytes", "decode.ssm.conv_bytes"}


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


def test_the_ring_leaves_the_windows_the_reference_holds(model, generated):
    """After the prefill and ``NEW - 1`` decode steps the ring's buffers,
    layer by layer: a convolution layer's window is the reference's
    after the same tokens (the last token handed out was never an
    input) and it keeps nothing else; an attention layer keeps a key
    row a position and no window."""
    _, params = model
    out, dec = generated
    want = ref.states(params, out[:, :-1], **REF)
    assert dec.memory == KINDS
    assert set(dec.state) >= {"conv", "k", "v"} and "h" not in dec.state
    for l, kind in enumerate(KINDS):
        if kind == "kv_cache":
            assert want[l] is None and dec.state["conv"][l] is None
            assert dec.state["k"][l].shape[-2:] == (SEQ + 1, 16)
            continue
        assert dec.state["k"][l] is None
        assert dec.state["conv"][l].shape == (1, 1, 2, 4, 64)
        window = conv_window.dense_window(dec.state["conv"][l][0, 0])
        assert rel_err(window, want[l]) < RTOL


def test_teacher_forcing_at_decode_rate_is_the_fused_prefill(model, ids,
                                                             generated):
    graph, params = model
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
    """4 + 4: both stages repeat ``c c a c``; stage 0's first two
    blocks are dense where stage 1's route (a tree a kind at a place:
    ``PipelinedDecoder._variant``)."""
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
    layer's window is written a piece at a time, from the piece's row
    on."""
    from defer_tpu.runtime import decode
    graph, params = model
    # the widest activation is the input projection's 3 x 64 columns
    monkeypatch.setattr(decode, "_PREFILL_PIECE_BYTES", 2 * PLEN * 192 * 4)
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    assert dec._prefill_rows(PLEN) == 2
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=True), generated[0])


def test_a_cut_of_five_and_three_is_refused(model):
    """Stage 1 would open with ``c a c`` where stage 0 opens ``c c a``."""
    graph, params = model
    with pytest.raises(ValueError, match="stage 1's layer 1 .block_6. keeps "
                       "KVCacheFormat.*block_1 at the same place of its "
                       "stage ConvWindowFormat.*cut the graph at a whole "
                       "period"):
        PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                         max_len=SEQ, cut=[5, 3])


@pytest.mark.parametrize("kwargs, words", [
    ({"beam_width": 2}, "beam search re-parents.*keep a conv_window "
     ".ConvWindowFormat."),
    ({"kv_cache": "int8"}, "quantizes cached key and value rows.*"
     "convolution window"),
], ids=["beam", "int8"])
def test_what_a_window_cannot_do_is_refused_by_message(model, kwargs, words):
    graph, params = model
    with pytest.raises(ValueError, match=words):
        PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                         max_len=SEQ, **kwargs)


def test_the_serving_engine_refuses_the_block_by_name(model):
    graph, params = model
    with pytest.raises(TypeError, match=r"block_0 \(Lfm2DenseConvBlock\) is "
                       "not a CausalTransformerBlock"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


def test_the_counters_and_gauges_by_kind(model, ids):
    graph, params = model
    updates = REGISTRY.counter("decode.conv.updates")
    pairs = REGISTRY.counter("decode.moe.assignments")
    for n in (1, 2):
        dec = PipelinedDecoder(graph, params, num_stages=n,
                               microbatch=4 // n, max_len=SEQ)
        before = updates.n, pairs.n
        dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
        # positions PLEN .. PLEN+NEW-2 are decoded by 6 convolution
        # layers (an attention layer sows 0) and routed by 6 layers (a
        # dense one sows 0), 2 choices a token; on two stages the
        # schedule's last step hands stage 0 the last token of group 0:
        # 2 sequences, its 3 convolution layers and 2 routed ones
        assert updates.n - before[0] == 4 * 6 * (NEW - 1) \
            + (6 if n == 2 else 0)
        assert pairs.n - before[1] == 4 * 6 * 2 * (NEW - 1) \
            + (8 if n == 2 else 0)
    window = 4 * 6 * 2 * 64 * 4
    assert REGISTRY.gauge("decode.conv.window_bytes").value == window
    assert REGISTRY.gauge("decode.conv_window.state_bytes").value == window
    # an attention layer a stage: two groups and the scratch group of 2
    # sequences, SEQ rows and the scratch row of two heads of 16, keys
    # and values
    full = 2 * (2 + 1) * 2 * 2 * (SEQ + 1) * 16 * 4 * 2
    assert REGISTRY.gauge("decode.kv_cache.state_bytes").value == full
    assert REGISTRY.gauge("decode.cache.full_bytes").value == full


# -- the contract ----------------------------------------------------------------------

def test_the_blocks_declare_their_memory(model):
    graph, _ = model
    dense, attn, conv = (graph.nodes[f"block_{i}"].op for i in (0, 2, 3))
    assert isinstance(dense, Lfm2DenseConvBlock) \
        and isinstance(conv, Lfm2MoeConvBlock) \
        and isinstance(attn, Lfm2MoeAttentionBlock)
    for op in (dense, conv):
        assert isinstance(op, ConvWindowBlock) \
            and isinstance(op, DecoderBlock) \
            and not isinstance(op, StateSpaceBlock)
        assert op.memory == "conv_window" and op.geometry(64) is None
        assert op.mixer_width == 192
        assert op.memory_format(64, SEQ, jnp.bfloat16, groups=2) == \
            conv_window.ConvWindowFormat(64, 3, jnp.bfloat16, groups=2)
        # neither the positions nor the stream's width reach the format
        assert op.memory_format(999, 5, jnp.bfloat16, groups=2) == \
            op.memory_format(64, SEQ, jnp.bfloat16, groups=2)
    assert not isinstance(attn, ConvWindowBlock)
    assert attn.memory == "kv_cache" and attn.geometry(64) == (4, 2, 16)
    # the widest activation: the input projection's [B, C, X], or a
    # dense layer's SwiGLU, or a token's rows sorted by expert
    assert (dense.widest(64), conv.widest(64), attn.widest(64)) == (
        192, 192, 128)
    assert dense.decode_stats == conv.decode_stats == attn.decode_stats \
        == STATS
    assert conv.scoring == attn.scoring == "noaux_tc"
    params = graph.init(jax.random.key(0))
    assert set(params["block_0"]) == {
        "ln1", "in_proj", "conv", "out_proj", "ln2", "mlp_gate", "mlp_up",
        "mlp_down"}
    assert set(params["block_3"]) == {
        "ln1", "in_proj", "conv", "out_proj", "ln2", "router", "experts"}
    assert set(params["block_2"]) == {
        "ln1", "q", "q_norm", "k", "k_norm", "v", "proj", "ln2", "router",
        "experts"}
    assert set(params["block_0"]["conv"]) == {"w"}          # no bias
    assert params["block_0"]["conv"]["w"].shape == (3, 64)
    assert params["block_2"]["q_norm"]["scale"].shape == (16,)
    assert params["block_3"]["router"]["bias"].shape == (8,)
    # the mixer's pieces: z = B * X and the gate C of one projection
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 64)),
                    jnp.float32)
    z, c_gate = conv.mixer_inputs(params["block_3"], x)
    u = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    bcx = u @ params["block_3"]["in_proj"]["w"]
    np.testing.assert_allclose(z, bcx[:, :64] * bcx[:, 128:], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(c_gate, bcx[:, 64:128], rtol=1e-5, atol=1e-6)
    # the dense blocks sow zeros into the routed ledger
    sown: dict = {}
    dense.apply(params["block_0"], x[None], sow=sown)
    assert [int(sown[k]) for k in STATS] == [0, 0, 0, 2]
    sown = {}
    attn.apply(params["block_2"], x[None], sow=sown)
    assert int(sown["moe.assignments"]) == 4 and int(sown["conv.updates"]) == 0


def test_the_published_geometry_attends_over_joined_rows(ids):
    """32 query heads on 8 KV heads of 64 are four queries a KV head
    and two KV heads a lane row: a group for the matrix unit over heads
    that pair into lane rows (``ops/kv_cache.py::_JOINED_GROUP``,
    ``_lane_heads``), so the cell's two attention layers hold their rows
    joined — 2559 positions and the scratch row, 1 KB a position, the
    bytes the plain rows took — and leave ``kv_step``'s pass a query.  A
    ring of that kind of geometry (heads of 64 in a group of 4, a narrow
    stream) names ``kv_attend`` once an attention layer and no
    ``kv_step``, beside the gauge that says which of the two kernels of
    that name it is; its tokens are the float32 reference's, prompt and
    steps over joined rows, fused prefill and teacher-forced."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "chipbench", "configs",
                           "lfm2-24b-a2b-10l.json")) as f:
        args = json.load(f)["model_args"]
    attn = lfm2_moe(**args).nodes["block_2"].op
    assert attn.geometry(args["hidden"]) == (32, 8, 64)
    fmt = attn.memory_format(args["hidden"], 2559, jnp.bfloat16, groups=1)
    assert fmt == kv_cache.KVCacheFormat(8, 64, 2559, jnp.bfloat16,
                                         groups=1, query_group=4)
    assert fmt.joined and not fmt.writes_in_attention
    assert fmt.buffers(128)["k"].shape == (2, 128, 2560, 512)
    said = fmt.gauges(128, 1)
    assert (said["decode.cache.block_sequences"],
            said["decode.cache.block_positions"]) == (1, 1024)

    graph = lfm2_moe(4, 64, 8, 2, 64, 96, SEQ, VOCAB, TYPES, 8, 2, 32)
    params = tie_head(graph.init(jax.random.key(0)))
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                           max_len=SEQ)
    attention = dec.memory.count("kv_cache")
    assert attention == 1
    a, caches = dec._init_state()
    assert caches["k"][2].shape == (1, 2, 2, 48, 128)   # stage, groups, ...
    i32 = jnp.int32(0)
    for name in ("decode.kv.joined_layers", "decode.kv.fused_layers"):
        REGISTRY.gauge(name).set(-1)
    jaxpr = jax.make_jaxpr(dec._get_decode_fn(4, False, None))(
        dec._w, jnp.zeros((1, 2, 5), jnp.int32), i32, i32, i32,
        jnp.uint32(0), jnp.float32(0), jnp.zeros((1, 2), jnp.int32),
        i32, i32, a, caches)
    # (the joined call's ``jit`` is named ``kv_attend_joined``)
    calls = re.findall(r"\bname=(kv_attend|kv_step|kv_write_rows)\b",
                       str(jaxpr))
    assert calls == ["kv_attend"] * attention
    assert REGISTRY.gauge("decode.kv.joined_layers").value == attention
    assert REGISTRY.gauge("decode.kv.fused_layers").value == 0
    narrow = {"module": REF_CFG["module"],
              "args": dict(REF, n_head=8, head_dim=64)}
    out = dec.generate(ids[:2, :PLEN], NEW, prefill=True)
    assert logit_gaps(params, out, PLEN, narrow).max() <= 0
    np.testing.assert_array_equal(
        dec.generate(ids[:2, :PLEN], NEW, prefill=False), out)


def test_the_contract_reports_kinds_and_geometries_by_layer(model):
    graph, _ = model
    parts = decoder_parts(graph, 2)
    assert parts.memory == KINDS and parts.decode_stats == STATS
    assert parts.geometry == (None, None, (4, 2, 16), None) * 2
    assert [len(b) for b in parts.stage_blocks] == [4, 4]


@pytest.mark.parametrize("layer_types, dense_layers, words", [
    (("conv", "attention"), 1, "neither 'conv' nor 'full_attention'"),
    (("full_attention", "conv"), 1, "layer 0 is a dense layer.*and an "
     "attention layer"),
])
def test_the_builder_refuses_what_the_family_has_not(layer_types,
                                                     dense_layers, words):
    with pytest.raises(ValueError, match=words):
        lfm2_moe(2, 64, 4, 2, 16, 96, SEQ, VOCAB, layer_types, 8, 2, 32,
                 dense_layers=dense_layers)


def test_importing_the_family_does_no_work():
    """ROADMAP A6 / C18: every cell imports the package, so the module
    makes no array and compiles nothing at import."""
    import importlib
    import sys
    # (the package's ``lfm2_moe`` is the builder; the module is behind it)
    mod = sys.modules["defer_tpu.models.lfm2_moe"]
    before = len(jax.live_arrays())
    importlib.reload(mod)
    assert len(jax.live_arrays()) == before
