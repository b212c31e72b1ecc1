"""Jamba on the normal path, against the plain reference
(``chipbench/reference/jamba.py``) at a tiny size: seeded random
weights, two periods of four layers with attention at offset 2, d 64, 4
query heads on 1 KV head of 16, 128 channels of 8 states, ``dt_rank``
4, ``d_conv`` 4, MLP width 96, vocabulary 211 — a graph whose layers
keep two *kinds* of memory.

Tolerances.  In float32 both sides multiply in float32 in different
orders (the program runs the recurrence a block of channels at a time
in a kernel and, decoding, a step a call; the reference scans whole
rows), so logits agree to about 1e-5 of their largest.  ``RTOL`` 2e-4
leaves room and stays 50x under what a change of the mathematics costs
(the family's three small norms dropped: asserted below by mutating
the reference).  Tokens are held by the benchmark's own measure,
``logit_gaps``: in float32 no generated token may sit under the
reference's best at all.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.agreement import logit_gaps, rel_err
from chipbench.reference import jamba as ref
from defer_tpu.models import (brumby_tiny, cohere_moe_tiny,
                              granite_hybrid_tiny, gpt_tiny, jamba_tiny,
                              olmoe_tiny)
from defer_tpu.models.cohere_moe import tie_head
from defer_tpu.models.decoder import (DecoderBlock, StateSpaceBlock,
                                      decoder_parts)
from defer_tpu.models.jamba import JambaAttentionBlock, JambaMambaBlock
from defer_tpu.obs import REGISTRY
from defer_tpu.ops import kv_cache, layered, retention, ssm
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

VOCAB, SEQ, PLEN, NEW = 211, 32, 7, 9
REF = dict(n_layer=8, n_head=4, n_kv=1, head_dim=16, attn_period=4,
           attn_offset=2, d_state=8, dt_rank=4, eps=1e-6)
REF_CFG = {"module": "chipbench.reference.jamba", "args": REF}
RTOL = 2e-4
KINDS = ("ssm", "ssm", "kv_cache", "ssm") * 2


@pytest.fixture(scope="module")
def model():
    graph = jamba_tiny(seq_len=SEQ, vocab=VOCAB)
    return graph, tie_head(graph.init(jax.random.key(3)))


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

def test_full_sequence_logits_match_the_reference(model, ids):
    graph, params = model
    got = jax.jit(graph.apply)(params, jnp.asarray(ids))
    want = ref.logits(params, ids, **REF)
    assert got.shape == (4, SEQ, VOCAB)
    assert rel_err(got, want) < RTOL


def test_the_tolerance_tells_plain_mamba_apart(model, ids):
    """Without the family's three small norms the reference's logits
    move by more than 1e-2 of their largest: 50x the tolerance above."""
    _, params = model
    assert rel_err(ref.logits(params, ids, small_norms=False, **REF),
                   ref.logits(params, ids, **REF)) > 1e-2


@pytest.mark.parametrize("state_dtype, least, most", [
    (None, 0.0, 1e-5), (jnp.bfloat16, 1e-3, 0.1)])
def test_the_references_recurrence_is_its_explicit_sum(state_dtype, least,
                                                       most):
    """The reference's scan ends on what its closed form says; with the
    state rounded to bfloat16 after every position (the control the
    chip's limits are set against) it departs by what that mantissa
    gives under decays near 1."""
    rng = np.random.default_rng(0)
    b, t, e, n = 2, 96, 24, 8
    dt = jnp.asarray(rng.uniform(0.0, 0.02, (b, t, e)), jnp.float32)
    x, bm, cm = (jnp.asarray(rng.normal(size=s), jnp.float32)
                 for s in ((b, t, e), (b, t, n), (b, t, n)))
    a = -jnp.asarray(rng.uniform(0.25, 1.0, (e, n)), jnp.float32)
    _, got = ref.selective_scan(dt, x, bm, cm, a, state_dtype=state_dtype)
    err = rel_err(got, ref.explicit_state(dt, x, bm, a))
    assert least <= err < most


# -- the format and its two kernels ----------------------------------------------

def _inputs(seed, b, t, e, n):
    rng = np.random.default_rng(seed)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(b, t, e)) - 2.0,
                                     jnp.float32))
    x, bm, cm = (jnp.asarray(rng.normal(size=s), jnp.float32)
                 for s in ((b, t, e), (b, t, n), (b, t, n)))
    a = -jnp.exp(jnp.asarray(rng.normal(size=(n, e)) * 0.3, jnp.float32))
    return dt, x, bm, cm, a


@pytest.mark.parametrize("t", [3, 20, 264])
def test_ssm_scan_is_the_recurrence_position_by_position(t):
    """The prefill kernel (interpret mode) against the plain oracle: a
    prompt shorter than a tile, one that ends inside a tile, and one of
    33 blocks of positions (264 = 8 x 33: the state crosses 32 block
    boundaries in VMEM)."""
    dt, x, bm, cm, a = _inputs(t, 2, t, 128, 8)
    y, h = ssm.ssm_scan(dt, dt * x, bm, cm, a)
    want_y, want_h = ssm.prefill_reference(dt, x, bm, cm, a)
    assert y.shape == (2, t, 128)
    assert rel_err(y, want_y) < 1e-5 and rel_err(h, want_h) < 1e-5


@pytest.mark.parametrize("batch", [2, 8])
def test_ssm_step_updates_its_group_in_place(batch):
    """The decode kernel against the plain oracle, on group 1 of 2: the
    other group's state is not touched."""
    dt, x, bm, cm, a = _inputs(1, batch, 1, 128, 8)
    start = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, batch, 8, 128)), jnp.float32)
    y, out = ssm.ssm_step(dt[:, 0], dt[:, 0] * x[:, 0], bm[:, 0], cm[:, 0],
                          a, start, jnp.array([1]))
    want_y, want_h = ssm.step_reference(dt[:, 0], x[:, 0], bm[:, 0],
                                        cm[:, 0], a, start[1])
    assert rel_err(y, want_y) < 1e-5 and rel_err(out[1], want_h) < 1e-5
    np.testing.assert_array_equal(out[0], start[0])


@pytest.mark.parametrize("plen", [2, 9])
def test_the_format_prefills_then_steps_like_one_long_prefill(plen):
    """Through :class:`SsmFormat` alone: a prompt (shorter than the
    convolution's window, and longer) prefilled, then the rest a step at
    a time, gives the taps, the outputs and the state of one prefill of
    the whole — the window and ``H`` are left where a step looks."""
    t, b, e, n = 14, 2, 128, 8
    dt, x, bm, cm, a = _inputs(7, b, t, e, n)
    u = jnp.asarray(np.random.default_rng(8).normal(size=(b, t, e)),
                    jnp.float32)
    fmt = ssm.SsmFormat(e, n, 4, jnp.float32, groups=2)
    empty = fmt.layer(fmt.zeros(b, 1), 0)
    slot = fmt.prefill_slot(True, 1)
    want_taps, whole = fmt.prefill_shift(u, empty, slot)
    want_y, whole = fmt.prefill(dt, x, bm, cm, a, whole, slot)

    taps, layer = fmt.prefill_shift(u[:, :plen], empty, slot)
    y, layer = fmt.prefill(dt[:, :plen], x[:, :plen], bm[:, :plen],
                           cm[:, :plen], a, layer, slot)
    for j in range(4):
        np.testing.assert_array_equal(taps[j], want_taps[j][:, :plen])
    assert rel_err(y, want_y[:, :plen]) < 1e-5
    for p in range(plen, t):
        taps, layer = fmt.shift(u[:, p], layer, group=1)
        for j in range(4):
            np.testing.assert_array_equal(taps[j], want_taps[j][:, p])
        y, layer = fmt.step(dt[:, p], x[:, p], bm[:, p], cm[:, p], a, layer,
                            group=1)
        assert rel_err(y, want_y[:, p]) < 1e-5
    assert rel_err(layer["h"], whole["h"]) < 1e-5
    np.testing.assert_array_equal(layer["conv"], whole["conv"])
    assert not np.asarray(layer["h"][0]).any()      # group 0: untouched
    h, window = ssm.dense(layer["h"][1], layer["conv"][1])
    assert h.shape == (b, e, n) and window.shape == (b, 3, e)
    np.testing.assert_array_equal(window, u[:, -3:])


def test_a_bubble_leaves_the_window_and_the_state_bit_for_bit():
    b, e, n = 2, 128, 8
    dt, x, bm, cm, a = _inputs(3, b, 4, e, n)
    fmt = ssm.SsmFormat(e, n, 4, jnp.float32, groups=1)
    layer = fmt.layer(fmt.zeros(b, 1), 0)
    _, layer = fmt.prefill_shift(x, layer, fmt.prefill_slot(True, 0))
    _, layer = fmt.prefill(dt, x, bm, cm, a, layer,
                           fmt.prefill_slot(True, 0))
    assert np.asarray(layer["h"]).any() and np.asarray(layer["conv"]).any()
    bubble = fmt.decode_slot(False, 0)
    _, after = fmt.shift(x[:, 0], layer, group=0, valid=bubble)
    _, after = fmt.step(dt[:, 0], x[:, 0], bm[:, 0], cm[:, 0], a, after,
                        group=0, valid=bubble)
    _, after = fmt.prefill_shift(x, after, fmt.prefill_slot(False, 0))
    _, after = fmt.prefill(dt, x, bm, cm, a, after,
                           fmt.prefill_slot(False, 0))
    for key in ("conv", "h"):
        assert np.asarray(after[key]).tobytes() == \
            np.asarray(layer[key]).tobytes()


def test_a_state_of_unlike_layers_lies_side_by_side():
    """``ops/layered.py``: under each key any layer names a tuple with an
    entry a layer, None where the layer's format has no such key; each
    format reaches its own layer and passes the others through."""
    fmts = (ssm.SsmFormat(128, 8, 4, jnp.float32, groups=2),
            kv_cache.KVCacheFormat(1, 16, 12, jnp.float32, groups=2),
            retention.RetentionFormat(2, 8, groups=2))
    shapes = layered.shapes_by_layer(fmts, 3)
    assert list(shapes) == ["conv", "h", "k", "v", "S", "z"]
    assert [s is None for s in shapes["k"]] == [True, False, True]
    state = layered.zeros_by_layer(fmts, 3, lead=(5,))
    assert state["h"][0].shape == (5, 2, 3, 8, 128)
    assert state["h"][1] is None and state["conv"][2] is None
    assert state["k"][1].shape == (5, 3, 3, 1, 13, 16)
    layer = fmts[0].layer(state, 0)
    assert set(layer) == {"conv", "h"}
    new = fmts[0].with_layer(state, 0, {k: v + 1 for k, v in layer.items()})
    assert float(new["h"][0].max()) == 1.0 and new["k"] is state["k"]
    # where the layers are alike it is what one format's zeros gives
    alike = layered.zeros_by_layer(fmts[:1] * 2, 3)
    want = fmts[0].zeros(3, 2)
    assert jax.tree.structure(alike) == jax.tree.structure(want)


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


def test_the_ring_leaves_the_state_the_reference_holds(model, generated):
    """After the prefill and ``NEW - 1`` decode steps the ring's buffers,
    layer by layer: a Mamba layer's ``H`` and window are the
    reference's after the same tokens (the last token handed out was
    never an input); an attention layer keeps a key row a position and
    nothing under the state's keys."""
    _, params = model
    out, dec = generated
    want = ref.states(params, out[:, :-1], **REF)
    assert dec.memory == KINDS
    for l, kind in enumerate(KINDS):
        if kind == "kv_cache":
            assert want[l] is None and dec.state["h"][l] is None
            assert dec.state["k"][l].shape[-2:] == (SEQ + 1, 16)
            continue
        assert dec.state["k"][l] is None
        h, window = ssm.dense(dec.state["h"][l][0, 0],
                              dec.state["conv"][l][0, 0])
        assert rel_err(h, want[l][0]) < RTOL
        assert rel_err(window, want[l][1]) < RTOL
    # one position off, the window is another: the comparison sees it
    off = ref.states(params, out[:, :-2], **REF)
    assert rel_err(ssm.dense(dec.state["h"][0][0, 0],
                             dec.state["conv"][0][0, 0])[1], off[0][1]) > 0.1


def test_teacher_forcing_at_decode_rate_is_the_fused_prefill(model, ids,
                                                             generated):
    _, dec = generated
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=False), generated[0])


@pytest.mark.parametrize("chunk", [1, 3])
def test_the_tokens_do_not_depend_on_the_chunking(model, ids, generated,
                                                  chunk):
    _, dec = generated
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=chunk),
        generated[0])


@pytest.mark.parametrize("prefill", [True, False])
def test_two_stages_of_a_period_each_are_one_stage(model, ids, generated,
                                                   prefill):
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    assert dec.memory == KINDS[:4] and dec.l_max == 4
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=prefill, token_chunk=2),
        generated[0])


def test_a_prefill_in_pieces_is_the_prefill(model, ids, generated,
                                            monkeypatch):
    """A group that crosses the stage two sequences at a time: each
    layer's window and state are written a piece at a time, from the
    piece's row on."""
    from defer_tpu.runtime import decode
    graph, params = model
    # the widest activation is the input projection's 2 x 128 columns
    monkeypatch.setattr(decode, "_PREFILL_PIECE_BYTES", 2 * PLEN * 256 * 4)
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    assert dec._prefill_rows(PLEN) == 2
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=True), generated[0])


def test_a_cut_inside_a_period_is_refused(model):
    """Four stages of two layers: stage 1 opens with the attention
    layer where stage 0 opens with a state-space layer.  Left to the
    bytes, the ring lies on four stages that each open a period."""
    graph, params = model
    with pytest.raises(ValueError, match="stage 1's layer 0 .block_2. keeps "
                       "KVCacheFormat.*cut the graph at a whole period"):
        PipelinedDecoder(graph, params, num_stages=4, microbatch=1,
                         max_len=SEQ, cut=[2, 2, 2, 2])


@pytest.mark.parametrize("prefill", [True, False])
def test_left_to_the_bytes_four_stages_each_open_a_period(model, ids,
                                                          generated, prefill):
    """The even rule's two layers a stage cut both periods; the cut the
    bytes choose among those the ring can run is 3 | 1 | 3 | 1 — every
    stage a prefix of ``ssa`` — and hands out the one-stage tokens."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=1,
                           max_len=SEQ)
    assert [len(b) for b in dec.stage_blocks] == [3, 1, 3, 1]
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=prefill, token_chunk=2),
        generated[0])


@pytest.mark.parametrize("kwargs, words", [
    ({"beam_width": 2}, "beam search re-parents.*keep a ssm .SsmFormat."),
    ({"kv_cache": "int8"}, "quantizes cached key and value rows.*"
     "state-space state"),
], ids=["beam", "int8"])
def test_what_a_state_cannot_do_is_refused_by_message(model, kwargs, words):
    graph, params = model
    with pytest.raises(ValueError, match=words):
        PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                         max_len=SEQ, **kwargs)


def test_the_serving_engine_refuses_the_block_by_name(model):
    graph, params = model
    with pytest.raises(TypeError, match=r"block_0 \(JambaMambaBlock\) is "
                       "not a CausalTransformerBlock"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


def test_the_counters_and_gauges_by_kind(model, ids):
    graph, params = model
    counter = REGISTRY.counter("decode.ssm.updates")
    for n in (1, 2):
        dec = PipelinedDecoder(graph, params, num_stages=n,
                               microbatch=4 // n, max_len=SEQ)
        before = counter.n
        dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
        # positions PLEN .. PLEN+NEW-2 are decoded by 6 Mamba layers (an
        # attention layer sows 0); on two stages the schedule's last
        # step hands stage 0 the last token of group 0: 2 sequences, its
        # 3 Mamba layers
        assert counter.n - before == 4 * 6 * (NEW - 1) + (6 if n == 2 else 0)
    h = 4 * 6 * 8 * 128 * 4
    conv = 4 * 6 * 3 * 128 * 4
    assert REGISTRY.gauge("decode.ssm.state_bytes").value == h + conv
    assert REGISTRY.gauge("decode.ssm.conv_bytes").value == conv
    # an attention layer a stage: two groups and the scratch group of 2
    # sequences, SEQ rows and the scratch row of one head of 16, keys
    # and values
    full = 2 * (2 + 1) * 2 * (SEQ + 1) * 16 * 4 * 2
    assert REGISTRY.gauge("decode.kv_cache.state_bytes").value == full
    assert REGISTRY.gauge("decode.cache.full_bytes").value == full
    assert REGISTRY.gauge("decode.cache.window_bytes").value == 0


# -- the contract ----------------------------------------------------------------------

def test_the_blocks_declare_their_memory(model):
    graph, _ = model
    mamba, attn = graph.nodes["block_0"].op, graph.nodes["block_2"].op
    assert isinstance(mamba, JambaMambaBlock) \
        and isinstance(mamba, StateSpaceBlock) \
        and isinstance(mamba, DecoderBlock)
    assert isinstance(attn, JambaAttentionBlock) \
        and not isinstance(attn, StateSpaceBlock)
    assert (mamba.memory, attn.memory) == ("ssm", "kv_cache")
    assert mamba.geometry(64) is None and attn.geometry(64) == (4, 1, 16)
    # the contract's words: the input projection's [u, z] is the widest
    # activation, no heads (a decay a channel and a state), and the
    # selection hands the recurrence its input beside dt, B, C and A
    assert mamba.mixer_width == 256 == mamba.widest(64)
    assert attn.widest(64) == 64
    assert getattr(mamba, "heads", None) is None
    params0 = graph.init(jax.random.key(0))["block_0"]
    c = jnp.ones((2, 128), jnp.float32)
    dt, xs, b, c_read, a = mamba.mixer_selection(params0, c, None)
    assert xs is c and dt.shape == (2, 128) and a.shape == (8, 128)
    assert b.shape == c_read.shape == (2, 8)
    assert mamba.memory_format(64, SEQ, jnp.bfloat16, groups=2) == \
        ssm.SsmFormat(128, 8, 4, jnp.bfloat16, groups=2)
    assert mamba.decode_stats == attn.decode_stats == ("ssm.updates",)
    # every leaf is an argument of its own on the ring, the five norms'
    # scales with the matrices and the per-channel vectors
    params = graph.init(jax.random.key(0))
    assert {"ln1", "ln2", "dt_norm", "b_norm", "c_norm"} \
        < set(params["block_0"])
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    for tree, nm in zip(dec._w["blocks"], dec.stage_blocks[1], strict=True):
        assert jax.tree.structure(tree) == jax.tree.structure(params[nm])


@pytest.mark.parametrize("family, kinds, heads", [
    (gpt_tiny, ("kv_cache",), (2, 2, 16)),
    (olmoe_tiny, ("kv_cache",), (4, 4, 16)),
    (brumby_tiny, ("retention",), (4, 2, 16)),
    (cohere_moe_tiny, ("kv_cache",), (8, 2, 8)),
    (jamba_tiny, ("ssm", "ssm", "kv_cache", "ssm"), (4, 1, 16)),
    (granite_hybrid_tiny, ("ssm", "ssm", "kv_cache", "ssm"), (4, 2, 16)),
], ids=["gpt", "olmoe", "brumby", "cohere_moe", "jamba", "granite_hybrid"])
def test_the_contract_reports_kinds_and_geometries_by_layer(family, kinds,
                                                            heads):
    graph = family()
    parts = decoder_parts(graph, 1)
    n = len(parts.block_names)
    assert parts.memory == (kinds * n)[:n]
    assert parts.geometry == tuple(
        None if kind == "ssm" else heads for kind in parts.memory)
    assert all(graph.nodes[nm].out_spec.shape[-1] == parts.d_model
               for nm in parts.block_names)


def _with_block(graph, name, op):
    nodes = dict(graph.nodes)
    nodes[name] = dataclasses.replace(nodes[name], op=op)
    other = graph.__class__.__new__(graph.__class__)
    other.__dict__.update(graph.__dict__)
    other.nodes = nodes
    return other


def test_head_geometry_is_a_layers_own():
    """GPT blocks of two geometries in one graph: the contract reports
    each layer's, the ring takes the graph where every stage repeats
    the same formats (and refuses the cut that does not, by
    ``check_cut``'s message), the serving engine — one homogeneous
    cache — refuses it."""
    graph = gpt_tiny()
    odd = dataclasses.replace(graph.nodes["block_1"].op, num_kv_heads=1)
    mixed = _with_block(_with_block(graph, "block_1", odd), "block_3", odd)
    params = graph.init(jax.random.key(0))
    assert decoder_parts(mixed, 2).geometry == ((2, 2, 16), (2, 1, 16)) * 2
    dec = PipelinedDecoder(mixed, params, num_stages=2, microbatch=1,
                           max_len=12)
    assert [f.kv_heads for f in dec.state_formats] == [2, 1]
    with pytest.raises(ValueError, match="repeat the same kinds of memory"):
        PipelinedDecoder(_with_block(graph, "block_1", odd), params,
                         num_stages=2, microbatch=1, max_len=12, cut=[2, 2])
    with pytest.raises(ValueError, match="one head geometry"):
        ContinuousBatchEngine(mixed, params, num_stages=2, width=2)
