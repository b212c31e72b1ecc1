"""Granite 4.0-H on the normal path, against the plain reference
(``chipbench/reference/granite_hybrid.py``) at a tiny size: seeded
random weights, two periods of four layers with attention at offset 2,
d 64, 4 query heads on 2 KV heads of 16, Mamba-2 of 8 heads x 16 with 16
states and a chunk of 8, 8 experts of 32 with 3 a token beside a shared
expert of 64, vocabulary 211, no multiplier 1 — a graph whose layers
keep two *kinds* of memory and all route.

Tolerances.  In float32 both sides multiply in float32 in different
orders (the program runs a prompt's recurrence in the chunked matrix
form in a kernel and, decoding, a step a call; the reference scans
position by position), so logits agree to about 1e-5 of their largest.
``RTOL`` 2e-4 leaves room and stays far under what a change of the
mathematics costs (the router's rule changed: asserted below by
mutating the reference).  Tokens are held by the benchmark's own
measure, ``logit_gaps``: in float32 no generated token may sit under
the reference's best at all.
"""

import json
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.agreement import logit_gaps, rel_err
from chipbench.reference import granite_hybrid as ref
from defer_tpu.models import granite_hybrid, granite_hybrid_tiny
from defer_tpu.models.cohere_moe import tie_head
from defer_tpu.models.decoder import (DecoderBlock, StateSpaceBlock,
                                      decoder_parts)
from defer_tpu.models.granite_hybrid import (GraniteAttentionBlock,
                                             GraniteMambaBlock)
from defer_tpu.obs import REGISTRY
from defer_tpu.ops import ssm
from defer_tpu.ops.kv_cache import KVCacheFormat
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

VOCAB, SEQ, PLEN, NEW = 211, 32, 11, 9
TYPES = ("mamba", "mamba", "attention", "mamba") * 2
REF = dict(layer_types=TYPES, n_head=4, n_kv=2, head_dim=16, mamba_heads=8,
           d_state=16, top_k=3, held=(0, 8), attention_multiplier=0.1,
           residual_multiplier=0.35, embedding_multiplier=6.0,
           logits_scaling=4.0, eps=1e-5)
REF_CFG = {"module": "chipbench.reference.granite_hybrid", "args": REF}
RTOL = 2e-4
KINDS = ("ssm", "ssm", "kv_cache", "ssm") * 2
STATS = ("moe.assignments", "moe.held_assignments", "moe.experts_hit",
         "moe.load_max", "ssm.updates")


@pytest.fixture(scope="module")
def model():
    graph = granite_hybrid_tiny(seq_len=SEQ, vocab=VOCAB)
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


def _forward(graph, params, ids):
    """The graph on every sequence (a grouped product takes no
    ``vmap``: a sequence a call)."""
    fn = jax.jit(graph.apply)
    return jnp.stack([fn(params, jnp.asarray(row)) for row in ids])


# -- the full-sequence graph, and the reference against itself -------------------

def test_full_sequence_logits_match_the_reference(model, ids):
    graph, params = model
    want = ref.logits(params, ids, **REF)
    assert rel_err(_forward(graph, params, ids), want) < RTOL


def test_the_tolerance_tells_the_routers_rule_apart(model, ids):
    """The reference with its weights a softmax over all eight experts
    (OLMoE's rule) is another model by far more than ``RTOL``."""
    graph, params = model
    other = ref.logits(params, ids, renormalise_over_all=True, **REF)
    assert rel_err(_forward(graph, params, ids), other) > 50 * RTOL


@pytest.mark.parametrize("state_dtype, least, most", [
    (None, 0.0, 1e-5), (jnp.bfloat16, 1e-3, 1.0)], ids=["f32", "bf16"])
def test_the_references_recurrence_is_its_explicit_sum(state_dtype, least,
                                                       most):
    """The oracle against itself: the recurrence position by position
    holds the closed form's state; rounded to bfloat16 after every
    position it does not (what the benchmark's control shows)."""
    rng = np.random.default_rng(0)
    b, t, nh, p, n = 2, 96, 4, 8, 16
    dt = jnp.asarray(rng.uniform(0.0, 0.02, (b, t, nh)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, t, nh, p)), jnp.float32)
    bm, cm = (jnp.asarray(rng.normal(size=(b, t, n)), jnp.float32)
              for _ in range(2))
    a = -jnp.asarray(rng.uniform(0.25, 1.0, (nh,)), jnp.float32)
    _, h = ref.selective_scan(dt, x, bm, cm, a, state_dtype=state_dtype)
    assert least <= rel_err(h, ref.explicit_state(dt, x, bm, a)) <= most


# -- the state's second shape: kernels against the plain oracle ------------------

def _inputs(seed, b, t, nh, p, n, g=1):
    """``g`` B/C groups: ``B`` and ``C`` ``[b, t, g * n]``, a group
    after the other."""
    rng = np.random.default_rng(seed)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(b, t, nh)) - 2.0,
                                     jnp.float32))
    x, bm, cm = (jnp.asarray(rng.normal(size=s), jnp.float32)
                 for s in ((b, t, nh * p), (b, t, g * n), (b, t, g * n)))
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (nh,)), jnp.float32)
    return dt, x, bm, cm, a


@pytest.mark.parametrize("t, nh, p, n, g", [
    (3, 8, 16, 16, 1), (19, 8, 16, 16, 1), (40, 8, 16, 16, 1),
    (24, 2, 128, 8, 1), (20, 4, 64, 128, 1), (19, 8, 32, 16, 2),
    (16, 8, 32, 16, 2), (40, 16, 32, 16, 4), (12, 128, 64, 128, 8)],
    ids=["shorter-than-d_conv", "ends-inside-a-chunk", "five-chunks",
         "wide-heads", "cells-head", "two-groups-ends-inside-a-chunk",
         "two-groups-ends-at-a-chunk", "four-groups-five-chunks",
         "eight-groups-published-shape"])
def test_ssd_scan_is_the_recurrence_position_by_position(t, nh, p, n, g):
    """The prefill kernel (interpret mode, chunks of 8) against the
    plain oracle: a prompt shorter than the convolution, one that is no
    multiple of the chunk, one whose state crosses four chunk
    boundaries in VMEM; heads of a lane tile and more, and heads of 64
    x 128 states as the cell has them; with B/C groups — a block of
    channels reading its own group's ``B`` and ``C`` — a prompt that
    ends inside a chunk and one that ends at a chunk's end, and
    Nemotron-3-Super's published shape: 128 heads of 64 in 8 groups of
    1024 channels, 128 states."""
    b = 1 if g == 8 else 2
    dt, x, bm, cm, a = _inputs(t, b, t, nh, p, n, g)
    y, h = ssm.ssd_scan(dt, jnp.repeat(dt, p, -1) * x, bm, cm, a, chunk=8,
                        bc_groups=g)
    want_y, want_h = ssm.ssd_prefill_reference(dt, x, bm, cm, a,
                                               bc_groups=g)
    assert y.shape == (b, t, nh * p) and h.shape == (b, n, nh * p)
    assert rel_err(y, want_y) < 1e-5 and rel_err(h, want_h) < 1e-5
    if g > 1:
        # every head reading the first group's B and C is another
        # recurrence: the comparison sees the groups
        other, _ = ssm.ssd_prefill_reference(dt, x, bm[..., :n],
                                             cm[..., :n], a)
        assert rel_err(y, other) > 0.1


@pytest.mark.parametrize("batch, nh, p, n, g", [
    (2, 8, 16, 16, 1), (8, 8, 16, 16, 1), (2, 8, 32, 16, 2),
    (8, 16, 32, 16, 4), (8, 128, 64, 128, 8)],
    ids=["2", "8", "two-groups", "four-groups",
         "eight-groups-published-shape"])
def test_ssd_step_updates_its_group_in_place(batch, nh, p, n, g):
    """The decode kernel against the plain oracle, on (ring) group 1 of
    2: the other group's state is not touched.  With B/C groups a block
    of channels reads its own group's ``B`` and ``C``: two and four
    groups of 128 channels, and the published 8 groups of 1024 (a block
    of 1024 channels a group, 8 sequences a grid step)."""
    dt, x, bm, cm, a = _inputs(batch, batch, 1, nh, p, n, g)
    rng = np.random.default_rng(1)
    state = jnp.asarray(rng.normal(size=(2, batch, n, nh * p)), jnp.float32)
    fmt = ssm.SsdFormat(nh, p, n, 4, 8, jnp.float32, groups=2, bc_groups=g)
    assert fmt.conv_width == nh * p + 2 * g * n
    layer = {"h": state, "conv": jnp.zeros((2, 3, batch, fmt.conv_width))}
    y, after = fmt.step(dt[:, 0], x[:, 0], bm[:, 0], cm[:, 0], a, layer,
                        group=1)
    want_y, want_h = ssm.ssd_step_reference(dt[:, 0], x[:, 0], bm[:, 0],
                                            cm[:, 0], a, state[1])
    assert rel_err(y, want_y) < 1e-5
    assert rel_err(after["h"][1], want_h) < 1e-5
    np.testing.assert_array_equal(after["h"][0], state[0])


@pytest.mark.parametrize("plen, p, g", [
    (2, 16, 1), (11, 16, 1), (2, 32, 2), (11, 32, 2), (8, 32, 2)],
    ids=["2", "11", "two-groups-2", "two-groups-11",
         "two-groups-ends-at-a-chunk"])
def test_the_format_prefills_then_steps_like_one_long_prefill(plen, p, g):
    """A prompt through ``prefill_shift`` / ``prefill`` and the rest a
    token at a time through ``shift`` / ``step``: the taps and outputs
    of one prefill over everything (a prompt shorter than ``d_conv``,
    and one that crosses a chunk boundary); with two B/C groups too,
    and there a prompt that ends where a chunk ends."""
    nh, n, t = 8, 16, 16
    dt, x, bm, cm, a = _inputs(7, 2, t, nh, p, n, g)
    rng = np.random.default_rng(2)
    fmt = ssm.SsdFormat(nh, p, n, 4, 8, jnp.float32, bc_groups=g)
    u = jnp.asarray(rng.normal(size=(2, t, fmt.conv_width)), jnp.float32)
    empty = fmt.layer(fmt.zeros(2, 1), 0)
    all_taps, _ = fmt.prefill_shift(u, empty)
    all_y, _ = fmt.prefill(dt, x, bm, cm, a, empty)
    taps, layer = fmt.prefill_shift(u[:, :plen], empty)
    y, layer = fmt.prefill(dt[:, :plen], x[:, :plen], bm[:, :plen],
                           cm[:, :plen], a, layer)
    assert rel_err(y, all_y[:, :plen]) < 1e-5
    for pos in range(plen, t):
        taps, layer = fmt.shift(u[:, pos], layer)
        for j, tap in enumerate(taps):
            np.testing.assert_array_equal(tap, all_taps[j][:, pos])
        y, layer = fmt.step(dt[:, pos], x[:, pos], bm[:, pos], cm[:, pos],
                            a, layer)
        assert rel_err(y, all_y[:, pos]) < 1e-5


@pytest.mark.parametrize("p, g", [(16, 1), (32, 2)],
                         ids=["one-group", "two-groups"])
def test_a_bubble_leaves_the_window_and_the_state_bit_for_bit(p, g):
    nh, n = 8, 16
    dt, x, bm, cm, a = _inputs(3, 2, 4, nh, p, n, g)
    fmt = ssm.SsdFormat(nh, p, n, 4, 8, jnp.float32, groups=1, bc_groups=g)
    u = jnp.concatenate([x, bm, cm], axis=-1)
    layer = fmt.layer(fmt.zeros(2, 1), 0)
    _, layer = fmt.prefill_shift(u, layer, fmt.prefill_slot(True, 0))
    _, layer = fmt.prefill(dt, x, bm, cm, a, layer,
                           fmt.prefill_slot(True, 0))
    assert np.asarray(layer["h"]).any() and np.asarray(layer["conv"]).any()
    bubble = fmt.decode_slot(False, 0)
    _, after = fmt.shift(u[:, 0], layer, group=0, valid=bubble)
    _, after = fmt.step(dt[:, 0], x[:, 0], bm[:, 0], cm[:, 0], a, after,
                        group=0, valid=bubble)
    _, after = fmt.prefill_shift(u, after, fmt.prefill_slot(False, 0))
    _, after = fmt.prefill(dt, x, bm, cm, a, after,
                           fmt.prefill_slot(False, 0))
    for key in ("conv", "h"):
        assert np.asarray(after[key]).tobytes() == \
            np.asarray(layer[key]).tobytes()


def test_the_two_shapes_share_their_buffers_and_their_window():
    """Mamba-1's format and Mamba-2's at one size keep the same ``h``
    and differ in the window's width alone; the window's calls are one
    implementation."""
    one = ssm.SsmFormat(128, 16, 4, jnp.bfloat16, groups=2)
    two = ssm.SsdFormat(8, 16, 16, 4, 8, jnp.bfloat16, groups=2)
    assert one.buffers(4)["h"] == two.buffers(4)["h"]
    assert one.buffers(4)["conv"].shape == (2, 3, 4, 128)
    assert two.buffers(4)["conv"].shape == (2, 3, 4, 128 + 2 * 16)
    assert two.keys == one.keys == ("conv", "h")
    for name in ("shift", "prefill_shift", "decode_slot", "prefill_slot"):
        assert getattr(ssm.SsmFormat, name) is getattr(ssm.SsdFormat, name)
    h, window = ssm.dense(np.zeros((4, 16, 128)), np.zeros((3, 4, 160)),
                          heads=8)
    assert h.shape == (4, 8, 16, 16) and window.shape == (4, 3, 160)


def test_one_group_is_the_format_before_groups():
    """``bc_groups`` 1 is the default and names the format every older
    family has: the same buffers, the same window, a kernel's block
    reading columns 0 by a constant (so the calls lower to what they
    were); more groups widen the window alone, and a group that no lane
    tile of channels divides is refused by message."""
    one = ssm.SsdFormat(8, 16, 16, 4, 8, jnp.bfloat16, groups=2)
    assert one == ssm.SsdFormat(8, 16, 16, 4, 8, jnp.bfloat16, groups=2,
                                bc_groups=1)
    assert one.gauges(4, 2)["decode.ssm.bc_groups"] == 1
    two = ssm.SsdFormat(8, 32, 16, 4, 8, jnp.bfloat16, groups=2, bc_groups=2)
    assert two.buffers(4)["h"].shape == (2, 4, 16, 256)
    assert two.buffers(4)["conv"].shape == (2, 3, 4, 256 + 2 * 2 * 16)
    assert two.gauges(4, 2)["decode.ssm.bc_groups"] == 2
    assert ssm._group_of_block(8192, 1024, 1)(5) == 0
    assert [ssm._group_of_block(8192, 512, 8)(j) for j in (0, 1, 2, 15)] \
        == [0, 0, 1, 7]
    assert ssm._block_in_group(8192, 8, [1024, 512]) == 1024
    assert ssm._block_in_group(8192, 16, [1024, 512]) == 512
    assert ssm._block_in_group(100, 1, []) == 100
    with pytest.raises(ValueError, match="lies inside one group"):
        ssm._block_in_group(128, 2, [128])
    with pytest.raises(ValueError, match="do not form"):
        ssm.SsdFormat(8, 16, 16, 4, 8, jnp.bfloat16, bc_groups=3)


# -- the ring against the reference -----------------------------------------------

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
    never an input); the attention layer keeps a key row a position and
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
                              dec.state["conv"][l][0, 0], heads=8)
        assert h.shape == (4, 8, 16, 16) and window.shape == (4, 3, 160)
        assert rel_err(h, want[l][0]) < RTOL
        assert rel_err(window, want[l][1]) < RTOL
    # one position off, the window is another: the comparison sees it
    off = ref.states(params, out[:, :-1], window_shift=1, **REF)
    assert rel_err(ssm.dense(dec.state["h"][0][0, 0],
                             dec.state["conv"][0][0, 0], 8)[1],
                   off[0][1]) > 0.1


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
    # the widest activation is the input projection's 2 x 128 + 2 x 16
    # + 8 columns
    monkeypatch.setattr(decode, "_PREFILL_PIECE_BYTES", 2 * PLEN * 296 * 4)
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
    ({"beam_width": 2}, "beam search re-parents.*keep a ssm .SsdFormat."),
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
    with pytest.raises(TypeError, match=r"block_0 \(GraniteMambaBlock\) is "
                       "not a CausalTransformerBlock"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


# -- a share of a layer's experts ---------------------------------------------------

def _share(params, lo, hi):
    """``params`` of the whole layer cut to the experts ``lo .. hi - 1``."""
    return dict(params, experts={k: v[lo:hi]
                                 for k, v in params["experts"].items()})


@pytest.mark.parametrize("name", ["block_0", "block_2"],
                         ids=["mamba", "attention"])
def test_the_shares_add_up_to_the_whole_layer(model, ids, name):
    """One layer's two routed parts — experts ``[0, 4)`` and ``[4, 8)``,
    each routing over all eight and keeping the full choice's weights —
    plus the shared expert counted once are the uncut layer, in the
    program and against the reference's uncut layer (every branch
    enters under the residual multiplier)."""
    graph, params = model
    p = params[name]
    whole = graph.nodes[name].op
    x = 0.5 * jax.random.normal(jax.random.key(9), (2, 12, 64), jnp.float32)
    y_whole = whole.apply(p, x)
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        op = type(whole)(**{**vars(whole), "experts_held": (lo, hi)})
        assert op.held == (lo, hi)
        parts.append(op.apply(_share(p, lo, hi), x))
    # neither share holds the shared expert or the experts alone: with
    # no routed expert at all a layer is ``alone``
    zero = jax.tree.map(jnp.zeros_like, p["experts"])
    alone = whole.apply(dict(p, experts=zero), x)
    assert rel_err(parts[0] + parts[1] - alone, y_whole) < RTOL
    assert rel_err(parts[0], y_whole) > 50 * RTOL
    kind = TYPES[int(name.split("_")[1])]
    args = {k: v for k, v in REF.items() if k not in (
        "layer_types", "embedding_multiplier", "logits_scaling")}
    want, _, chosen = ref.block(p, x, kind=kind, **args)
    assert rel_err(y_whole, want) < RTOL
    # and a share is the reference's share, choices over all eight
    half, _, half_chosen = ref.block(_share(p, 4, 8), x, kind=kind,
                                     **dict(args, held=(4, 8)))
    assert rel_err(parts[1], half) < RTOL
    np.testing.assert_array_equal(chosen, half_chosen)
    sown: dict = {}
    whole.apply(p, x, sow=sown)
    np.testing.assert_array_equal(
        np.sort(np.asarray(sown["moe.chosen"]).reshape(2, 12, 3), -1),
        np.sort(np.asarray(chosen), -1))


def test_a_ring_of_shares_is_the_references_share(ids):
    """The ring on a graph that holds experts ``[2, 6)``: prefill then
    decode gives the reference's tokens under the same share."""
    graph = granite_hybrid_tiny(seq_len=SEQ, vocab=VOCAB, experts_held=(2, 6))
    params = tie_head(graph.init(jax.random.key(4)))
    assert params["block_0"]["experts"]["gate"].shape == (4, 64, 32)
    assert params["block_0"]["router"]["w"].shape == (64, 8)
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    out = dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
    cfg = {"module": REF_CFG["module"], "args": dict(REF, held=(2, 6))}
    assert logit_gaps(params, out, PLEN, cfg).max() <= 0


# -- counters, gauges, the contract ------------------------------------------------

def test_the_counters_and_gauges_by_kind(model, ids):
    graph, params = model
    names = ["decode." + s for s in STATS]
    for n in (1, 2):
        dec = PipelinedDecoder(graph, params, num_stages=n,
                               microbatch=4 // n, max_len=SEQ)
        before = {nm: REGISTRY.counter(nm).n for nm in names}
        dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
        got = {nm: REGISTRY.counter(nm).n - before[nm] for nm in names}
        # positions PLEN .. PLEN+NEW-2 are decoded by 6 Mamba layers (an
        # attention layer sows 0 updates); on two stages the schedule's
        # last step hands stage 0 the last token of group 0: 2
        # sequences, its 4 layers, 3 of them Mamba
        extra = 2 if n == 2 else 0
        assert got["decode.ssm.updates"] == 4 * 6 * (NEW - 1) + 3 * extra
        # every layer routes 3 choices a sequence a step, all held
        assert got["decode.moe.assignments"] == \
            3 * (4 * 8 * (NEW - 1) + 4 * extra)
        assert got["decode.moe.held_assignments"] == \
            got["decode.moe.assignments"]
        # a layer's step of one group hits at most its 8 experts
        layer_steps = got["decode.moe.assignments"] // (3 * (4 // n))
        assert 0 < got["decode.moe.experts_hit"] <= 8 * layer_steps
    h = 4 * 6 * 16 * 128 * 4
    conv = 4 * 6 * 3 * 160 * 4
    assert REGISTRY.gauge("decode.ssm.state_bytes").value == h + conv
    assert REGISTRY.gauge("decode.ssm.conv_bytes").value == conv
    # an attention layer a stage: two groups and the scratch group of 2
    # sequences, SEQ rows and the scratch row of two heads of 16, keys
    # and values
    full = 2 * (2 + 1) * 2 * (SEQ + 1) * 2 * 16 * 4 * 2
    assert REGISTRY.gauge("decode.kv_cache.state_bytes").value == full
    assert REGISTRY.gauge("decode.cache.full_bytes").value == full


def test_the_blocks_declare_their_memory(model):
    graph, _ = model
    mamba, attn = graph.nodes["block_0"].op, graph.nodes["block_2"].op
    assert isinstance(mamba, GraniteMambaBlock) \
        and isinstance(mamba, StateSpaceBlock) \
        and isinstance(mamba, DecoderBlock)
    assert isinstance(attn, GraniteAttentionBlock) \
        and not isinstance(attn, StateSpaceBlock)
    assert (mamba.memory, attn.memory) == ("ssm", "kv_cache")
    assert mamba.geometry(64) is None and attn.geometry(64) == (4, 2, 16)
    # the input projection's z, x B C and dt
    assert mamba.mixer_width == 128 + 160 + 8 == mamba.widest(64)
    assert attn.widest(64) == 64
    assert mamba.memory_format(64, SEQ, jnp.bfloat16, groups=2) == \
        ssm.SsdFormat(8, 16, 16, 4, 8, jnp.bfloat16, groups=2)
    assert mamba.decode_stats == attn.decode_stats == STATS
    assert decoder_parts(graph, 2).decode_stats == STATS
    # every leaf is an argument of its own on the ring, the norms'
    # scales with the matrices and the mixer's vectors
    params = graph.init(jax.random.key(0))
    for name, norms in (("block_0", {"ln1", "ln2", "gate_norm"}),
                        ("block_2", {"ln1", "ln2"})):
        assert norms < set(params[name])
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    for tree, nm in zip(dec._w["blocks"], dec.stage_blocks[1], strict=True):
        assert jax.tree.structure(tree) == jax.tree.structure(params[nm])
    assert params["block_0"]["in_proj"]["w"].shape == (64, 296)
    assert params["block_0"]["conv"]["w"].shape == (4, 160)
    assert {k: v.shape for k, v in params["block_0"]["ssm"].items()} == {
        "a_log": (8,), "dt_bias": (8,), "d": (8,)}


def test_the_four_multipliers_enter_where_the_family_says(model, ids):
    """Each multiplier changed alone moves the reference and the program
    together (the tiny graph's are none of them 1)."""
    _, params = model
    base = dict(embedding_multiplier=6.0, residual_multiplier=0.35,
                attention_multiplier=0.1, logits_scaling=4.0)
    for key, value in (("embedding_multiplier", 3.0),
                       ("residual_multiplier", 0.7),
                       ("attention_multiplier", 0.3),
                       ("logits_scaling", 2.0)):
        args = dict(base, **{key: value})
        graph = granite_hybrid(
            8, 64, 4, 2, 16, SEQ, VOCAB, TYPES[:4], 8, 16, 16, 8, 3, 32, 64,
            mamba_chunk=8, **args)
        got = _forward(graph, params, ids[:1])
        assert rel_err(got, ref.logits(params, ids[:1],
                                       **dict(REF, **args))) < RTOL
        assert rel_err(got, ref.logits(params, ids[:1], **REF)) > 50 * RTOL


def test_the_published_geometry_attends_over_joined_rows(ids):
    """32 query heads on 8 KV heads of 128 are four queries a KV head:
    a group for the matrix unit (``ops/kv_cache.py::_JOINED_GROUP``),
    so the cell's attention layer holds its rows joined — 3072
    positions and the scratch row in whole sublane tiles, 2 KB a
    position — and a ring step of that geometry (two layers, a narrow
    stream) names ``kv_attend`` once, beside the gauge that says which
    of the two kernels of that name it is; its tokens are the
    reference's, prompt and steps over joined rows."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "chipbench", "configs",
                           "granite-4.0-h-small-10l-ep2.json")) as f:
        args = json.load(f)["model_args"]
    attn = granite_hybrid(**args).nodes["block_5"].op
    assert attn.geometry(args["hidden"]) == (32, 8, 128)
    fmt = attn.memory_format(args["hidden"], 3072, jnp.bfloat16, groups=1)
    assert fmt == KVCacheFormat(8, 128, 3072, jnp.bfloat16, groups=1,
                                query_group=4)
    assert fmt.joined and not fmt.writes_in_attention
    assert fmt.buffers(64)["k"].shape == (2, 64, 3088, 1024)

    graph = granite_hybrid(
        2, 64, 8, 2, 128, SEQ, VOCAB, ("mamba", "attention"), 8, 16, 16,
        8, 3, 32, 64, mamba_chunk=8)
    params = tie_head(graph.init(jax.random.key(0)))
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                           max_len=SEQ)
    a, caches = dec._init_state()
    assert caches["k"][1].shape == (1, 2, 2, 48, 256)   # stage, groups, ...
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
    assert calls == ["kv_attend"]
    assert REGISTRY.gauge("decode.kv.joined_layers").value == 1
    assert REGISTRY.gauge("decode.kv.fused_layers").value == 0
    narrow = {"module": REF_CFG["module"], "args": dict(
        REF, layer_types=("mamba", "attention"), n_head=8, head_dim=128,
        attention_multiplier=1 / math.sqrt(128), residual_multiplier=1.0,
        embedding_multiplier=1.0, logits_scaling=1.0)}
    out = dec.generate(ids[:2, :PLEN], NEW, prefill=True)
    assert logit_gaps(params, out, PLEN, narrow).max() <= 0
    np.testing.assert_array_equal(
        dec.generate(ids[:2, :PLEN], NEW, prefill=False), out)
