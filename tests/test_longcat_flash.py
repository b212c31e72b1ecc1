"""The LongCat-Flash family (models/longcat_flash.py): a block that is a
double layer — two latent-attention sublayers, two latent caches, around
one shortcut-connected MoE whose router chooses by a biased softmax among
routed experts (a share of them held) and zero-compute ones: held to the
plain reference (chipbench/reference/longcat_flash.py), on the ring and
off it; and Kimi's programs, which share the attention half, unchanged."""

import hashlib
import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.agreement import logit_gaps, rel_err
from defer_tpu.models import kimi_k2_tiny, longcat_flash, longcat_flash_tiny
from defer_tpu.models.decoder import DecoderBlock, LatentBlock, decoder_parts
from defer_tpu.models.kimi_k2 import KimiMoeBlock
from defer_tpu.models.latent_attention import LatentAttention
from defer_tpu.models.longcat_flash import LongcatFlashBlock
from defer_tpu.obs import REGISTRY
from defer_tpu.ops import latent_cache
from defer_tpu.ops.routed import (SCORING_RULES, expert_dispatch_held,
                                  route_top_k, zero_expert_pairs)
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

ref = importlib.import_module("chipbench.reference.longcat_flash")

VOCAB, SEQ, PLEN, NEW = 211, 32, 11, 9
REF = dict(n_layer=4, n_head=4, nope=16, rope=8, latent=32, q_rank=24,
           n_experts=16, top_k=4, routed_scale=6.0, theta=1e7, held=(0, 4),
           eps=1e-5)
REF_CFG = {"module": "chipbench.reference.longcat_flash", "args": REF}
RTOL = 2e-4
STATS = ("moe.assignments", "moe.held_assignments", "moe.experts_hit",
         "moe.load_max", "moe.zero_assignments", "moe.real_assignments")


def make(held=(0, 4), seed=3, dtype=None):
    graph = longcat_flash_tiny(seq_len=SEQ, vocab=VOCAB, experts_held=held)
    params = graph.init(jax.random.key(seed))
    # wider embedding rows and a bias large enough to turn choices:
    # tokens differ at the router
    params = dict(params,
                  embeddings={"wte": params["embeddings"]["wte"] * 50})
    for i in range(4):
        blk = dict(params[f"block_{i}"])
        blk["router"] = dict(blk["router"], bias=blk["router"]["bias"] * 30)
        params[f"block_{i}"] = blk
    if dtype is not None:
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    return graph, params


@pytest.fixture(scope="module")
def model():
    return make()


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
    fn = jax.jit(graph.apply)
    return jnp.stack([fn(params, jnp.asarray(row)) for row in ids])


def ref_forward(params, ids, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, ids, **dict(REF, **kw))


def _through_both_caches(graph, params, seqs, dtype):
    """The blocks' rounds as the ring composes them: a prompt of
    ``PLEN`` through ``prefill`` (expanded heads, both sublayers' rows
    bulk-written), then the rest a token at a time through ``decode``
    (two turns around the two caches a block, group 1 of two): every
    position's logits."""
    nodes = graph.nodes
    n = len([nm for nm in nodes if nm.startswith("block_")])
    fmts = [nodes[f"block_{i}"].op.memory_format(64, SEQ, dtype, groups=2)
            for i in range(n)]
    caches = [f.layer(f.zeros(4, 1), 0) for f in fmts]

    def head(x):
        h = nodes["final_ln"].op.apply(params["final_ln"], x)
        return nodes["lm_head"].op.apply(params["lm_head"], h)

    # jitted, as the ring runs them
    op, fmt = nodes["block_0"].op, fmts[0]
    assert all(f == fmt for f in fmts)
    prefill = jax.jit(lambda p, x, c: op.prefill(
        p, x, c, fmt, fmt.prefill_slot(True, 1)))
    decode = jax.jit(lambda p, x, c, pos: op.decode(
        p, x, c, pos, fmt, fmt.decode_slot(True, pos), 1))
    x = nodes["embeddings"].op.apply(params["embeddings"], seqs[:, :PLEN])
    for i in range(n):
        x, caches[i] = prefill(params[f"block_{i}"], x, caches[i])
    got = [head(x)]
    for pos in range(PLEN, seqs.shape[1]):
        x = nodes["embeddings"].op.embed_at(params["embeddings"],
                                            seqs[:, pos], pos)
        for i in range(n):
            x, caches[i] = decode(params[f"block_{i}"], x, caches[i],
                                  jnp.int32(pos))
        got.append(head(x)[:, None])
    return jnp.concatenate(got, axis=1)


# -- the graph against the reference -------------------------------------------------

@pytest.mark.parametrize("dtype, tol", [(None, RTOL), (jnp.bfloat16, 0.2)],
                         ids=["float32", "bfloat16"])
def test_full_sequence_logits_match_the_reference(ids, dtype, tol):
    graph, params = make(dtype=dtype)
    want, _ = ref_forward(params, ids)
    assert rel_err(_forward(graph, params, ids), want) < tol


@pytest.mark.parametrize("weights", ["float32", "bfloat16"])
def test_prefill_then_steps_through_both_caches_give_the_references_logits(
        ids, weights):
    """Every position's *logits* are the reference's full forward's,
    which sees no cache.  ``bfloat16``: the weights as the chip holds
    them, rounded to bfloat16, under float32 products — the CPU's
    runtime has no batched product of two bfloat16 operands into
    float32 (the absorbed query's, Kimi's too), which only the chip
    runs in that type; the full-sequence case above runs in bfloat16
    throughout."""
    graph, params = make(dtype=None if weights == "float32"
                         else jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    seqs = ids[:, :PLEN + 12]
    want, _ = ref_forward(params, seqs)
    got = _through_both_caches(graph, params, seqs, jnp.float32)
    assert rel_err(got, want) < RTOL


def test_the_reference_tells_the_controls(model, ids):
    """The controls' rules are other models: no zero-compute experts,
    renormalised weights, a bias that weighs and no LoRA scales all
    move the logits."""
    graph, params = model
    got = _forward(graph, params, ids)
    for control in ("no_zero_experts", "renormalise", "bias_weighs",
                    "plain_lora"):
        assert rel_err(got, ref_forward(params, ids, **{control: True})[0]) \
            > 20 * RTOL, control


@pytest.mark.parametrize("sublayer", [0, 1])
def test_the_absorbed_step_equals_the_expanded_form(model, sublayer):
    """One sublayer under the LoRA scales, float32: the expanded prompt
    form's last position is the absorbed step of that token over the
    rows of the positions before it, and the step writes the row the
    prompt's path would have — into that sublayer's buffer alone."""
    graph, params = model
    op, p = graph.nodes["block_1"].op, params["block_1"]
    assert op.q_scale == pytest.approx((64 / 24) ** 0.5)
    assert op.latent_scale == pytest.approx(2 ** 0.5)
    attn = p[f"attn_{sublayer}"]
    t = 13
    x = jax.random.normal(jax.random.key(7), (3, t, 64), jnp.float32)
    q_n, q_r, rows = op._q_rows(attn, x, jnp.arange(t))
    want = op._expanded(attn, q_n, q_r, rows)[:, -1]
    fmt = op.memory_format(64, SEQ, jnp.float32)
    assert fmt.sublayers == 2 and fmt.keys == ("latent", "latent_1")
    cache = fmt.write_prefix(fmt.layer(fmt.zeros(3, 1), 0), rows[:, :t - 1],
                             0, sublayer=sublayer)
    q, row = op.round_q_row(p, x[:, -1], jnp.int32(t - 1), sublayer)
    att, cache = fmt.step(q, cache, fmt.rows(row), jnp.int32(t - 1),
                          sublayer=sublayer)
    got = op._out_of_latent(attn, x[:, -1], att)
    assert rel_err(got, want) < 1e-5
    key, other = fmt.keys[sublayer], fmt.keys[1 - sublayer]
    np.testing.assert_allclose(cache[key][:, t - 1, :fmt.width],
                               rows[:, t - 1], rtol=1e-5, atol=1e-6)
    assert not np.asarray(cache[other]).any()
    # the latent's scale lives in the row: without it the row is Kimi's
    plain = type(op)(**{**vars(op), "latent_scale": 1.0})
    np.testing.assert_allclose(
        rows[..., :32], plain._q_rows(attn, x, jnp.arange(t))[2][..., :32]
        * op.latent_scale, rtol=1e-5)


def test_a_block_step_is_the_blocks_prompt_form(model):
    """The whole double layer: the last position of ``apply_with_rows``
    is ``decode`` of that token over both caches filled with the rows of
    the positions before it — the shortcut's output carried across the
    second turn."""
    graph, params = model
    op, p = graph.nodes["block_2"].op, params["block_2"]
    t = 10
    x = jax.random.normal(jax.random.key(8), (3, t, 64), jnp.float32)
    want, rows = op.apply_with_rows(p, x)
    assert isinstance(rows, tuple) and len(rows) == 2
    assert rel_err(rows[0], rows[1]) > 0.1
    fmt = op.memory_format(64, SEQ, jnp.float32)
    cache = fmt.layer(fmt.zeros(3, 1), 0)
    # the second sublayer's rows depend on the first's output: fill both
    # from the prompt's path
    for i in range(2):
        cache = fmt.write_prefix(cache, rows[i][:, :t - 1], 0, sublayer=i)
    got, cache = op.decode(p, x[:, -1], cache, jnp.int32(t - 1), fmt)
    assert rel_err(got, want[:, -1]) < 1e-5
    for i, key in enumerate(fmt.keys):
        np.testing.assert_allclose(cache[key][:, t - 1, :fmt.width],
                                   rows[i][:, t - 1], rtol=1e-5, atol=1e-6)


# -- the router and the three fates of a pair -----------------------------------------

def test_the_new_rule_against_a_hand_count():
    """Softmax over real and zero columns; the bias chooses and never
    weighs; the chosen are scaled and not renormalised; an id past the
    routed experts is a zero-compute expert."""
    logits = jnp.log(jnp.asarray([[8.0, 4.0, 2.0, 1.0, 0.5, 0.5]]))
    p = np.asarray([8.0, 4.0, 2.0, 1.0, 0.5, 0.5]) / 16
    eid, w = route_top_k(logits, 2, "softmax_bias", bias=jnp.zeros(6),
                         scale=6.0)
    np.testing.assert_array_equal(eid, [[0, 1]])
    np.testing.assert_allclose(w, [[6 * p[0], 6 * p[1]]], rtol=1e-6)
    assert float(w.sum()) == pytest.approx(6 * 0.75)        # not 6
    # a bias turns the second choice to column 5 (a zero expert of 4
    # routed + 2 zero) and leaves its weight the softmax's own
    bias = jnp.asarray([0.0, -0.3, 0.0, 0.0, 0.0, 0.3])
    eid, w = route_top_k(logits, 2, "softmax_bias", bias=bias, scale=6.0)
    np.testing.assert_array_equal(eid, [[0, 5]])
    np.testing.assert_allclose(w, [[6 * p[0], 6 * p[5]]], rtol=1e-6)
    x = jnp.asarray([[1.0, -2.0, 3.0]])
    zero, count = zero_expert_pairs(x, eid, w, 4)
    assert int(count) == 1
    np.testing.assert_allclose(zero, 6 * p[5] * np.asarray(x), rtol=1e-6)
    # against random logits: the largest of p + b, weights scale * p
    logits = jax.random.normal(jax.random.key(0), (64, 24))
    bias = 0.05 * jax.random.normal(jax.random.key(1), (24,))
    eid, w = route_top_k(logits, 4, "softmax_bias", bias=bias, scale=6.0)
    plain, _ = route_top_k(logits, 4, "softmax_bias", bias=jnp.zeros(24),
                           scale=6.0)
    assert (np.sort(eid, -1) != np.sort(plain, -1)).any(-1).mean() > 0.2
    probs = jax.nn.softmax(logits, -1)
    np.testing.assert_array_equal(
        np.sort(eid, -1), np.sort(jax.lax.top_k(probs + bias, 4)[1], -1))
    np.testing.assert_allclose(
        w, 6.0 * jnp.take_along_axis(probs, eid, -1), rtol=1e-6)
    assert "softmax_bias" in SCORING_RULES


def test_a_zero_pair_is_never_dispatched():
    """To the held dispatcher an id past the routed experts is no
    expert's: no group counts it, and the product never sees its row."""
    x = jnp.arange(12.0).reshape(4, 3)
    eid = jnp.asarray([[0, 17], [16, 23], [1, 5], [20, 21]])
    gate = jnp.full((4, 2), 0.5)
    seen = []

    def expert_fn(xs, sizes):
        seen.append(sizes)
        return xs

    y, sizes = expert_dispatch_held(x, eid, gate, (0, 2), expert_fn)
    np.testing.assert_array_equal(sizes, [1, 1])
    np.testing.assert_allclose(y, 0.5 * np.asarray(x) * np.asarray(
        [[1], [0], [1], [0]]))
    zero, count = zero_expert_pairs(x, eid, gate, 16)
    assert int(count) == 5
    np.testing.assert_allclose(zero, np.asarray(x) * np.asarray(
        [[0.5], [1.0], [0.0], [1.0]]))


def _biased(p, bias):
    return dict(p, router=dict(p["router"], bias=jnp.asarray(bias)))


def test_a_zero_only_token_and_a_real_only_token(model):
    """All four choices zero-compute: the shortcut is the normed stream
    times the sum of the four weights, the experts multiply nothing.
    All four real: no zero pair, and without the experts nothing is
    left of the shortcut."""
    graph, params = model
    op, p = graph.nodes["block_1"].op, params["block_1"]
    h = jax.random.normal(jax.random.key(4), (9, 64), jnp.float32)
    no_experts = dict(p, experts=jax.tree.map(jnp.zeros_like, p["experts"]))
    to_zero = _biased(p, np.where(np.arange(24) >= 16, 10.0, 0.0))
    eid, w = op.route(to_zero, h)
    assert (np.asarray(eid) >= 16).all()
    got = op.shortcut(to_zero, h)
    np.testing.assert_allclose(got, h * np.asarray(w).sum(-1)[:, None],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        got, op.shortcut(_biased(no_experts, to_zero["router"]["bias"]), h),
        rtol=1e-6)
    sown: dict = {}
    op.apply(to_zero, h[None], sow=sown)
    assert int(sown["moe.zero_assignments"]) == 9 * 4
    assert int(sown["moe.real_assignments"]) == 0
    assert int(sown["moe.held_assignments"]) == 0
    # real only, and to the held four
    to_held = _biased(p, np.where(np.arange(24) < 4, 10.0, 0.0))
    eid, _ = op.route(to_held, h)
    assert (np.sort(np.asarray(eid), -1) == np.arange(4)).all()
    assert not np.asarray(op.shortcut(
        _biased(no_experts, to_held["router"]["bias"]), h)).any()
    assert np.abs(np.asarray(op.shortcut(to_held, h))).max() > 1e-2
    sown = {}
    op.apply(to_held, h[None], sow=sown)
    assert int(sown["moe.zero_assignments"]) == 0
    assert int(sown["moe.real_assignments"]) == 9 * 4
    assert int(sown["moe.held_assignments"]) == 9 * 4
    assert int(sown["moe.experts_hit"]) == 4 and int(sown["moe.load_max"]) == 9
    # real only, and to other chips': nothing is computed at all
    away = _biased(p, np.where((np.arange(24) >= 4) & (np.arange(24) < 16),
                               10.0, 0.0))
    assert not np.asarray(op.shortcut(away, h)).any()


def _share(params, lo, hi):
    return dict(params, experts={k: v[lo:hi]
                                 for k, v in params["experts"].items()})


def test_the_shares_add_up_to_the_uncut_layer():
    """16 routed experts over 4 shares, each routing over all 24 columns
    under the full choice's weights: the held parts, with the zero
    experts' part and the dense path (both attentions, both dense
    halves) counted once, are the uncut layer — the program's and the
    reference's."""
    graph, params = make(held=None)
    whole, p = graph.nodes["block_2"].op, params["block_2"]
    assert p["experts"]["gate"].shape[0] == 16 and whole.held == (0, 16)
    x = 0.5 * jax.random.normal(jax.random.key(9), (2, 12, 64), jnp.float32)
    y_whole = whole.apply(p, x)
    # the dense path and the zero experts' part alone: no routed expert
    alone = whole.apply(dict(p, experts=jax.tree.map(
        jnp.zeros_like, p["experts"])), x)
    parts = []
    for lo in range(0, 16, 4):
        op = type(whole)(**{**vars(whole), "experts_held": (lo, lo + 4)})
        parts.append(op.apply(_share(p, lo, lo + 4), x))
    assert rel_err(sum(parts) - 3 * alone, y_whole) < RTOL
    assert rel_err(parts[0], y_whole) > 20 * RTOL
    args = {k: REF[k] for k in ("n_head", "nope", "rope", "latent", "q_rank",
                                "n_experts", "top_k", "routed_scale", "eps")}
    freqs = ref.rope_frequencies(8, 1e7)
    with jax.default_matmul_precision("highest"):
        want, ex = ref.block(p, x, freqs, held=None, **args)
        part, ex1 = ref.block(_share(p, 4, 8), x, freqs, held=(4, 8), **args)
        bare, _ = ref.block(_share(p, 4, 8), x, freqs, held=(4, 8),
                            no_zero_experts=True, **args)
    assert rel_err(y_whole, want) < RTOL
    assert rel_err(parts[1], part) < RTOL
    assert rel_err(parts[1], bare) > 20 * RTOL
    np.testing.assert_array_equal(ex["chosen"], ex1["chosen"])
    # the zero experts' part, counted once: the sum of the shares'
    # shortcuts less three times the zero part is the whole shortcut
    zero_part = ex1["shortcut"] - ref.block(
        _share(p, 4, 8), x, freqs, held=(4, 8), no_zero_experts=True,
        **args)[1]["shortcut"]
    shares = [ref.block(_share(p, lo, lo + 4), x, freqs, held=(lo, lo + 4),
                        **args)[1]["shortcut"] for lo in range(0, 16, 4)]
    assert rel_err(sum(shares) - 3 * zero_part, ex["shortcut"]) < RTOL
    sown: dict = {}
    whole.apply(p, x, sow=sown)
    np.testing.assert_array_equal(
        np.sort(np.asarray(sown["moe.chosen"]).reshape(2, 12, 4), -1),
        np.sort(np.asarray(ex["chosen"]), -1))


def test_the_programs_router_and_shortcut_on_the_references_stream(model,
                                                                   ids):
    """``route`` and ``shortcut`` on the reference's own normed stream:
    the same columns, the same weights, the same ``s`` (what the
    benchmark's check holds the chip's bfloat16 to)."""
    graph, params = model
    _, extras = ref_forward(params, ids, keep=("chosen", "weights", "ffn_in",
                                               "shortcut"))
    zero = np.mean([(ex["chosen"] >= 16).mean() for ex in extras])
    assert 0.2 < zero < 0.5                 # 8 of 24 columns
    for i in range(4):
        op, p = graph.nodes[f"block_{i}"].op, params[f"block_{i}"]
        h = jnp.asarray(extras[i]["ffn_in"]).reshape(-1, 64)
        eid, w = op.route(p, h)
        order = np.argsort(np.asarray(eid), -1)
        want = np.argsort(extras[i]["chosen"].reshape(-1, 4), -1)
        np.testing.assert_array_equal(
            np.take_along_axis(np.asarray(eid), order, -1),
            np.take_along_axis(extras[i]["chosen"].reshape(-1, 4), want, -1))
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(w), order, -1),
            np.take_along_axis(extras[i]["weights"].reshape(-1, 4), want, -1),
            rtol=1e-4)
        assert rel_err(op.shortcut(p, h),
                       extras[i]["shortcut"].reshape(-1, 64)) < RTOL


# -- the ring against the reference ----------------------------------------------------

def test_prefill_then_decode_is_the_references_full_forward(model, ids,
                                                            generated):
    _, params = model
    out, _ = generated
    assert out.shape == (4, PLEN + NEW)
    np.testing.assert_array_equal(out[:, :PLEN], ids[:, :PLEN])
    assert logit_gaps(params, out, PLEN, REF_CFG).max() <= 0


def test_the_ring_leaves_both_sublayers_rows_as_the_reference_holds_them(
        model, generated):
    """After the prefill and ``NEW - 1`` decode steps a layer's two
    buffers hold the reference's ``[c * scale, k_r]`` of every token
    that was an input, sublayer by sublayer."""
    _, params = model
    out, dec = generated
    assert dec.memory == ("latent_cache",) * 4
    assert set(dec.state) >= {"latent", "latent_1"}
    _, extras = ref_forward(params, out[:, :-1], keep=("rows", "rows_1"))
    for l in range(4):
        for key, want in (("latent", "rows"), ("latent_1", "rows_1")):
            buf = np.asarray(dec.state[key][l])
            assert buf.shape == (1, 2, 4, 48, 128)
            got = buf[0, 0, :, :PLEN + NEW - 1, :40]
            assert rel_err(got, extras[l][want]) < RTOL
            assert rel_err(got[:, PLEN:], extras[l][want][:, PLEN:]) < RTOL
            assert not buf[0, 0, :, :, 40:].any()
        assert rel_err(extras[l]["rows"], extras[l]["rows_1"]) > 0.1


@pytest.mark.parametrize("stages, chunk, prefill", [
    (1, 3, True), (1, 4, False), (2, None, True), (2, 2, True),
    (2, None, False)])
def test_the_tokens_do_not_depend_on_stages_chunks_or_the_prefill(
        model, ids, generated, stages, chunk, prefill):
    """Two stages of two double layers each: the cut falls between
    blocks, and the shortcut's output never crosses it."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=stages,
                           microbatch=4 // stages, max_len=SEQ)
    if stages == 2:
        assert dec.stage_blocks == [["block_0", "block_1"],
                                    ["block_2", "block_3"]]
        assert [fmt.sublayers for fmt in dec.state_formats] == [2, 2]
        # the ring's carry is one [mb, d] buffer
        assert dec._ring_width == 64
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=prefill, token_chunk=chunk),
        generated[0])


def test_a_prefill_in_pieces_is_the_prefill(model, ids, generated,
                                            monkeypatch):
    from defer_tpu.runtime import decode
    graph, params = model
    # the widest activation is the expanded keys and values' 4 x 32
    # columns
    monkeypatch.setattr(decode, "_PREFILL_PIECE_BYTES", 2 * PLEN * 128 * 4)
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    assert dec._prefill_rows(PLEN) == 2
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=True), generated[0])


def test_a_ring_of_another_share_is_the_references_share(ids):
    graph, params = make(held=(6, 10), seed=4)
    assert params["block_1"]["experts"]["gate"].shape == (4, 64, 32)
    assert params["block_1"]["router"]["w"].shape == (64, 24)
    assert params["block_1"]["router"]["bias"].shape == (24,)
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    out = dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
    cfg = {"module": REF_CFG["module"], "args": dict(REF, held=(6, 10))}
    assert logit_gaps(params, out, PLEN, cfg).max() <= 0


# -- counters, gauges, the contract ----------------------------------------------------

def test_the_counters_and_gauges_count_both_sublayers(model, ids):
    graph, params = model
    names = ["decode." + s for s in STATS]
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    before = {nm: REGISTRY.counter(nm).n for nm in names}
    dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
    got = {nm: REGISTRY.counter(nm).n - before[nm] for nm in names}
    # four double layers route 4 choices a sequence a step
    pairs = 4 * 4 * 4 * (NEW - 1)
    assert got["decode.moe.assignments"] == pairs
    assert got["decode.moe.zero_assignments"] \
        + got["decode.moe.real_assignments"] == pairs
    assert 0.15 * pairs < got["decode.moe.zero_assignments"] < 0.55 * pairs
    assert 0 < got["decode.moe.held_assignments"] \
        <= got["decode.moe.real_assignments"]
    assert 0 < got["decode.moe.experts_hit"] <= 4 * 4 * (NEW - 1)
    # four layers of two sublayers, a group and the scratch group of 4
    # sequences, SEQ rows and the scratch row in whole sublane tiles,
    # 128 columns
    rows = 8 * 2 * 4 * 48
    assert REGISTRY.gauge("decode.cache.latent_sublayers").value == 8
    assert REGISTRY.gauge("decode.cache.latent_positions").value == rows
    assert REGISTRY.gauge("decode.cache.latent_bytes").value \
        == rows * 128 * 4
    assert REGISTRY.gauge("decode.latent_cache.state_bytes").value \
        == rows * 128 * 4
    assert dec.state_format.gauges(4, 1)["decode.cache.latent_sublayers"] \
        == 2
    text = dec._get_decode_fn(4, False, None).lower(
        dec._w, jnp.zeros((1, 4, PLEN), jnp.int32), *(jnp.int32(0),) * 3,
        jnp.uint32(0), jnp.float32(0), jnp.zeros((1, 4), jnp.int32),
        jnp.int32(0), jnp.int32(0), *dec._init_state()).as_text()
    assert "latent_attend" in text
    # Kimi's ring: one sublayer a layer, counted as before
    graph_k = kimi_k2_tiny(seq_len=SEQ, vocab=VOCAB)
    PipelinedDecoder(graph_k, graph_k.init(jax.random.key(0)), num_stages=1,
                     microbatch=4, max_len=SEQ)
    assert REGISTRY.gauge("decode.cache.latent_sublayers").value == 5
    assert REGISTRY.gauge("decode.cache.latent_positions").value \
        == 5 * 2 * 4 * 48


def test_the_shortcut_branch_is_named_in_the_lowered_step(model):
    """``jax.named_scope("shortcut_moe")`` around the MoE: a trace tells
    its fusions from the dense path's."""
    graph, params = model
    op, p = graph.nodes["block_0"].op, params["block_0"]
    fmt = op.memory_format(64, SEQ, jnp.float32)
    cache = fmt.layer(fmt.zeros(2, 1), 0)
    lowered = jax.jit(lambda x, c: op.decode(p, x, c, jnp.int32(3), fmt)) \
        .lower(jnp.zeros((2, 64)), cache)
    text = lowered.as_text(debug_info=True)
    assert "shortcut_moe" in text


def test_the_block_declares_its_memory_and_its_widths(model):
    graph, _ = model
    op = graph.nodes["block_0"].op
    assert isinstance(op, LongcatFlashBlock) and isinstance(op, LatentBlock)
    assert isinstance(op, LatentAttention) and isinstance(op, DecoderBlock)
    assert op.memory == "latent_cache" and op.sublayers == 2
    parts = decoder_parts(graph, 2, SEQ)
    assert parts.decode_stats == STATS
    assert parts.memory == ("latent_cache",) * 4
    assert parts.geometry == ((4, 4, 24),) * 4
    fmt = op.memory_format(64, SEQ, jnp.bfloat16, groups=2)
    assert isinstance(fmt, latent_cache.LatentCacheFormat)
    assert (fmt.latent, fmt.rope, fmt.groups, fmt.sublayers) == (32, 8, 2, 2)
    assert fmt.scale == pytest.approx(24 ** -0.5)
    assert set(fmt.buffers(4)) == {"latent", "latent_1"}
    assert fmt.state_bytes(4, 1) == 2 * 3 * 4 * 48 * 128 * 2
    # Kimi's format is the one-buffer format it was
    kimi = kimi_k2_tiny().nodes["block_1"].op
    assert isinstance(kimi, KimiMoeBlock) and isinstance(kimi,
                                                         LatentAttention)
    assert kimi.sublayers == 1 and kimi.q_scale == kimi.latent_scale == 1.0
    assert kimi.memory_format(64, SEQ, jnp.bfloat16).keys == ("latent",)
    # the published widths: the expanded heads pass the dense halves
    full = longcat_flash(1, 6144, 64, 1536, 512, 128, 64, 128, 12288, 64,
                         256, 512, 256, 12, 2048, routed_scale=6.0,
                         experts_held=(0, 16))
    big = full.nodes["block_0"].op
    assert big.widest(6144) == 64 * 256 > 12288
    assert big.geometry(6144) == (64, 64, 192)
    assert big.q_scale == pytest.approx(2.0)
    assert big.latent_scale == pytest.approx(3.4641, rel=1e-4)
    assert big.softmax_scale == pytest.approx(192 ** -0.5)
    spec = full.nodes["block_0"].param_spec
    assert spec["router"]["w"].shape == (6144, 768)
    assert spec["router"]["bias"].shape == (768,)
    assert spec["experts"]["gate"].shape == (16, 6144, 2048)
    assert spec["ffn_1"]["down"]["w"].shape == (12288, 6144)
    assert spec["attn_1"]["k_up"]["w"].shape == (64, 128, 512)
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(spec))
    assert count == 638_874_368 + 16 * 37_748_736
    with pytest.raises(ValueError, match="no range of 16 routed experts"):
        longcat_flash_tiny(experts_held=(12, 20)).nodes["block_0"].op.held


@pytest.mark.parametrize("kwargs, words", [
    ({"beam_width": 2}, "beam search re-parents.*keep a latent_cache "
     ".LatentCacheFormat."),
    ({"kv_cache": "int8"}, "quantizes cached key and value rows.*"
     "latent cache"),
], ids=["beam", "int8"])
def test_what_a_latent_cache_cannot_do_is_refused_by_message(model, kwargs,
                                                             words):
    graph, params = model
    with pytest.raises(ValueError, match=words):
        PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                         max_len=SEQ, **kwargs)


def test_the_serving_engine_refuses_the_block_by_name(model):
    graph, params = model
    with pytest.raises(TypeError, match=r"block_0 \(LongcatFlashBlock\) is "
                       "not a CausalTransformerBlock"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


# -- Kimi's programs through the shared half -------------------------------------------

#: sha256 (16 digits) of the ring's lowered programs of ``kimi_k2_tiny``
#: as ``scripts/lowered_text_hashes.py`` lowers them, recorded on the
#: parent commit (f2c0c8e, PR 56): the attention half moved into
#: ``models/latent_attention.py`` and ``LatentBlock.decode`` became a
#: round a sublayer, and Kimi's programs lower byte for byte.  A PR that
#: changes them on purpose records them anew and says so: PR 60 did the
#: two-stage pair's — the ring's cut goes by what a stage reads a step,
#: and Kimi's five blocks lie 3 | 2 where the even rule laid the odd
#: one beside the head (2 | 3), and the shorter stage touches the layer
#: it lacks (``LayeredState.idle``); one stage is cut nowhere and lowers
#: as it did.
PARENT_KIMI_SHA = {
    "ring.kimi_k2_tiny.buffer.beam1.stages1.decode.greedy":
        "08e62c95a36e92ac",
    "ring.kimi_k2_tiny.buffer.beam1.stages1.prefill.greedy":
        "dee3d5be46af6873",
    "ring.kimi_k2_tiny.buffer.beam1.stages2.decode.greedy":
        "21b530164ad1d4fa",
    "ring.kimi_k2_tiny.buffer.beam1.stages2.prefill.greedy":
        "c3bd8c070e586a74",
}


@pytest.mark.parametrize("stages", [1, 2])
def test_kimis_programs_lower_as_on_the_parent(stages):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    try:
        from lowered_text_hashes import ring_programs
    finally:
        sys.path.pop(0)
    got = {name: hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
           for name, lowered in ring_programs(
               "kimi_k2_tiny", kimi_k2_tiny(), (stages,), ("buffer",), (1,))
           if name.endswith(".greedy")}
    assert got == {name: sha for name, sha in PARENT_KIMI_SHA.items()
                   if f"stages{stages}" in name}
