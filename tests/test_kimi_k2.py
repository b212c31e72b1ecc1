"""The Kimi K2 family (models/kimi_k2.py): latent attention — one row a
position, a step in the latent space, a prompt over the expanded heads —
in front of a dense layer and of routed experts chosen by a biased
sigmoid, a share of them held: held to the plain reference
(chipbench/reference/kimi_k2.py), on the ring and off it."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.agreement import logit_gaps, rel_err
from defer_tpu.models import kimi_k2, kimi_k2_tiny
from defer_tpu.models.decoder import DecoderBlock, LatentBlock, decoder_parts
from defer_tpu.models.kimi_k2 import (KimiDenseBlock, KimiMoeBlock,
                                      yarn_inv_freq, yarn_softmax_scale)
from defer_tpu.obs import REGISTRY
from defer_tpu.ops import latent_cache
from defer_tpu.ops.flash_attention import flash_latent
from defer_tpu.ops.routed import SCORING_RULES, route_top_k
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

ref = importlib.import_module("chipbench.reference.kimi_k2")

VOCAB, SEQ, PLEN, NEW = 211, 32, 11, 9
REF = dict(n_layer=5, n_head=4, nope=16, rope=8, latent=32, top_k=4,
           routed_scale=2.827, theta=50000.0, factor=4.0, original=8,
           held=(0, 4), eps=1e-5)
REF_CFG = {"module": "chipbench.reference.kimi_k2", "args": REF}
RTOL = 2e-4
STATS = ("moe.assignments", "moe.held_assignments", "moe.experts_hit",
         "moe.load_max")


def make(held=(0, 4), seed=3):
    graph = kimi_k2_tiny(seq_len=SEQ, vocab=VOCAB, experts_held=held)
    params = graph.init(jax.random.key(seed))
    # wider embedding rows and a bias large enough to turn choices:
    # tokens differ at the router
    params = dict(params,
                  embeddings={"wte": params["embeddings"]["wte"] * 50})
    for i in range(1, 5):
        blk = dict(params[f"block_{i}"])
        blk["router"] = dict(blk["router"], bias=blk["router"]["bias"] * 30)
        params[f"block_{i}"] = blk
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


# -- the full-sequence graph against the reference ---------------------------------

def test_full_sequence_logits_match_the_reference(model, ids):
    graph, params = model
    want, _ = ref_forward(params, ids)
    assert rel_err(_forward(graph, params, ids), want) < RTOL


def test_the_reference_tells_a_wrong_scale_and_a_weighing_bias(model, ids):
    """The controls' two rules are other models: ``sigma`` without ``m **
    2`` and a bias that weighs both move the logits."""
    graph, params = model
    got = _forward(graph, params, ids)
    for control in ({"plain_scale": True}, {"bias_weighs": True}):
        assert rel_err(got, ref_forward(params, ids, **control)[0]) \
            > 20 * RTOL


def test_prefill_then_cached_steps_give_the_references_logits(model, ids):
    """The blocks' two halves as the ring composes them: a prompt of
    ``PLEN`` through ``prefill`` (expanded heads, rows bulk-written),
    then 16 tokens through ``decode`` (absorbed queries over the latent
    cache, group 1 of two): every position's *logits* are the
    reference's full forward's, which sees no cache."""
    graph, params = model
    nodes = graph.nodes
    steps = 16
    seqs = ids[:, :PLEN + steps]
    want, _ = ref_forward(params, seqs)
    fmts = [nodes[f"block_{i}"].op.memory_format(64, SEQ, jnp.float32,
                                                 groups=2)
            for i in range(5)]
    caches = [f.layer(f.zeros(4, 1), 0) for f in fmts]

    def head(x):
        h = nodes["final_ln"].op.apply(params["final_ln"], x)
        return nodes["lm_head"].op.apply(params["lm_head"], h)

    x = nodes["embeddings"].op.apply(params["embeddings"], seqs[:, :PLEN])
    for i, fmt in enumerate(fmts):
        x, caches[i] = nodes[f"block_{i}"].op.prefill(
            params[f"block_{i}"], x, caches[i], fmt,
            fmt.prefill_slot(True, 1))
    got = [head(x)]
    for pos in range(PLEN, PLEN + steps):
        x = nodes["embeddings"].op.embed_at(params["embeddings"],
                                            seqs[:, pos], pos)
        for i, fmt in enumerate(fmts):
            x, caches[i] = nodes[f"block_{i}"].op.decode(
                params[f"block_{i}"], x, caches[i], jnp.int32(pos), fmt,
                fmt.decode_slot(True, jnp.int32(pos)), 1)
        got.append(head(x)[:, None])
    assert rel_err(jnp.concatenate(got, axis=1), want) < RTOL


@pytest.mark.parametrize("name", ["block_0", "block_1"],
                         ids=["dense", "routed"])
def test_the_absorbed_step_equals_the_expanded_form(model, name):
    """One layer, float32: the last position of the expanded
    full-sequence forward is the absorbed step of that token over the
    rows of the positions before it, to 1e-5."""
    graph, params = model
    op, p = graph.nodes[name].op, params[name]
    t = 13
    x = jax.random.normal(jax.random.key(7), (3, t, 64), jnp.float32)
    want, rows = op.apply_with_rows(p, x)
    fmt = op.memory_format(64, SEQ, jnp.float32)
    cache = fmt.write_prefix(fmt.layer(fmt.zeros(3, 1), 0),
                             rows[:, :t - 1], 0)
    got, cache = op.decode(p, x[:, -1], cache, jnp.int32(t - 1), fmt)
    assert rel_err(got, want[:, -1]) < 1e-5
    # and the step wrote the row the prompt's path would have
    np.testing.assert_allclose(cache["latent"][:, t - 1, :fmt.width],
                               rows[:, t - 1], rtol=1e-5, atol=1e-6)


# -- the latent cache's format and its kernel --------------------------------------

@pytest.mark.parametrize("groups", [None, 2], ids=["slots", "ring"])
@pytest.mark.parametrize("positions", [40, 300])
def test_latent_attend_is_the_einsum(groups, positions):
    """Ragged lengths (a sequence a position, the first at 0), rows past
    a sequence's position holding NaN — the scratch row and another
    group's among them — and, with groups, the second of two."""
    fmt = latent_cache.LatentCacheFormat(32, 8, positions, jnp.float32,
                                         0.21, groups=groups)
    b, heads = 3, 4
    k1, k2 = jax.random.split(jax.random.key(positions))
    layer = fmt.layer(fmt.zeros(b, 1), 0)
    shape = layer["latent"].shape
    assert shape[-1] == 128 and shape[-2] % 16 == 0
    pos = jnp.asarray([0, positions // 3, positions - 1])
    rows = jax.random.normal(k1, shape[-3:], jnp.float32)
    rows = rows.at[..., fmt.width:].set(0.0)
    live = jnp.arange(shape[-2])[None, :] <= pos[:, None]
    item = jnp.where(live[..., None], rows, jnp.nan)
    if groups is None:
        layer, group = {"latent": item}, None
    else:
        layer = {"latent": jnp.full(shape, jnp.nan).at[1].set(item)}
        group = 1
    q = jax.random.normal(k2, (b, heads * fmt.width), jnp.float32)
    got = fmt.attend(q, layer, pos, group=group)
    want = latent_cache.attend_einsum(
        fmt._pad(q.reshape(b, heads, -1)), fmt.item(layer, group), pos,
        latent=32, scale=0.21)
    assert got.shape == (b, heads * 32) and not np.isnan(got).any()
    assert rel_err(got, want.reshape(b, -1)) < 1e-5


def test_a_bubble_writes_the_scratch_row_and_the_scratch_group():
    fmt = latent_cache.LatentCacheFormat(32, 8, 20, jnp.float32, 0.2,
                                         groups=2)
    layer = fmt.layer(fmt.zeros(2, 1), 0)
    assert layer["latent"].shape == (3, 2, 32, 128)
    row = jnp.ones((2, 40), jnp.float32)
    step = fmt.write_position(layer, fmt.rows(row),
                              fmt.decode_slot(False, jnp.int32(5)), group=1)
    assert float(step["latent"][1, :, 20, :40].sum()) == 80.0
    assert float(step["latent"].sum()) == 80.0
    rows = jnp.ones((1, 7, 40), jnp.float32)
    pre = fmt.write_prefix(layer, rows, fmt.prefill_slot(False, 0, 1))
    assert float(pre["latent"][2, 1, :7, :40].sum()) == 280.0
    assert float(pre["latent"].sum()) == 280.0
    # a real piece of group 0 goes to its sequence, from position 0 on
    pre = fmt.write_prefix(layer, rows, fmt.prefill_slot(True, 0, 1))
    assert float(pre["latent"][0, 1, :7, :40].sum()) == 280.0


def test_the_published_row_is_padded_to_whole_lane_tiles():
    fmt = latent_cache.LatentCacheFormat(512, 64, 12288, jnp.bfloat16,
                                         0.1447, groups=1)
    (buf,) = fmt.buffers(32).values()
    assert buf.shape == (2, 32, 12304, 640)
    assert buf.shape[-1] * buf.dtype.itemsize == 1280 <= 1.12 * 1152
    assert latent_cache.block_rows(640, 12304, 2) == 768


@pytest.mark.parametrize("t,block", [(12, 64), (150, 64), (1000, 256)],
                         ids=["12", "150", "4x4-blocks"])
def test_flash_latent_is_the_masked_softmax(t, block):
    """The prompt's kernel (interpreter mode): a shared rotated key read
    by index, a value narrower than the key; at 4 x 4 blocks of two
    lane groups the sum a lane is rescaled across a query block's
    pairs."""
    ks = jax.random.split(jax.random.key(t), 5)
    b, h = 2, 3
    q_n = jax.random.normal(ks[0], (b, h, t, 16))
    q_r = jax.random.normal(ks[1], (b, h, t, 8))
    k_n = jax.random.normal(ks[2], (b, h, t, 16))
    k_r = jax.random.normal(ks[3], (b, 1, t, 8))
    v = jax.random.normal(ks[4], (b, h, t, 12))
    got = flash_latent(q_n, q_r, k_n, k_r, v, scale=0.3, block=block)
    att = (jnp.einsum("bhqd,bhkd->bhqk", q_n, k_n)
           + jnp.einsum("bhqd,bxkd->bhqk", q_r, k_r)) * 0.3
    att = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :], att,
                    -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(att, -1), v)
    assert got.shape == (b, h, t, 12)
    assert rel_err(got, want) < 1e-5


def test_the_flash_path_is_the_xla_path(model):
    graph, params = model
    op, p = graph.nodes["block_1"].op, params["block_1"]
    x = jax.random.normal(jax.random.key(2), (2, 20, 64), jnp.float32)
    flash = type(op)(**{**vars(op), "attn_impl": "flash"})
    assert rel_err(flash.apply(p, x), op.apply(p, x)) < 1e-5


# -- the router ------------------------------------------------------------------------

def test_the_bias_changes_choices_and_never_a_weight():
    logits = jax.random.normal(jax.random.key(0), (64, 16))
    bias = 0.3 * jax.random.normal(jax.random.key(1), (16,))
    eid, w = route_top_k(logits, 4, "noaux_tc", bias=bias, scale=2.827)
    plain, _ = route_top_k(logits, 4, "noaux_tc", bias=jnp.zeros(16),
                           scale=2.827)
    assert (np.sort(eid, -1) != np.sort(plain, -1)).any(-1).mean() > 0.2
    # the chosen are the largest of p + b ...
    p = jax.nn.sigmoid(logits)
    np.testing.assert_array_equal(
        np.sort(eid, -1), np.sort(jax.lax.top_k(p + bias, 4)[1], -1))
    # ... and weigh by p alone, renormalised and scaled
    chosen = jnp.take_along_axis(p, eid, -1)
    np.testing.assert_allclose(
        w, 2.827 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 2.827, rtol=1e-6)


def test_an_unknown_rule_is_refused_with_the_rules_named():
    with pytest.raises(ValueError) as err:
        route_top_k(jnp.zeros((2, 4)), 2, "argmax")
    for rule in SCORING_RULES:
        assert repr(rule) in str(err.value)


def test_a_choice_outside_the_held_range_adds_nothing(model):
    """A layer whose router is biased away from the experts it holds
    computes the shared expert alone; biased towards them, more."""
    graph, params = model
    op, p = graph.nodes["block_1"].op, params["block_1"]
    x = jax.random.normal(jax.random.key(4), (2, 9, 64), jnp.float32)
    away = jnp.where(jnp.arange(16) < 4, -10.0, 0.0)
    sown: dict = {}
    got = op.apply(dict(p, router=dict(p["router"], bias=away)), x, sow=sown)
    assert int(sown["moe.held_assignments"]) == 0
    assert int(sown["moe.assignments"]) == 2 * 9 * 4
    assert (np.asarray(sown["moe.chosen"]) >= 4).all()
    no_experts = dict(p, experts=jax.tree.map(jnp.zeros_like, p["experts"]))
    assert rel_err(got, op.apply(no_experts, x)) < 1e-6
    assert rel_err(op.apply(p, x), op.apply(no_experts, x)) > 1e-2


def _share(params, lo, hi):
    return dict(params, experts={k: v[lo:hi]
                                 for k, v in params["experts"].items()})


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """16 experts over 4 shares, each routing over all 16 under the full
    choice's weights: their routed parts and the shared expert counted
    once are the uncut layer, the program's and the reference's."""
    graph, params = make(held=None)
    whole, p = graph.nodes["block_2"].op, params["block_2"]
    assert p["experts"]["gate"].shape[0] == 16 and whole.held == (0, 16)
    x = 0.5 * jax.random.normal(jax.random.key(9), (2, 12, 64), jnp.float32)
    y_whole = whole.apply(p, x)
    alone = whole.apply(dict(p, experts=jax.tree.map(
        jnp.zeros_like, p["experts"])), x)
    parts = []
    for lo in range(0, 16, 4):
        op = type(whole)(**{**vars(whole), "experts_held": (lo, lo + 4)})
        parts.append(op.apply(_share(p, lo, lo + 4), x))
    assert rel_err(sum(parts) - 3 * alone, y_whole) < RTOL
    assert rel_err(parts[0], y_whole) > 50 * RTOL
    args = {k: REF[k] for k in ("n_head", "nope", "rope", "latent", "top_k",
                                "routed_scale", "eps")}
    freqs = ref.yarn_frequencies(8, 50000.0, 4.0, 8, 32.0, 1.0)
    sigma = ref.softmax_scale(24, 4.0, 1.0)
    with jax.default_matmul_precision("highest"):
        want, ex = ref.block(p, x, freqs, held=None, sigma=sigma, **args)
        part, ex1 = ref.block(_share(p, 4, 8), x, freqs, held=(4, 8),
                              sigma=sigma, **args)
    assert rel_err(y_whole, want) < RTOL
    assert rel_err(parts[1], part) < RTOL
    np.testing.assert_array_equal(ex["chosen"], ex1["chosen"])
    sown: dict = {}
    whole.apply(p, x, sow=sown)
    np.testing.assert_array_equal(
        np.sort(np.asarray(sown["moe.chosen"]).reshape(2, 12, 4), -1),
        np.sort(np.asarray(ex["chosen"]), -1))


def test_the_programs_router_makes_the_references_choices_and_weights(
        model, ids):
    """``KimiMoeBlock.route`` on the reference's own normed stream: the
    same experts, the same weights (what the benchmark's check holds
    the chip's bfloat16 to)."""
    graph, params = model
    _, extras = ref_forward(params, ids, keep=("chosen", "weights",
                                               "ffn_in"))
    assert "chosen" not in extras[0]
    for i in range(1, 5):
        eid, w = graph.nodes[f"block_{i}"].op.route(
            params[f"block_{i}"], jnp.asarray(extras[i]["ffn_in"]))
        order = np.argsort(np.asarray(eid), -1)
        want = np.argsort(extras[i]["chosen"], -1)
        np.testing.assert_array_equal(
            np.take_along_axis(np.asarray(eid), order, -1),
            np.take_along_axis(extras[i]["chosen"], want, -1))
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(w), order, -1),
            np.take_along_axis(extras[i]["weights"], want, -1), rtol=1e-4)


# -- YaRN ------------------------------------------------------------------------------

def test_yarn_at_the_published_numbers():
    """``lo`` 8, ``hi`` 20: pairs 0-8 keep their frequency, pairs 20-31
    turn 64 times slower, a ramp between; the program's frequencies are
    the reference's own formula's."""
    got = np.asarray(yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0))
    want = np.asarray(ref.yarn_frequencies(64, 50000.0, 64.0, 4096, 32.0,
                                           1.0))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 50000.0 ** (-2 * np.arange(32) / 64)
    np.testing.assert_allclose(got[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(got[20:], plain[20:] / 64, rtol=1e-6)
    assert got[31] * 64 == pytest.approx(plain[31], rel=1e-6)
    assert (got[9:20] < plain[9:20]).all() \
        and (got[9:20] > plain[9:20] / 64).all()
    assert yarn_softmax_scale(192, 64.0, 1.0) == pytest.approx(0.14468,
                                                               rel=1e-4)
    assert ref.softmax_scale(192, 64.0, 1.0) == pytest.approx(0.14468,
                                                              rel=1e-4)
    # no scaling: plain RoPE and one over the width's root
    np.testing.assert_allclose(yarn_inv_freq(64, 50000.0, 1.0, 4096), plain,
                               rtol=1e-6)
    assert yarn_softmax_scale(192, 1.0) == pytest.approx(192 ** -0.5)


# -- the ring against the reference ----------------------------------------------------

def test_prefill_then_decode_is_the_references_full_forward(model, ids,
                                                            generated):
    _, params = model
    out, _ = generated
    assert out.shape == (4, PLEN + NEW)
    np.testing.assert_array_equal(out[:, :PLEN], ids[:, :PLEN])
    assert logit_gaps(params, out, PLEN, REF_CFG).max() <= 0


def test_the_ring_leaves_the_rows_the_reference_holds(model, generated):
    """After the prefill and ``NEW - 1`` decode steps a layer's buffer
    holds the reference's ``[c, k_r]`` of every token that was an input,
    the prompt's (written by the prefill) and the generated ones'
    (written a step at a time)."""
    _, params = model
    out, dec = generated
    assert dec.memory == ("latent_cache",) * 5
    _, extras = ref_forward(params, out[:, :-1], keep=("rows",))
    for l in range(5):
        buf = np.asarray(dec.state["latent"][l])
        assert buf.shape == (1, 2, 4, 48, 128)
        got = buf[0, 0, :, :PLEN + NEW - 1, :40]
        assert rel_err(got, extras[l]["rows"]) < RTOL
        assert rel_err(got[:, PLEN:], extras[l]["rows"][:, PLEN:]) < RTOL
        assert not buf[0, 0, :, :, 40:].any()


@pytest.mark.parametrize("stages, chunk, prefill", [
    (1, 3, True), (1, 4, False), (2, None, True), (2, 2, True),
    (2, None, False)])
def test_the_tokens_do_not_depend_on_stages_chunks_or_the_prefill(
        model, ids, generated, stages, chunk, prefill):
    """Two stages cut the graph into (dense, routed x 2 | routed x 2)
    — the bytes' cut: the even rule's 2 | 3 laid the odd block beside
    the head: the dense block lies at the place of the other stage's
    first routed one, each in a tree of its own."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=stages,
                           microbatch=4 // stages, max_len=SEQ)
    if stages == 2:
        assert dec.stage_blocks == [["block_0", "block_1", "block_2"],
                                    ["block_3", "block_4"]]
        assert dec._variant == [[0, 1], None, None]
        dense, routed = dec._w["blocks"][0]
        assert "gate" in dense and "router" in routed
        # a stage holds zeros at the place of the other kind
        assert not np.asarray(dense["gate"]["w"][1]).any()
        assert not np.asarray(routed["router"]["w"][0]).any()
        assert np.asarray(routed["router"]["w"][1]).any()
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
    assert params["block_1"]["router"]["w"].shape == (64, 16)
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    out = dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
    cfg = {"module": REF_CFG["module"], "args": dict(REF, held=(6, 10))}
    assert logit_gaps(params, out, PLEN, cfg).max() <= 0


# -- counters, gauges, the contract ----------------------------------------------------

def test_both_kinds_of_block_sow_one_ledger(model):
    graph, params = model
    parts = decoder_parts(graph, 2, SEQ)
    assert parts.decode_stats == STATS
    assert parts.memory == ("latent_cache",) * 5
    assert parts.geometry == ((4, 4, 24),) * 5
    x = jax.random.normal(jax.random.key(1), (1, 5, 64), jnp.float32)
    sown: dict = {}
    graph.nodes["block_0"].op.apply(params["block_0"], x, sow=sown)
    assert set(sown) == set(STATS) and not any(map(int, sown.values()))
    sown = {}
    graph.nodes["block_1"].op.apply(params["block_1"], x, sow=sown)
    assert set(sown) == set(STATS) | {"moe.chosen", "moe.weights"}


def test_the_counters_and_gauges(model, ids):
    graph, params = model
    names = ["decode." + s for s in STATS]
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    before = {nm: REGISTRY.counter(nm).n for nm in names}
    dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
    got = {nm: REGISTRY.counter(nm).n - before[nm] for nm in names}
    # the four routed layers route 4 choices a sequence a step; the
    # dense layer sows zeros
    assert got["decode.moe.assignments"] == 4 * 4 * 4 * (NEW - 1)
    assert 0 < got["decode.moe.held_assignments"] \
        < got["decode.moe.assignments"]
    layer_steps = 4 * (NEW - 1)
    assert 0 < got["decode.moe.experts_hit"] <= 4 * layer_steps
    # five layers, a group and the scratch group of 4 sequences, SEQ
    # rows and the scratch row in whole sublane tiles, 128 columns
    rows = 5 * 2 * 4 * 48
    assert REGISTRY.gauge("decode.cache.latent_positions").value == rows
    assert REGISTRY.gauge("decode.cache.latent_bytes").value \
        == rows * 128 * 4
    assert REGISTRY.gauge("decode.latent_cache.state_bytes").value \
        == rows * 128 * 4
    # the names are the format's own: the decoder spells none of them
    said = dec.state_format.gauges(4, 1)
    assert {"decode.cache.latent_bytes", "decode.cache.latent_positions"} \
        <= set(said)
    assert said["decode.cache.latent_positions"] * 5 == rows
    text = dec._get_decode_fn(4, False, None).lower(
        dec._w, jnp.zeros((1, 4, PLEN), jnp.int32), *(jnp.int32(0),) * 3,
        jnp.uint32(0), jnp.float32(0), jnp.zeros((1, 4), jnp.int32),
        jnp.int32(0), jnp.int32(0), *dec._init_state()).as_text()
    assert "latent_attend" in text


def test_the_blocks_declare_their_memory(model):
    graph, _ = model
    dense, routed = (graph.nodes[nm].op for nm in ("block_0", "block_1"))
    assert isinstance(dense, KimiDenseBlock) \
        and isinstance(routed, KimiMoeBlock)
    for op in (dense, routed):
        assert isinstance(op, LatentBlock) and isinstance(op, DecoderBlock)
        assert op.memory == "latent_cache"
        fmt = op.memory_format(64, SEQ, jnp.bfloat16, groups=2)
        assert isinstance(fmt, latent_cache.LatentCacheFormat)
        assert (fmt.latent, fmt.rope, fmt.groups) == (32, 8, 2)
        assert fmt.scale == pytest.approx(
            24 ** -0.5 * (0.1 * np.log(4.0) + 1) ** 2)
    # the widest activation: the expanded keys and values, until the
    # dense layer's hidden columns pass them (the published widths')
    assert dense.widest(64) == routed.widest(64) == 4 * 32
    full = kimi_k2(2, 7168, 64, 1536, 512, 128, 64, 128, 18432, 64, 256,
                   384, 8, 2048, routed_scale=2.827, experts_held=(0, 12),
                   rope_factor=64.0)
    big = full.nodes["block_0"].op
    assert big.widest(7168) == 18432
    assert full.nodes["block_1"].op.widest(7168) == 64 * 256
    assert big.geometry(7168) == (64, 64, 192)
    assert big.softmax_scale == pytest.approx(0.14468, rel=1e-4)


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
    with pytest.raises(TypeError, match=r"block_0 \(KimiDenseBlock\) is "
                       "not a CausalTransformerBlock"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)
