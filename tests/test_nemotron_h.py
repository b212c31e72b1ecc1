"""Nemotron-H (as Nemotron-3-Super publishes it) on the normal path,
against the plain reference (``chipbench/reference/nemotron_h.py``) at a
tiny size: seeded random weights, two periods of ``*EMEME``, d 64, 4
query heads on 2 KV heads of 16, Mamba-2 of 8 heads x 32 in 2 B/C groups
with 16 states and a chunk of 8, 3 of 8 relu² experts of 48 in a latent
space of 32 beside a shared one of 96, routed scale 2.5, vocabulary 211
— a graph whose layers are a mixer **or** a feed-forward part, a block a
layer, one kind in three keeping no memory at all.

Tolerances.  In float32 both sides multiply in float32 in different
orders (the program runs a prompt's recurrence in the chunked matrix
form in a kernel and, decoding, a step a call; the reference scans
position by position), so logits agree to about 1e-5 of their largest.
``RTOL`` 2e-4 leaves room and stays far under what a change of the
mathematics costs: a state kept in bfloat16, one B/C group for all
heads, ``silu`` for relu² — each asserted below, by the reference's own
controls, to differ by 50 x ``RTOL`` or more.  Tokens are held by the
benchmark's own measure, ``logit_gaps``: in float32 no generated token
may sit under the reference's best at all.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.agreement import logit_gaps, rel_err
from chipbench.reference import nemotron_h as ref
from defer_tpu.models import nemotron_h_tiny
from defer_tpu.models.decoder import (DecoderBlock, MemorylessBlock,
                                      StateSpaceBlock, decoder_parts)
from defer_tpu.models.nemotron_h import (NemotronAttentionBlock,
                                         NemotronExpertBlock,
                                         NemotronMambaBlock)
from defer_tpu.obs import REGISTRY
from defer_tpu.ops import ssm
from defer_tpu.ops.kv_cache import KVCacheFormat
from defer_tpu.ops.layered import NoMemory
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

VOCAB, SEQ, PLEN, NEW = 211, 32, 11, 9
PATTERN = "*EMEME" * 2
REF = dict(layer_pattern=PATTERN, n_head=4, n_kv=2, head_dim=16,
           mamba_heads=8, d_state=16, groups=2, top_k=3, held=(0, 8),
           routed_scale=2.5, eps=1e-5)
REF_CFG = {"module": "chipbench.reference.nemotron_h", "args": REF}
RTOL = 2e-4
KINDS = ("kv_cache", None, "ssm", None, "ssm", None) * 2
STATS = ("moe.assignments", "moe.held_assignments", "moe.experts_hit",
         "moe.load_max", "ssm.updates", "moe.latent_rows")


@pytest.fixture(scope="module")
def model():
    graph = nemotron_h_tiny(seq_len=SEQ, vocab=VOCAB)
    return graph, graph.init(jax.random.key(3))


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


def _through_memory(graph, params, seqs, plen, groups=1):
    """Logits ``[n, t - plen + 1, vocab]`` of the blocks driven as the
    ring drives them, each through its own layer's format: a prefill of
    ``seqs[:, :plen]``, then one decode step a further token
    (teacher-forced) through the states and rows the prefill left."""
    nodes = graph.nodes
    names = [nm for nm in graph.topo_order if nm.startswith("block_")]
    n, t = seqs.shape
    fmts = [nodes[nm].op.memory_format(64, t, jnp.float32, groups=groups)
            for nm in names]
    embed = nodes["embeddings"].op

    def head(x):
        return nodes["lm_head"].op.apply(
            params["lm_head"],
            nodes["final_ln"].op.apply(params["final_ln"], x))

    @jax.jit
    def run(seqs):
        x = embed.apply(params["embeddings"], seqs[:, :plen])
        layers = []
        for nm, fmt in zip(names, fmts):
            x, layer = nodes[nm].op.prefill(
                params[nm], x, fmt.layer(fmt.zeros(n, 1), 0), fmt,
                fmt.prefill_slot(True, 0))
            layers.append(layer)
        out = [head(x[:, -1])]
        for pos in range(plen, t):
            x = embed.embed_at(params["embeddings"], seqs[:, pos], pos)
            for i, (nm, fmt) in enumerate(zip(names, fmts)):
                x, layers[i] = nodes[nm].op.decode(
                    params[nm], x, layers[i], jnp.int32(pos), fmt,
                    fmt.decode_slot(True, jnp.int32(pos)), 0, {})
            out.append(head(x))
        return jnp.stack(out, axis=1)

    return run(jnp.asarray(seqs))


# -- the full-sequence graph, and the reference against itself -------------------

def test_full_sequence_logits_match_the_reference(model, ids):
    graph, params = model
    want = ref.logits(params, ids, **REF)
    assert rel_err(_forward(graph, params, ids), want) < RTOL


@pytest.mark.parametrize("control", [
    dict(one_bc_group=True),
    dict(activation="silu"), dict(activation="relu"),
    dict(norm_one_group=True), dict(drop_last=True),
    dict(bias_in_weights=True), dict(rotation_theta=10000.0),
    dict(routed_scale=1.0)], ids=lambda c: "-".join(c))
def test_the_tolerance_tells_each_control_apart(model, ids, control):
    """The reference with one thing changed — one B/C group for all
    heads, ``silu`` or a plain relu for relu², the gated norm over all channels as one group, the last choice dropped,
    the bias let into the weights, a rotation let into the attention,
    the routed scale 1 — is another model by far more than ``RTOL``."""
    graph, params = model
    if "bias_in_weights" in control:
        # a seeded bias of 0.001 is too small to see; a trained one is not
        params = dict(params, **{
            nm: dict(p, router=dict(p["router"],
                                    bias=p["router"]["bias"] * 300))
            for nm, p in params.items() if "router" in p})
    other = ref.logits(params, ids, **dict(REF, **control))
    assert rel_err(_forward(graph, params, ids), other) > 50 * RTOL


@pytest.mark.parametrize("state_dtype, least, most", [
    (None, 0.0, 1e-5), (jnp.bfloat16, 1e-3, 1.0)], ids=["f32", "bf16"])
def test_the_references_recurrence_is_its_explicit_sum(state_dtype, least,
                                                       most):
    """The oracle against itself, with groups: the recurrence position
    by position holds the closed form's state; rounded to bfloat16 after
    every position it does not — at 32 positions of seeded decays a
    bfloat16 state hides under ``RTOL`` in the logits, and this sum over
    96 positions under a decay near 1 is what fails it (the benchmark's
    long-memory probe, at the cell's size)."""
    rng = np.random.default_rng(0)
    b, t, nh, p, n, g = 2, 96, 4, 8, 16, 2
    dt = jnp.asarray(rng.uniform(0.0, 0.02, (b, t, nh)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, t, nh, p)), jnp.float32)
    bm, cm = (jnp.asarray(rng.normal(size=(b, t, g, n)), jnp.float32)
              for _ in range(2))
    a = -jnp.asarray(rng.uniform(0.25, 1.0, (nh,)), jnp.float32)
    _, h = ref.selective_scan(dt, x, bm, cm, a, state_dtype=state_dtype)
    assert least <= rel_err(h, ref.explicit_state(dt, x, bm, a)) <= most


# -- the ring against the reference -----------------------------------------------

@pytest.mark.parametrize("groups", [1, 2], ids=["one-stage", "two-stages"])
def test_prefill_then_decode_gives_the_references_logits(model, ids, groups):
    """The blocks through their formats as a ring of ``groups`` stages
    builds them (the group axis included): a prefill, then decode steps
    through the states, windows and rows it left, against the
    reference's full forward pass, by logits."""
    graph, params = model
    got = _through_memory(graph, params, ids[:, :PLEN + NEW], PLEN, groups)
    want = ref.logits(params, ids[:, :PLEN + NEW], lo=PLEN - 1, **REF)
    assert got.shape == want.shape == (4, NEW + 1, VOCAB)
    assert rel_err(got, want) < RTOL


def test_prefill_then_decode_is_the_references_full_forward(model, ids,
                                                            generated):
    """Every token the ring generates is the reference's own argmax at
    its position (float32: no token sits under the best at all)."""
    _, params = model
    out, _ = generated
    assert out.shape == (4, PLEN + NEW)
    np.testing.assert_array_equal(out[:, :PLEN], ids[:, :PLEN])
    assert logit_gaps(params, out, PLEN, REF_CFG).max() <= 0


def test_the_ring_leaves_the_memory_the_reference_holds(model, generated):
    """After the prefill and ``NEW - 1`` decode steps the ring's buffers,
    layer by layer: an ``M`` layer's ``H`` and window and the ``*``
    layer's rows are the reference's after the same tokens; an ``E``
    layer has no entry under any key."""
    _, params = model
    out, dec = generated
    want = ref.states(params, out[:, :-1], **REF)
    assert dec.memory == KINDS
    assert set(dec.state) == {"k", "v", "conv", "h"}
    t = PLEN + NEW - 1
    for l, kind in enumerate(KINDS):
        if kind is None:
            assert want[l] is None
            assert all(dec.state[key][l] is None for key in dec.state)
        elif kind == "kv_cache":
            assert dec.state["h"][l] is None
            fmt = dec.state_formats[l]
            rows = fmt.head_major({key: dec.state[key][l][0, 0]
                                   for key in ("k", "v")})
            for key, ref_rows in zip(("k", "v"), want[l]):
                assert rel_err(rows[key][:, :, :t], ref_rows) < RTOL
        else:
            assert dec.state["k"][l] is None
            h, window = ssm.dense(dec.state["h"][l][0, 0],
                                  dec.state["conv"][l][0, 0], heads=8)
            assert h.shape == (4, 8, 32, 16)
            assert window.shape == (4, 3, 256 + 2 * 2 * 16)
            assert rel_err(h, want[l][0]) < RTOL
            assert rel_err(window, want[l][1]) < RTOL


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
    assert dec.memory == KINDS[:6] and dec.l_max == 6
    np.testing.assert_array_equal(
        dec.generate(ids[:, :PLEN], NEW, prefill=prefill, token_chunk=2),
        generated[0])


def test_a_cut_inside_a_period_is_refused(model):
    graph, params = model
    with pytest.raises(ValueError, match="whole period"):
        PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                         max_len=SEQ, cut=[5, 7])
    # the three kinds are the pattern's: a memory-less layer at the
    # place of a mixer is as much off the pattern as two unlike mixers
    with pytest.raises(ValueError, match="keeps"):
        PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                         max_len=SEQ, cut=[7, 5])
    parts = decoder_parts(graph, 2, SEQ, cut=[6, 6])
    assert [len(b) for b in parts.stage_blocks] == [6, 6]
    assert parts.memory == KINDS


@pytest.mark.parametrize("kwargs, words", [
    (dict(kv_cache="int8"), "state-space state"),
    (dict(beam_width=2), "cannot hand one sequence's memory")])
def test_what_a_state_cannot_do_is_refused_by_message(model, kwargs, words):
    graph, params = model
    with pytest.raises(ValueError, match=words):
        PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                         max_len=SEQ, **kwargs)


def test_the_serving_engine_refuses_the_block_by_name(model):
    graph, params = model
    with pytest.raises(TypeError, match=r"block_0 \(NemotronAttentionBlock\) "
                       "is not a CausalTransformerBlock"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


# -- a memory-less block -----------------------------------------------------------

def test_a_memoryless_block_allocates_nothing(model, generated):
    """An ``E`` layer's format has no key, no buffer and no byte; the
    ring's state holds None at its place under every key, its gauges
    name no kind for it, and what it is handed as its memory comes back
    as it went."""
    graph, params = model
    _, dec = generated
    op = graph.nodes["block_1"].op
    assert isinstance(op, MemorylessBlock) and op.memory is None
    assert op.geometry(64) is None
    fmt = op.memory_format(64, SEQ, jnp.float32, groups=2)
    assert fmt == NoMemory(groups=2) and fmt.keys == ()
    assert fmt.buffers(4) == {} and fmt.state_bytes(4, 1) == 0
    assert fmt.zeros(4, 3) == {} and fmt.layer({"k": (1, 2)}, 1) == {}
    state = {"k": (1, None)}
    assert fmt.with_layer(state, 1, {}) == state and fmt.idle({}) == {}
    assert fmt.decode_slot(True, 3) is None
    assert fmt.prefill_slot(True, 0, 2) is None
    # int8 rows are other layers' business: nothing to refuse here
    assert op.memory_format(64, SEQ, jnp.float32, quantized=True) \
        == NoMemory()
    x = jnp.ones((3, 64), jnp.float32)
    marker = {}
    out, back = op.decode(params["block_1"], x, marker, 5, fmt)
    assert back is marker
    np.testing.assert_allclose(out, op.feed_forward(params["block_1"], x))
    assert REGISTRY.gauge("decode.memoryless_layers").value == 6
    assert not any("None" in name or ".none." in name
                   for name in REGISTRY.snapshot())
    assert sum(fmt.keys == () for fmt in dec.state_formats) == 6


def test_a_memoryless_layer_beside_kv_layers_takes_beams_and_int8():
    """Without a state in the graph nothing is refused: beams re-parent
    the attention layers' rows and pass the memory-less layers by."""
    graph = nemotron_h_tiny(seq_len=SEQ, vocab=VOCAB, layer_pattern="*E*E")
    params = graph.init(jax.random.key(1))
    ids = np.random.default_rng(2).integers(0, VOCAB, (2, 5)).astype(np.int32)
    want = PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                            max_len=SEQ).generate(ids, 6)
    beams = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                             max_len=SEQ, beam_width=2)
    assert beams.generate(ids[:1], 6).shape == (1, 11)
    quant = PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                             max_len=SEQ, kv_cache="int8")
    assert (quant.generate(ids, 6) == want).mean() > 0.8


# -- the shares of an E layer --------------------------------------------------------

def _share(params, lo, hi):
    ex = params["experts"]
    return dict(params, experts={k: v[lo:hi] for k, v in ex.items()})


def test_the_four_shares_add_up_to_the_uncut_layer(model, ids):
    """Four chips each hold a quarter of an ``E`` layer's experts; the
    up-projection is linear, so their up-projected latent sums, with the
    shared expert and the residual counted once, are the uncut layer."""
    graph, params = model
    op = graph.nodes["block_1"].op
    p = params["block_1"]
    x = jax.random.normal(jax.random.key(9), (24, 64), jnp.float32)
    h = x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + 1e-5)
    whole = op.branch(p, h)
    import dataclasses
    parts, held_pairs = [], 0
    shared = jnp.dot(jnp.square(jax.nn.relu(h @ p["shared_up"]["w"])),
                     p["shared_down"]["w"])
    for lo in range(0, 8, 2):
        share = dataclasses.replace(op, experts_held=(lo, lo + 2))
        sown = {}
        parts.append(share.branch(_share(p, lo, lo + 2), h, sown) - shared)
        held_pairs += int(sown["moe.held_assignments"])
        assert int(sown["moe.assignments"]) == 24 * 3
    assert held_pairs == 24 * 3
    assert rel_err(sum(parts) + shared, whole) < 1e-5
    # and the reference's share is the program's
    want, _, _, latent = ref.expert_branch(
        _share(p, 2, 4), x[None], top_k=3, held=(2, 4), routed_scale=2.5,
        eps=1e-5)
    share = dataclasses.replace(op, experts_held=(2, 4))
    assert rel_err(share.branch(_share(p, 2, 4), h), want[0]) < RTOL
    got_latent, u = share.latent_sum(_share(p, 2, 4), h)
    assert got_latent.shape == u.shape == (24, 32)
    assert rel_err(got_latent, latent[0]) < RTOL


def test_a_ring_of_shares_is_the_references_share(ids):
    graph = nemotron_h_tiny(seq_len=SEQ, vocab=VOCAB, experts_held=(2, 6))
    params = graph.init(jax.random.key(4))
    assert params["block_1"]["experts"]["up"].shape == (4, 32, 48)
    assert params["block_1"]["router"]["w"].shape == (64, 8)
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    out = dec.generate(ids[:, :PLEN], NEW, prefill=True, token_chunk=4)
    cfg = {"module": REF_CFG["module"], "args": dict(REF, held=(2, 6))}
    assert logit_gaps(params, out, PLEN, cfg).max() <= 0


# -- counters, gauges, declarations ----------------------------------------------------

def test_the_counters_and_gauges_by_kind(model, ids):
    graph, params = model
    names = [f"decode.{s}" for s in STATS]
    before = {nm: REGISTRY.counter(nm).n for nm in names}
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    dec.generate(ids[:, :PLEN], NEW, prefill=True)
    done = {nm: REGISTRY.counter(nm).n - before[nm] for nm in names}
    steps = NEW - 1             # the prefill made the first token
    assert done["decode.moe.assignments"] == 4 * 3 * 6 * steps
    assert done["decode.moe.held_assignments"] == 4 * 3 * 6 * steps
    assert done["decode.ssm.updates"] == 4 * 4 * steps
    assert done["decode.moe.latent_rows"] == 4 * 6 * steps
    assert 0 < done["decode.moe.experts_hit"] <= 8 * 6 * steps
    gauge = {nm: REGISTRY.gauge(nm).value for nm in (
        "decode.ssm.bc_groups", "decode.moe.latent_width",
        "decode.memoryless_layers", "decode.ssm.state_bytes",
        "decode.ssm.conv_bytes", "decode.kv_cache.state_bytes")}
    assert gauge["decode.ssm.bc_groups"] == 2
    assert gauge["decode.moe.latent_width"] == 32
    assert gauge["decode.memoryless_layers"] == 6
    conv = 4 * 3 * 4 * (256 + 64) * 4
    assert gauge["decode.ssm.conv_bytes"] == conv
    assert gauge["decode.ssm.state_bytes"] == 4 * 4 * 16 * 256 * 4 + conv
    assert gauge["decode.kv_cache.state_bytes"] > 0


def test_the_blocks_declare_their_memory(model):
    graph, _ = model
    ops = [graph.nodes[f"block_{i}"].op for i in range(12)]
    for op, kind in zip(ops, PATTERN):
        want = {"*": NemotronAttentionBlock, "E": NemotronExpertBlock,
                "M": NemotronMambaBlock}[kind]
        assert type(op) is want and isinstance(op, DecoderBlock)
        assert tuple(op.decode_stats) == STATS
    attn, moe, mamba = ops[0], ops[1], ops[2]
    assert isinstance(mamba, StateSpaceBlock)
    fmt = mamba.memory_format(64, SEQ, jnp.float32, groups=2)
    assert fmt == ssm.SsdFormat(8, 32, 16, 4, 8, jnp.float32, groups=2,
                                bc_groups=2)
    assert fmt.conv_width == 256 + 2 * 2 * 16 == mamba.conv_width
    assert mamba.mixer_width == 256 + 320 + 8 and mamba.widest(64) == 584
    assert isinstance(attn.memory_format(64, SEQ, jnp.float32),
                      KVCacheFormat)
    assert attn.geometry(64) == (4, 2, 16)
    assert moe.widest(64) == 96 and moe.held == (0, 8)
    # no second half anywhere: a mixer's parameters end at its output
    # projection, an E layer's hold no mixer
    assert set(graph.init(jax.random.key(0))["block_2"]) == {
        "ln", "in_proj", "conv", "ssm", "gate_norm", "out_proj"}


def test_the_pattern_names_every_layer():
    with pytest.raises(ValueError, match="layer kind"):
        nemotron_h_tiny(layer_pattern="*EMX")
    graph = nemotron_h_tiny(layer_pattern="MEM*E")
    kinds = [graph.nodes[f"block_{i}"].op.memory for i in range(5)]
    assert kinds == ["ssm", None, "ssm", "kv_cache", None]
