"""The Mellum 2 family (models/mellum.py): window layers on ring buffers
under plain RoPE beside full layers under YaRN, 4 KV heads of grouped
queries, a softmax router renormalised over the chosen, every expert
held — held to the plain reference (chipbench/reference/mellum.py), on
the ring and off it; and the rotation probe of the cell's check
(chipbench/drivers/batch_decode_rotary_window_moe.py), which must fail
each way a rotation can be wrong."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.agreement import rel_err
from defer_tpu import models
from defer_tpu.models import mellum, mellum_tiny, rotary
from defer_tpu.models.mellum import FULL_LAYER, WINDOW_LAYER, MellumBlock
from defer_tpu.models.olmoe import rope
from defer_tpu.obs import REGISTRY
from defer_tpu.ops.routed import route_top_k
from defer_tpu.runtime.decode import PipelinedDecoder

ref = importlib.import_module("chipbench.reference.mellum")
drv = importlib.import_module(
    "chipbench.drivers.batch_decode_rotary_window_moe")

VOCAB, WINDOW, SEQ, PLEN = 211, 8, 64, 20
PATTERN = (WINDOW_LAYER,) * 3 + (FULL_LAYER,)
YARN = dict(factor=4.0, original=32, beta_fast=2.0, beta_slow=0.5)
REF = dict(n_layer=8, n_head=8, n_kv=4, head_dim=32, top_k=2,
           layer_types=PATTERN, window=WINDOW, eps=1e-6, theta=10000.0,
           yarn=YARN)
REF_CFG = {"module": "chipbench.reference.mellum", "args": REF}
#: float32 on both sides; what is left is the order of the sums (the
#: program's online softmax over blocks, its products' own order) and
#: the table's last bit (numpy on the host against jax.numpy), times
#: positions up to 63: measured 2e-6 at the most over this file's
#: cases, and a fault of the kinds below moves logits by 1e-2 and more
RTOL = 2e-5
#: the tiny preset's limit on the rotation probe: float32, 64 positions;
#: the program reads under 1e-5, the least of the faults 0.05
PROBE_TOL = 1e-3


def make(seed=0):
    graph = mellum_tiny(SEQ, VOCAB)
    params = graph.init(jax.random.key(seed))
    # wider embedding rows and a sharper router, as the benchmark's
    # init_gain makes them: tokens differ at the router
    params = dict(params, embeddings={"wte": params["embeddings"]["wte"] * 50})
    for i in range(8):
        blk = dict(params[f"block_{i}"])
        blk["router"] = {"w": blk["router"]["w"] * 4}
        params[f"block_{i}"] = blk
    return graph, params


def ref_logits(params, ids, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.logits(params, ids, **dict(REF, **kw))


@pytest.fixture(scope="module")
def tiny():
    return make()


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, VOCAB, (4, SEQ)).astype(
        np.int32)


def gaps(params, seqs, plen):
    """How far the reference's logit of each generated token of ``seqs``
    sits under the reference's best, over the position's spread."""
    lg = np.asarray(ref_logits(params, seqs[:, :-1], lo=plen - 1))
    picked = np.take_along_axis(lg, seqs[:, plen:, None], -1)[..., 0]
    best = lg.max(-1)
    return (best - picked) / (best - lg.mean(-1))


# -- the model against the reference -------------------------------------------

def test_full_sequence_logits_match_the_reference(tiny, ids):
    graph, params = tiny
    got = jnp.stack([jax.jit(graph.apply)(params, row) for row in ids])
    assert rel_err(got, ref_logits(params, ids)) < RTOL


def _through_the_caches(graph, params, seqs, plen, stages):
    """Every position's logits from ``plen - 1`` on, the blocks' two
    halves composed as the ring composes them: a prompt through
    ``prefill`` (the flash kernels' math, rows bulk-written, a window
    layer's into its ring buffer), then a token a step through
    ``decode`` (the rotated key written at its row, the format's
    attention), on ``stages`` stages' worth of groups (group 1)."""
    nodes = graph.nodes
    fmts = [nodes[f"block_{i}"].op.memory_format(64, SEQ, jnp.float32,
                                                 groups=stages)
            for i in range(8)]
    caches = [f.layer(f.zeros(seqs.shape[0], 1), 0) for f in fmts]
    group = stages - 1

    def head(x):
        h = nodes["final_ln"].op.apply(params["final_ln"], x)
        return nodes["lm_head"].op.apply(params["lm_head"], h)

    x = nodes["embeddings"].op.apply(params["embeddings"], seqs[:, :plen])
    for i, fmt in enumerate(fmts):
        x, caches[i] = nodes[f"block_{i}"].op.prefill(
            params[f"block_{i}"], x, caches[i], fmt,
            fmt.prefill_slot(True, group))
    got = [head(x)[:, -1:]]
    for pos in range(plen, seqs.shape[1]):
        x = nodes["embeddings"].op.embed_at(params["embeddings"],
                                            seqs[:, pos], pos)
        for i, fmt in enumerate(fmts):
            x, caches[i] = nodes[f"block_{i}"].op.decode(
                params[f"block_{i}"], x, caches[i], jnp.int32(pos), fmt,
                fmt.decode_slot(True, jnp.int32(pos)), group)
        got.append(head(x)[:, None])
    return jnp.concatenate(got, axis=1)


@pytest.mark.parametrize("stages", [1, 2])
def test_prefill_then_cached_steps_give_the_references_logits(tiny, ids,
                                                              stages):
    """A prompt of 20 (two and a half windows, under YaRN's
    ``original`` 32) through ``prefill``, then 30 tokens through
    ``decode`` to position 49 (six windows; past ``original``): every
    position's *logits* are the reference's full forward's, which sees
    no cache — in a one-group format and in group 1 of two."""
    graph, params = tiny
    seqs = ids[:, :PLEN + 30]
    want = ref_logits(params, seqs, lo=PLEN - 1)
    got = _through_the_caches(graph, params, seqs, PLEN, stages)
    assert rel_err(got, want) < RTOL


@pytest.mark.parametrize("stages,chunk,prefill", [
    (1, None, True), (1, 4, True), (2, None, True), (2, 3, True),
    (1, 5, False), (2, None, False)])
def test_ring_tokens_are_the_references_argmax(tiny, ids, stages, chunk,
                                               prefill):
    """The ring itself, on one stage and on two of one period each:
    prefill (20 positions), then 36 decode steps to position 55.  Every
    token it hands out is the reference's own argmax, teacher-forced on
    the ring's tokens, to 1e-4 of the position's spread (float32 both:
    a near-tie closer than that may break either way)."""
    graph, params = tiny
    dec = PipelinedDecoder(graph, params, num_stages=stages,
                           microbatch=4 // stages, max_len=SEQ)
    out = dec.generate(ids[:, :PLEN], 36, prefill=prefill, token_chunk=chunk)
    assert out.shape == (4, PLEN + 36)
    np.testing.assert_array_equal(out[:, :PLEN], ids[:, :PLEN])
    assert float(gaps(params, out, PLEN).max()) < 1e-4


@pytest.mark.parametrize("fault", [
    {"window": WINDOW - 1}, {"window": WINDOW + 1},
    {"yarn": dict(YARN, factor=1.0, attention_factor=ref.layer_rotation(
        FULL_LAYER, head_dim=32, theta=1e4, yarn=YARN)[1])},
    {"yarn": dict(YARN, attention_factor=1.0)},
    {"yarn": dict(YARN, beta_fast=1.0)}],
    ids=["window-1", "window+1", "plain-table", "no-factor", "ramp"])
def test_a_fault_shows_against_the_reference(tiny, ids, fault):
    """The reference under another window, with the full layers turned
    by the plain table, without the attention factor or with a ramp that
    starts a pair later differs from the program by far more than the
    program differs from the reference as published."""
    graph, params = tiny
    got = jax.jit(graph.apply)(params, ids[0])
    assert rel_err(got, ref_logits(params, ids[:1], **fault)[0]) > 100 * RTOL


def test_the_programs_blocks_make_the_references_choices(tiny, ids):
    graph, params = tiny
    _, want = ref_logits(params, ids[:2], experts=True)
    x = graph.nodes["embeddings"].op.apply(params["embeddings"], ids[:2])
    for i in range(8):
        sown: dict = {}
        x, _k, _v = graph.nodes[f"block_{i}"].op.apply_with_kv(
            params[f"block_{i}"], x, sow=sown)
        got = np.sort(np.asarray(sown["moe.chosen"]).reshape(2, SEQ, 2), -1)
        assert (got == np.sort(np.asarray(want[i]), -1)).mean() > 0.99
        assert int(sown["moe.assignments"]) == 2 * SEQ * 2
        assert 0 < int(sown["moe.experts_hit"]) <= 8


def test_the_router_is_a_softmax_renormalised_over_the_chosen():
    """``softmax_of_chosen`` on the logits is the published rule — a
    softmax over all experts, the ``k`` largest, their probabilities
    over their own sum — number for number (the reference spells the
    published one)."""
    logits = jax.random.normal(jax.random.key(0), (64, 8)) * 3
    eid, w = route_top_k(logits, 2, scoring="softmax_of_chosen")
    chosen, weight = ref.route(logits, 2)
    np.testing.assert_array_equal(np.asarray(eid), np.asarray(chosen))
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(np.asarray(weight),
                                          np.asarray(chosen), -1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 1.0, rtol=1e-6)


# -- the rotations ---------------------------------------------------------------

def test_yarn_lives_in_one_place_for_both_families():
    kimi = importlib.import_module("defer_tpu.models.kimi_k2")
    own = importlib.import_module("defer_tpu.models.mellum")
    assert kimi.yarn_inv_freq is rotary.yarn_inv_freq is own.yarn_inv_freq
    assert models.mellum is mellum and models.mellum_tiny is mellum_tiny
    assert {"mellum", "mellum_tiny"} <= set(models.__all__)


def test_yarn_at_the_published_numbers():
    """Mellum2's table: pairs 0-18 keep their frequency, 35-63 turn 16
    times slower, the ramp between; the factor is the config's
    ``attention_factor``."""
    assert rotary.yarn_ramp(128, 500000.0, 8192, 32.0, 1.0) == (18, 35)
    got = np.asarray(rotary.yarn_inv_freq(128, 500000.0, 16.0, 8192))
    plain = 500000.0 ** (-2 * np.arange(64) / 128)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all(np.diff(got / plain)[18:35] < 0)
    np.testing.assert_allclose(
        got, np.asarray(ref.yarn_frequencies(128, 500000.0, 16.0, 8192,
                                             32.0, 1.0)), rtol=2e-6)
    assert rotary.yarn_attention_factor(16.0) == pytest.approx(
        1.2772588722239782, rel=1e-12)
    assert rotary.yarn_attention_factor(1.0) == 1.0
    # the graph hands each kind its own: a table and a factor to the
    # full layers, neither to the window layers
    g = mellum_tiny()
    ops = [g.nodes[f"block_{i}"].op for i in range(8)]
    assert [op.window for op in ops] == [8, 8, 8, None] * 2
    assert all(op.rope_freqs is None and op.rope_factor == 1.0
               for op in ops if op.window is not None)
    full = ops[3]
    assert full.rope_freqs == rotary.yarn_inv_freq(32, 1e4, 4.0, 32, 2.0, 0.5)
    assert full.rope_factor == pytest.approx(0.1 * np.log(4.0) + 1)
    explicit = mellum(4, 64, 8, 4, 32, 16, VOCAB, 8, 2, 32, PATTERN, 8,
                      rope_factor=4.0, attention_factor=1.5)
    assert explicit.nodes["block_3"].op.rope_factor == 1.5


@pytest.mark.parametrize("kind", ["window", "full"])
def test_a_blocks_rotation_is_the_references(tiny, kind):
    """``MellumBlock.rotate`` at positions up to 63 against the
    reference's rotate-half by the kind's table and factor; position 0
    multiplies by the factor and turns nothing."""
    op = tiny[0].nodes["block_0" if kind == "window" else "block_3"].op
    x = jax.random.normal(jax.random.key(1), (2, SEQ, 4, 32))
    freqs, c = ref.layer_rotation(
        WINDOW_LAYER if kind == "window" else FULL_LAYER, head_dim=32,
        theta=1e4, yarn=YARN)
    got = op.rotate(x, jnp.arange(SEQ))
    want = ref.rotate(x.transpose(0, 2, 1, 3), jnp.arange(SEQ), freqs,
                      c).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[:, 0]), c * np.asarray(x[:, 0]),
                               atol=1e-6)
    # the plain call is what it was: OLMoE's and Brumby's
    np.testing.assert_array_equal(
        np.asarray(rope(x, jnp.arange(SEQ), 1e4)),
        np.asarray(rope(x, jnp.arange(SEQ), 1e4, None, 1.0)))


def _probe(tiny, kind, **control):
    graph = tiny[0]
    op = graph.nodes["block_0" if kind == "window" else "block_3"].op
    freqs, c = ref.layer_rotation(
        WINDOW_LAYER if kind == "window" else FULL_LAYER, head_dim=32,
        theta=1e4, yarn=YARN)
    kw = dict(freqs=freqs, c=c)
    kw.update(control)
    return drv.rotation_probe(7, op, d_model=64, positions=SEQ,
                              dtype=jnp.float32, ref=ref, steps=8,
                              sequences=2, **kw)


@pytest.mark.parametrize("kind", ["window", "full"])
def test_the_rotation_probe_passes_the_program(tiny, kind):
    assert _probe(tiny, kind) < PROBE_TOL


FAULTS = {
    "plain-table": lambda: dict(freqs=ref.plain_frequencies(32, 1e4)),
    "ramp+1": lambda: dict(freqs=ref.yarn_frequencies(
        32, 1e4, 4.0, 32, 2.0, 0.5, shift=1)),
    "ramp-1": lambda: dict(freqs=ref.yarn_frequencies(
        32, 1e4, 4.0, 32, 2.0, 0.5, shift=-1)),
    "no-factor": lambda: dict(c=1.0),
    "interleaved": lambda: dict(pairing="interleaved"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_rotation_probe_fails_each_fault_of_a_full_layer(tiny, fault):
    """Held to a reference that turns the full layer by the plain table,
    by a ramp a pair off either way, without the attention factor or
    over interleaved pairs — the program's fault seen from the other
    side — the probe reads far over its limit."""
    assert _probe(tiny, "full", **FAULTS[fault]()) > 20 * PROBE_TOL


def test_the_rotation_probe_fails_the_wrong_pairing_in_a_window_layer(tiny):
    assert _probe(tiny, "window", pairing="interleaved") > 20 * PROBE_TOL


def test_the_rotation_probe_fails_a_window_layer_under_the_full_table(tiny):
    """And the other way round: a window layer turned by YaRN's table."""
    freqs, _c = ref.layer_rotation(FULL_LAYER, head_dim=32, theta=1e4,
                                   yarn=YARN)
    assert _probe(tiny, "window", freqs=freqs) > 20 * PROBE_TOL


def test_the_rotation_probes_take_a_layer_of_each_kind(tiny):
    out = drv.rotation_probes(3, tiny[0], positions=SEQ,
                              dtype=jnp.float32, ref_cfg=REF_CFG, steps=8)
    assert set(out) == {"window", "full"} and max(out.values()) < PROBE_TOL


# -- memory, names, counters -------------------------------------------------

def test_a_window_block_keeps_window_rows_and_names_its_kernels(tiny):
    graph, params = tiny
    dec = PipelinedDecoder(graph, params, num_stages=2, microbatch=2,
                           max_len=SEQ)
    assert len(dec.state_formats) == 4
    assert [fmt.window for fmt in dec.state_formats] == [WINDOW] * 3 + [None]
    assert [fmt.kernel_suffix for fmt in dec.state_formats] \
        == ["_window"] * 3 + ["_full"]
    lengths = [fmt.buffers(2)["k"].shape[3] for fmt in dec.state_formats]
    assert lengths == [WINDOW + 1] * 3 + [SEQ + 1]   # and the scratch row
    # 8 queries a KV head stand on the matrix unit's boundary: joined
    # buffers at the published geometry, plain ones at the tiny one's 2
    big = MellumBlock(32, 64, 8, 896, num_kv_heads=4, head_dim=128,
                      window=1024)
    fmt = big.memory_format(2304, 28672, jnp.bfloat16, groups=1)
    assert fmt.joined and fmt.query_group == 8
    assert fmt.buffers(16)["k"].shape == (2, 16, 1040, 512)
    full = MellumBlock(32, 64, 8, 896, num_kv_heads=4,
                       head_dim=128).memory_format(
        2304, 28672, jnp.bfloat16, groups=1)
    assert full.buffers(16)["k"].shape == (2, 16, 28688, 512)
    assert not dec.state_formats[0].joined
    # the widest activation a token has: its 2 sorted expert rows
    assert graph.nodes["block_0"].op.widest(64) == 8 * 32
    assert big.widest(2304) == 8 * 2304


def test_a_trace_tells_the_kinds_attention_apart(tiny):
    """The cache kernels' names in a lowered step: a window layer's
    ``kv_attend_window``, a full layer's ``kv_step_full`` (at this
    preset's small heads the full layers write inside the attention),
    and at the published geometry ``kv_attend_full``."""
    graph, _ = tiny
    for name, want in (("block_0", "kv_attend_window"),
                       ("block_3", "kv_step_full")):
        op = graph.nodes[name].op
        fmt = op.memory_format(64, SEQ, jnp.float32, groups=1)
        layer = fmt.layer(fmt.zeros(2, 1), 0)
        q = jnp.zeros((2, 8 * 32))
        rows = fmt.rows(jnp.zeros((2, 4 * 32)), jnp.zeros((2, 4 * 32)))
        text = str(jax.make_jaxpr(
            lambda q, layer, rows, fmt=fmt: fmt.step(
                q, layer, rows, jnp.int32(3), group=0))(q, layer, rows))
        assert want in text
    for window, want in ((None, "kv_attend_full"), (16, "kv_attend_window")):
        fmt = MellumBlock(16, 8, 2, 32, num_kv_heads=2, head_dim=128,
                          window=window).memory_format(
            256, 64, jnp.bfloat16, groups=1)
        layer = fmt.layer(fmt.zeros(2, 1), 0)
        text = str(jax.make_jaxpr(lambda q, layer, fmt=fmt: fmt.attend(
            q, layer, jnp.int32(3), group=0))(
                jnp.zeros((2, 16 * 128), jnp.bfloat16), layer))
        assert want in text and fmt.joined


def test_counters_and_gauges_add_up_over_a_generation(tiny, ids):
    graph, params = tiny
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=4,
                           max_len=SEQ)
    names = ("assignments", "experts_hit", "load_max")
    before = {n: REGISTRY.counter(f"decode.moe.{n}").n for n in names}
    dec.generate(ids[:, :PLEN], 9, prefill=True, token_chunk=4)
    got = {n: REGISTRY.counter(f"decode.moe.{n}").n - before[n]
           for n in names}
    # 8 decode steps x 8 layers x 4 rows x 2 experts a token
    assert got["assignments"] == 8 * 8 * 4 * 2
    assert 8 * 8 <= got["experts_hit"] <= 8 * 8 * 8
    assert got["load_max"] <= got["assignments"]
    # the step that made the last position (28) read the 28 rows before
    # it in the two full layers, a window's 8 in the six others
    assert REGISTRY.gauge("decode.cache.full_rows_read").value == 4 * 2 * 28
    assert REGISTRY.gauge("decode.cache.window_rows_read").value \
        == 4 * 6 * WINDOW
    assert REGISTRY.gauge("decode.cache.window_positions").value == WINDOW
    assert [fmt.window for fmt in dec._row_readers] == [8, 8, 8, None] * 2


def test_a_family_without_a_window_posts_no_rows_by_kind(ids):
    """The two gauges tell a window layer's rows from a full layer's:
    a decoder none of whose formats has a window sets neither."""
    graph = models.olmoe_tiny(SEQ, VOCAB)
    dec = PipelinedDecoder(graph, graph.init(jax.random.key(0)),
                           num_stages=1, microbatch=4, max_len=SEQ)
    assert dec._row_readers == ()
    for kind in ("full", "window"):
        REGISTRY.gauge(f"decode.cache.{kind}_rows_read").set(-1)
    dec.generate(ids[:, :PLEN], 5, prefill=True, token_chunk=4)
    for kind in ("full", "window"):
        assert REGISTRY.gauge(f"decode.cache.{kind}_rows_read").value == -1


def test_a_stage_whose_kinds_do_not_repeat_is_refused(tiny):
    graph, params = tiny
    with pytest.raises(ValueError, match="same kinds of memory in the same "
                                         "order"):
        PipelinedDecoder(graph, params, num_stages=4, microbatch=1,
                         max_len=SEQ, cut=[2, 2, 2, 2])
    # left to the bytes, every stage opens where the pattern does: the
    # full layer closes a stage of three, a window layer is one alone
    dec = PipelinedDecoder(graph, params, num_stages=4, microbatch=1,
                           max_len=SEQ)
    assert [len(b) for b in dec.stage_blocks] == [1, 3, 1, 3]


def test_the_serving_engine_refuses_the_block(tiny):
    from defer_tpu.serve.engine import ContinuousBatchEngine
    graph, params = tiny
    with pytest.raises((TypeError, ValueError), match="CausalTransformerBlock"
                       "|GPT|gpt"):
        ContinuousBatchEngine(graph, params, num_stages=1, width=2)


def test_the_graph_builder_checks_its_layer_types():
    with pytest.raises(ValueError, match="layer type"):
        mellum(4, 64, 8, 4, 32, 16, VOCAB, 8, 2, 32, ("chunked_attention",),
               8)
