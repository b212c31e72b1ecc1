"""The ring's prefill in pieces and its format a layer: a group whose
prompt's widest activation passes the piece limit crosses each stage a
few sequences at a time, for every family and every kind of memory, and
a graph of like layers keeps one format.  (Out of ``test_decode.py``,
whose wall time these cases were a third of.)
"""

import numpy as np
import pytest

import jax

from defer_tpu.models import gpt_tiny
from defer_tpu.runtime.decode import PipelinedDecoder

from test_decode import _family

VOCAB = 97
MAX_LEN = 24


@pytest.fixture(scope="module")
def model():
    graph = gpt_tiny(seq_len=MAX_LEN, vocab=VOCAB)
    params = graph.init(jax.random.key(7))
    return graph, params


@pytest.mark.parametrize("stages", [1, 2])
@pytest.mark.parametrize("name", ["gpt_tiny", "olmoe_tiny", "brumby_tiny",
                                  "cohere_moe_tiny"])
def test_a_prefill_in_pieces_gives_the_whole_prefills_tokens(
        monkeypatch, name, stages):
    """A group whose prompt's widest activation passes the piece limit
    crosses each stage a few sequences at a time (one mechanism for
    every family and every kind of memory, from shapes alone): the same
    tokens as the whole group at once, greedy and sampled."""
    import defer_tpu.runtime.decode as rt
    graph, params = _family(name)
    vocab = graph.nodes["lm_head"].out_spec.shape[-1]
    prompt = np.random.default_rng(1).integers(0, vocab, (4 * stages, 7))

    def run(limit):
        monkeypatch.setattr(rt, "_PREFILL_PIECE_BYTES", limit)
        dec = PipelinedDecoder(graph, params, num_stages=stages,
                               microbatch=4, max_len=16)
        rows = dec._prefill_rows(7)
        return rows, dec.generate(prompt, 6, prefill=True), dec.generate(
            prompt, 6, prefill=True, temperature=0.8, top_k=5, seed=11)

    whole = run(1 << 28)
    assert whole[0] == 4
    d = graph.nodes["block_0"].out_spec.shape[-1]
    for limit, rows in ((2 * 7 * d * 4, 2), (1, 1)):
        pieces = run(limit)
        assert pieces[0] == rows
        np.testing.assert_array_equal(pieces[1], whole[1])
        np.testing.assert_array_equal(pieces[2], whole[2])


def test_piece_rows_come_from_shapes_alone(model):
    """The cell sizes of the benchmark: GPT-2 XL's, OLMoE's and Brumby's
    groups cross whole (their programs are what they were), 16 prompts
    of 8192 tokens on 128 heads of 128 a sequence at a time."""
    import types
    from defer_tpu.runtime.decode import PipelinedDecoder as PD

    def rows(mb, plen, d, heads, hd, itemsize=2):
        # a block names its own widest activation (the merged heads
        # here; a state-space block its input projection's 2 E)
        op = types.SimpleNamespace(widest=lambda d_model: max(d_model,
                                                              heads * hd))
        me = types.SimpleNamespace(
            graph=types.SimpleNamespace(
                nodes={"block_0": types.SimpleNamespace(op=op)}),
            block_names=["block_0"], d_model=d, microbatch=mb,
            compute_dtype=np.dtype(np.float16 if itemsize == 2
                                   else np.float32))
        return PD._prefill_rows(me, plen)

    assert rows(8, 512, 1600, 25, 64) == 8
    assert rows(16, 1024, 2048, 16, 128) == 16
    assert rows(16, 1024, 5120, 40, 128) == 16
    assert rows(16, 8192, 4096, 128, 128) == 1
    assert rows(16, 2048, 4096, 128, 128) == 4
    assert rows(6, 8192, 4096, 128, 128, 4) == 1
    # 256 prompts of 256 tokens on a stream of 2560 whose widest
    # activation is 10240 columns: 32 sequences a piece, not all 256
    assert rows(256, 256, 2560, 80, 128) == 32
    assert rows(256, 256, 2560, 20, 128) == 128


@pytest.mark.parametrize("stages", [1, 2])
def test_one_format_a_layer_where_layers_are_alike(model, stages):
    """A graph of like layers: every local layer's format is the first's,
    and the state is what one format's ``zeros`` made."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=stages, microbatch=2,
                           max_len=MAX_LEN)
    assert len(dec.state_formats) == dec.l_max
    assert all(fmt == dec.state_format for fmt in dec.state_formats)
    _a, caches = dec._init_state()
    want = dec.state_format.zeros(2, dec.l_max, lead=(stages,))
    assert jax.tree.structure(
        {k: caches[k] for k in want}) == jax.tree.structure(want)
    for got, ref in zip(jax.tree.leaves({k: caches[k] for k in want}),
                        jax.tree.leaves(want)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
