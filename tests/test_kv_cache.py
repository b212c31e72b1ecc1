"""The KV cache's format (``defer_tpu/ops/kv_cache.py``): its buffers,
its three writes, its attention — and that nothing outside it knows the
layout: both decode engines give the same tokens over a format that
keeps the same rows in another axis order.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from defer_tpu.models import gpt_tiny
from defer_tpu.ops import kv_cache
from defer_tpu.ops.kv_cache import KVCacheFormat, quantize_rows, write_kv_rows
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine, DecodeRequest

KV, HD, L = 2, 8, 9


def _fmt(quantized=False, groups=None):
    return KVCacheFormat(KV, HD, L, jnp.float32, quantized=quantized,
                         groups=groups)


def _random_layer(fmt, batch, rng):
    """One layer's buffers filled with noise (int8 rows, small scales)."""
    out = {}
    for key, s in fmt.buffers(batch).items():
        if s.dtype == jnp.int8:
            out[key] = jnp.asarray(rng.integers(-127, 128, s.shape), jnp.int8)
        elif len(key) == 2:     # a scale
            out[key] = jnp.asarray(rng.uniform(0.01, 0.1, s.shape),
                                   jnp.float32)
        else:
            out[key] = jnp.asarray(rng.standard_normal(s.shape), jnp.float32)
    return out


def _dequantized(item):
    """[b, kv, L, hd] float keys and values of an item without groups."""
    k, v = (np.asarray(item[key], np.float32) for key in ("k", "v"))
    if "ks" in item:
        k = k * np.asarray(item["ks"])[..., None]
        v = v * np.asarray(item["vs"])[..., None]
    return k, v


# -- rows and buffers ---------------------------------------------------------

def test_quantize_row_roundtrip():
    rng = np.random.default_rng(0)
    row = jnp.asarray(rng.standard_normal((3, 2, 7, 16)) * 5)
    q, s = quantize_rows(row)
    assert q.dtype == jnp.int8 and s.shape == (3, 2, 7)
    dq = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    err = np.abs(dq - np.asarray(row))
    bound = np.abs(np.asarray(row)).max(-1) / 127.0 * 0.5 + 1e-7
    assert (err <= bound[..., None] + 1e-5).all()


@pytest.mark.parametrize("quantized", [False, True], ids=["buffer", "int8"])
@pytest.mark.parametrize("groups", [None, 3], ids=["slots", "groups"])
def test_buffers_and_state_of_both_holders(quantized, groups):
    """Slots alone: exactly the positions.  With groups: a scratch group
    and a scratch row more.  A state is one buffer a layer under each
    key, behind the holder's own axes."""
    fmt = _fmt(quantized, groups)
    bufs = fmt.buffers(5)
    assert tuple(bufs) == fmt.keys
    assert fmt.keys == (("k", "v", "ks", "vs") if quantized else ("k", "v"))
    lead, length = ((), L) if groups is None else ((groups + 1,), L + 1)
    for key in ("k", "v"):
        assert bufs[key].shape == lead + (5, KV, length, HD)
        assert bufs[key].dtype == (jnp.int8 if quantized else jnp.float32)
    if quantized:
        assert bufs["ks"].shape == bufs["vs"].shape == lead + (5, KV, length)
        assert bufs["ks"].dtype == jnp.float32
    if groups is not None:
        assert (fmt.scratch_group, fmt.scratch_position) == (groups, L)
    state = fmt.zeros(5, 4, lead=(2,))
    state["mine"] = jnp.ones(3)         # a holder's own entry
    for key, s in bufs.items():
        assert [b.shape for b in state[key]] == [(2,) + s.shape] * 4
        assert all(b.dtype == s.dtype and not b.any() for b in state[key])
    layer = fmt.layer(state, 2)
    assert tuple(layer) == fmt.keys
    marked = {key: buf + 1 for key, buf in layer.items()}
    new = fmt.with_layer(state, 2, marked)
    assert new["mine"] is state["mine"]
    for key in fmt.keys:
        assert [int(b.min()) for b in new[key]] == [0, 0, 1, 0]


# -- the three writes ---------------------------------------------------------

@pytest.mark.parametrize("quantized", [False, True], ids=["buffer", "int8"])
def test_write_position_and_attend_are_the_cache_half_of_a_step(quantized):
    """The format half of a block's one-token step: the rows of the new
    columns fit one position of every buffer, land at ``pos`` and touch
    nothing else, and attention over the item is plain softmax attention
    over the (dequantized) live positions."""
    fmt, rng = _fmt(quantized), np.random.default_rng(5)
    b, pos, heads = 3, 4, 4             # two query heads a KV head
    layer = _random_layer(fmt, b, rng)
    k_new, v_new = (jnp.asarray(rng.standard_normal((b, KV * HD)),
                                jnp.float32) for _ in range(2))
    rows = fmt.rows(k_new, v_new)
    assert {key: r.shape for key, r in rows.items()} == \
        {key: c.shape[:2] + (1,) + c.shape[3:] for key, c in layer.items()}
    got, item = fmt.write_position(layer, rows, pos)
    for key, c in layer.items():
        want = c.at[:, :, pos: pos + 1].set(rows[key].astype(c.dtype))
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want))
        assert item[key] is got[key]    # without groups: its own item
    if not quantized:
        np.testing.assert_array_equal(
            np.asarray(got["k"][:, :, pos]).reshape(b, KV * HD),
            np.asarray(k_new))

    q = jnp.asarray(rng.standard_normal((b, heads * HD)), jnp.float32)
    y = fmt.attend(q, item, pos)
    k, v = _dequantized(item)
    qh = np.asarray(q).reshape(b, KV, heads // KV, HD)
    att = np.einsum("bkgd,bkld->bkgl", qh, k[:, :, : pos + 1]) / math.sqrt(HD)
    att = np.exp(att - att.max(-1, keepdims=True))
    att /= att.sum(-1, keepdims=True)
    want = np.einsum("bkgl,bkld->bkgd", att, v[:, :, : pos + 1])
    np.testing.assert_allclose(np.asarray(y), want.reshape(b, heads * HD),
                               rtol=2e-5, atol=2e-5)


def test_attend_takes_each_sequences_own_position():
    fmt, rng = _fmt(), np.random.default_rng(6)
    layer = _random_layer(fmt, 3, rng)
    q = jnp.asarray(rng.standard_normal((3, 2 * KV * HD)), jnp.float32)
    pos = jnp.asarray([0, 5, L - 1], jnp.int32)
    got = fmt.attend(q, layer, fmt.live_to(pos))
    for i, p in enumerate(pos):
        want = fmt.attend(q[i: i + 1],
                          {key: c[i: i + 1] for key, c in layer.items()},
                          int(p))
        np.testing.assert_allclose(np.asarray(got[i: i + 1]),
                                   np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("quantized", [False, True], ids=["buffer", "int8"])
def test_write_position_of_a_group_touches_that_group_only(quantized):
    fmt, rng = _fmt(quantized, groups=3), np.random.default_rng(7)
    layer = _random_layer(fmt, 2, rng)
    rows = fmt.rows(*(jnp.asarray(rng.standard_normal((2, KV * HD)),
                                  jnp.float32) for _ in range(2)))
    for g, pos in ((1, 4), (fmt.scratch_group, fmt.scratch_position)):
        got, item = fmt.write_position(layer, rows, jnp.int32(pos),
                                       group=jnp.int32(g))
        for key, c in layer.items():
            want = np.asarray(c).copy()
            want[g, :, :, pos] = np.asarray(rows[key].astype(c.dtype))[:, :, 0]
            np.testing.assert_array_equal(np.asarray(got[key]), want)
            np.testing.assert_array_equal(np.asarray(item[key]), want[g])


@pytest.mark.parametrize("quantized", [False, True], ids=["buffer", "int8"])
def test_write_prefix_is_the_rows_written_one_by_one(quantized):
    """A prompt's columns written at once equal ``rows`` +
    ``write_position`` a position, int8 scales included."""
    fmt, rng = _fmt(quantized, groups=2), np.random.default_rng(8)
    b, t, g = 2, 5, 1
    layer = _random_layer(fmt, b, rng)
    k, v = (jnp.asarray(rng.standard_normal((b, t, KV * HD)), jnp.float32)
            for _ in range(2))
    got = fmt.write_prefix(layer, k, v, jnp.int32(g))
    want = layer
    for p in range(t):
        want, _ = fmt.write_position(want, fmt.rows(k[:, p], v[:, p]), p,
                                     group=g)
    for key in fmt.keys:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]))


@pytest.mark.parametrize("shape,positions", [
    ((3, 2, 16, 8), [0, 15, 7]),            # one window holds the item
    ((4, 2, 192, 8), [0, 127, 128, 191]),   # 1.5 windows: the cell's L
    ((2, 3, 300, 16), [255, 299]),
])
def test_write_kv_rows_touches_one_position_a_sequence(shape, positions):
    """The engine's row-writer: sequence i's row lands at pos[i], at
    window edges and in a partial last window too, and every other
    element keeps its bits."""
    rng = np.random.default_rng(5)
    w, kv, _, hd = shape
    cache = rng.normal(size=shape).astype(np.float32)
    rows = rng.normal(size=(w, kv, 1, hd)).astype(np.float32)
    got = jax.jit(write_kv_rows)(cache, rows,
                                 jnp.asarray(positions, jnp.int32))
    want = cache.copy()
    for i, p in enumerate(positions):
        want[i, :, p] = rows[i, :, 0]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_write_slots_is_the_row_writer_on_every_buffer():
    fmt, rng = _fmt(), np.random.default_rng(9)
    layer = _random_layer(fmt, 3, rng)
    rows = fmt.rows(*(jnp.asarray(rng.standard_normal((3, KV * HD)),
                                  jnp.float32) for _ in range(2)))
    pos = jnp.asarray([8, 0, 3], jnp.int32)
    got = fmt.write_slots(layer, rows, pos)
    for key, c in layer.items():
        want = np.asarray(c).copy()
        for i, p in enumerate(pos):
            want[i, :, int(p)] = np.asarray(rows[key])[i, :, 0]
        np.testing.assert_array_equal(np.asarray(got[key]), want)
    with pytest.raises(NotImplementedError, match="unquantized"):
        _fmt(quantized=True).write_slots(layer, rows, pos)


def test_reparent_moves_one_groups_rows_in_every_layer():
    fmt, rng = _fmt(quantized=True, groups=2), np.random.default_rng(10)
    state = {key: tuple(_random_layer(fmt, 4, rng)[key] for _ in range(2))
             for key in fmt.keys}
    state["beam_cum"] = jnp.ones((2, 4))
    parents = jnp.asarray([1, 1, 3, 2], jnp.int32)
    got = fmt.reparent(state, jnp.int32(1), parents)
    assert got["beam_cum"] is state["beam_cum"]
    for key in fmt.keys:
        for old, new in zip(state[key], got[key]):
            want = np.asarray(old).copy()
            want[1] = want[1][np.asarray(parents)]
            np.testing.assert_array_equal(np.asarray(new), want)


# -- another format in its place ------------------------------------------------

class PositionsLeading(KVCacheFormat):
    """The same rows kept positions-first (``[positions, (groups,) batch,
    kv_heads(, head_dim)]``): what a holder that indexed a buffer's axes
    itself, or built one from a shape of its own, could not run on."""

    @property
    def _axis(self):            # where the real format keeps positions
        return 2 if self.groups is None else 3

    def _out(self, layer, axis=None):
        axis = self._axis if axis is None else axis
        return {key: jnp.moveaxis(buf, axis, 0)
                for key, buf in layer.items()}

    def _in(self, layer):
        return {key: jnp.moveaxis(buf, 0, self._axis)
                for key, buf in layer.items()}

    def buffers(self, batch):
        def first(s):
            shape = list(s.shape)
            shape.insert(0, shape.pop(self._axis))
            return jax.ShapeDtypeStruct(tuple(shape), s.dtype)
        return {key: first(s) for key, s in super().buffers(batch).items()}

    def write_position(self, layer, rows, pos, group=None):
        layer, item = super().write_position(self._in(layer), rows, pos,
                                             group)
        return self._out(layer), self._out(item, 2)

    def write_slots(self, layer, rows, pos):
        return self._out(super().write_slots(self._in(layer), rows, pos))

    def write_prefix(self, layer, k, v, group):
        return self._out(super().write_prefix(self._in(layer), k, v, group))

    def reparent(self, state, group, parents):
        def each(fn, st):
            return {key: tuple(fn({key: b})[key] for b in bufs)
                    if key in self.keys else bufs for key, bufs in st.items()}
        return each(self._out,
                    super().reparent(each(self._in, state), group, parents))

    @staticmethod
    def attend(q, item, pos):
        return KVCacheFormat.attend(
            q, {key: jnp.moveaxis(buf, 0, 2) for key, buf in item.items()},
            pos)


@pytest.fixture(scope="module")
def model():
    graph = gpt_tiny(seq_len=24)
    return graph, graph.init(jax.random.key(3))


def _prompts(n, plen):
    return np.random.default_rng(4).integers(0, 97, (n, plen)).astype(np.int32)


@pytest.mark.parametrize("kv", ["buffer", "int8"])
@pytest.mark.parametrize("num_stages,beam", [(1, 1), (4, 1), (2, 2)])
def test_the_ring_runs_on_a_format_with_positions_leading(
        model, monkeypatch, num_stages, beam, kv):
    """The ring builds, shards, writes and reads its caches through the
    format alone: with another layout in the format's place (no program
    option: the class is replaced on its module) every path gives the
    tokens it gives on the real one — fused prefill, teacher-forced
    prompts, beam search's re-parenting."""
    graph, params = model
    prompt = _prompts(8 // beam, 5)
    calls = [dict(prefill=True, token_chunk=2), {}] if beam == 1 else [{}]

    def tokens():
        dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                               microbatch=8 // num_stages, max_len=24,
                               kv_cache=kv, beam_width=beam)
        return dec, [dec.generate(prompt, 6, **kw) for kw in calls]

    _, want = tokens()
    monkeypatch.setattr(kv_cache, "KVCacheFormat", PositionsLeading)
    dec, got = tokens()
    assert isinstance(dec.kv_format, PositionsLeading)
    _, caches = dec._init_state()
    assert caches["k"][0].shape[1] == 24 + 1     # [stage, positions, ...]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)


def test_the_engine_runs_on_a_format_with_positions_leading(
        model, monkeypatch):
    """Slots at three different positions in one step, joined at
    different steps: the same answers over the other layout."""
    graph, params = model
    prompts = [_prompts(1, n)[0] for n in (3, 7, 5)]
    join_at = {0: 0, 1: 2, 2: 5}

    def answers():
        eng = ContinuousBatchEngine(graph, params, num_stages=2, width=3)
        seen = []

        def stagger(e, queue):
            while queue and e.steps >= join_at[queue[0].request_id]:
                e.join(queue.pop(0))
            seen.append({s.pos for s in e._slots if s is not None})

        out = eng.run_all(
            [DecodeRequest(prompt=p, max_new_tokens=6, request_id=i)
             for i, p in enumerate(prompts)], joiner=stagger)
        assert any(len(ps) == 3 for ps in seen)
        return eng, out

    _, want = answers()
    monkeypatch.setattr(kv_cache, "KVCacheFormat", PositionsLeading)
    eng, got = answers()
    assert eng._caches["k"][0].shape == (24, 3, 2, 16)
    for rid, ids in want.items():
        np.testing.assert_array_equal(got[rid], ids)
